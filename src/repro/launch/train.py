"""Training driver.

CPU/example scale:
    PYTHONPATH=src python -m repro.launch.train --arch yi_6b --reduced \
        --steps 200 --data needle --seq 512 --batch 16

Cluster scale: same driver with --mesh production (the dry-run proves the
lowering; on real TPU hosts jax.distributed.initialize() picks up the pod
topology).  Features: grad accumulation, async checkpointing + --resume,
straggler watchdog, elastic re-mesh on restart.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint.checkpoint import AsyncCheckpointer
from repro.configs.base import get_config, reduced
from repro.data.synthetic import DataConfig, make_batches
from repro.distributed.fault_tolerance import StepWatchdog, elastic_mesh
from repro.distributed.sharding import (make_rules, set_rules,
                                        tree_specs)
from repro.launch.mesh import make_production_mesh
from repro.models.attention import RunFlags
from repro.optim import adamw
from repro.training import steps as ST


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data", default="needle", choices=["needle", "lm"])
    ap.add_argument("--dsa-mode", default="auto",
                    choices=["auto", "off", "faithful", "block", "kernel"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "production", "multipod"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-interval", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-interval", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    dsa_mode = args.dsa_mode
    if dsa_mode == "auto":
        dsa_mode = "block" if cfg.dsa.enabled else "off"
    flags = RunFlags(mode="train", dsa_mode=dsa_mode)

    if args.mesh == "host":
        mesh = elastic_mesh(model_parallel=1)
        rules = make_rules(fsdp=False, seq_parallel=False)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")
        rules = make_rules(multi_pod=args.mesh == "multipod")
    set_rules(rules)

    opt = adamw.OptConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(1, args.steps // 10))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed)
    data = make_batches(args.data, dcfg)

    with jax.set_mesh(mesh):
        state, state_log = ST.init_train_state(
            jax.random.PRNGKey(args.seed), cfg, opt)
        state_specs = tree_specs(state, state_log, rules, mesh)
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, jax.NamedSharding(mesh, s)),
            state, state_specs)
        step0 = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = AsyncCheckpointer(args.ckpt_dir)
            if args.resume:
                shardings = jax.tree.map(
                    lambda s: jax.NamedSharding(mesh, s), state_specs)
                restored, rstep = ckpt.restore_latest(state, shardings)
                if restored is not None:
                    state, step0 = restored, rstep
                    print(f"[resume] from step {step0}")

        train_step = jax.jit(
            ST.make_train_step(cfg, opt, flags,
                               microbatches=args.microbatches),
            in_shardings=(state_specs, None), donate_argnums=(0,))

        wd = StepWatchdog()
        t_start = time.monotonic()
        for step in range(step0, args.steps):
            batch = next(data)
            wd.start()
            state, metrics = train_step(state, batch)
            metrics = jax.device_get(metrics)
            slow = wd.stop(step)
            if slow:
                print(f"[watchdog] straggler at step {step}: "
                      f"{wd.times[-1]:.2f}s vs median {wd.median_step_s:.2f}s")
            if step % args.log_interval == 0 or step == args.steps - 1:
                print(f"step {step}: loss={metrics['loss']:.4f} "
                      f"ce={metrics['ce']:.4f} mse={metrics['mse']:.4f} "
                      f"gnorm={metrics['grad_norm']:.2f}")
            if ckpt and (step + 1) % args.save_interval == 0:
                ckpt.save(state, step + 1)
        if ckpt:
            ckpt.save(state, args.steps, block=True)
        dt = time.monotonic() - t_start
        ntok = args.steps - step0
        print(f"[done] {ntok} steps in {dt:.1f}s "
              f"({args.batch * args.seq * ntok / dt:.0f} tok/s)")
        return state, metrics


if __name__ == "__main__":
    main()
