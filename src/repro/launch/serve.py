"""Serving driver: batched prefill + decode with the Engine.

    PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --reduced \
        --batch 8 --prompt-len 128 --new-tokens 64 [--dsa] \
        [--dsa-mode block|faithful|kernel] [--loop scan|python]

``--loop scan`` (default) is the decode fast path: all new tokens are
generated in one fused on-device ``lax.scan`` dispatch.  ``--dsa-mode
kernel`` additionally routes each decode step through the fused Pallas
gather kernel (interpreted on the CPU backend).  The persistent compile
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache``.

``--continuous`` switches from one static batch to the continuous-batching
serving loop (repro.inference.scheduler): a synthetic open-loop Poisson
arrival process of ``--requests`` mixed-length requests at ``--rate``
req/s streams through a resident ``--slots``-slot engine, decoding in
fused ``--seg-len``-step segments with per-segment retirement/admission:

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b \
        --reduced --continuous --requests 16 --rate 4 --slots 4

``--mesh`` shards the resident engine over a data-parallel serving mesh of
``--dp`` devices (0 = all): the (slots, max_len) cache and every per-slot
carry shard over the "data" axis with replicated weights, and serving
stays BITWISE token-exact vs single-device.  ``--tp N`` builds a 2-D
(data, model) mesh instead and additionally shards WEIGHTS + KV heads
over the "model" axis (tensor parallelism — per-device weight bytes drop
~1/N; still token-exact, validated up front against the arch config).
Try either without accelerators via
XLA_FLAGS=--xla_force_host_platform_device_count=8.

``--nodes N --coordinator host:port --node-id I`` launches the SAME
program as one of N cooperating processes (jax.distributed.initialize):
the serving mesh then spans every node's devices, so dp x tp sharding
crosses hosts — run the identical command on each node, varying only
--node-id.

``--trace-out trace.json`` / ``--metrics-out metrics.prom`` /
``--telemetry-sample N`` enable serving telemetry
(repro.inference.telemetry): a perfetto-loadable Chrome trace of the
run's chunk bursts / decode segments / request lifecycles, a Prometheus
metrics snapshot, the compile-event log, and (with --dsa) sampled DSA
block-selection keep-rates.
"""
from __future__ import annotations

import argparse
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced
from repro.inference.config import ServingConfig
from repro.inference.engine import Engine
from repro.inference.scheduler import (ContinuousEngine, summarize,
                                       synthetic_workload)
from repro.inference.speculative import can_speculate
from repro.inference.telemetry import Telemetry
from repro.launch.mesh import init_serving_processes, make_serving_mesh
from repro.models.transformer import init_model


REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else at the fixed ``<repo>/.jax_cache`` —
    the path is part of the cache key, so it never moves.  Call before the
    first compile; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def _serving_config(cfg, args, max_len, dsa_on, mesh,
                    telemetry=None) -> ServingConfig:
    """One ServingConfig for both engines, straight from the CLI flags."""
    return ServingConfig(
        max_len=max_len, long_context=dsa_on,
        dsa_mode=args.dsa_mode if dsa_on else "off",
        cache_dtype=jnp.dtype(cfg.dtype),
        moe_prefill=args.moe_prefill, mesh=mesh, loop=args.loop,
        select_dtype=args.select_dtype if dsa_on else "float32",
        kv_quant=args.kv_quant,
        slots=args.slots or args.batch, seg_len=args.seg_len,
        spec=args.spec, max_mode_wait_s=args.max_mode_wait,
        paged=args.paged, pool_pages=args.pool_pages or None,
        deadline_s=args.deadline, queue_cap=args.queue_cap or None,
        shed_policy=args.shed_policy, telemetry=telemetry)


def _serve_continuous(cfg, args, params, config):
    eng = ContinuousEngine(cfg, params, config=config)
    if eng.mesh is not None and eng.engine.tp > 1:
        print(f"tensor parallel: tp={eng.engine.tp}, "
              f"{eng.weight_bytes_per_device() / 2**20:.2f} MiB "
              f"weights/device")
    if args.spec and not eng.spec:
        print(f"note: spec={args.spec} outside the speculation envelope "
              f"for {cfg.name}; using plain segments")
    workload = synthetic_workload(
        args.requests, rate_rps=args.rate,
        prompt_lens=(max(8, args.prompt_len // 4), args.prompt_len),
        n_new_range=(max(2, args.new_tokens // 4), args.new_tokens),
        vocab=cfg.vocab, seed=args.seed)
    eng.warmup([len(r.prompt) for r in workload])
    results = eng.serve(workload)
    # an all-shed/all-failed run completes zero requests: the wall clock
    # defaults to 0 (summarize zeroes the ok-set stats) and the lifecycle
    # line below still reports what happened instead of crashing here
    wall = max((r.finish_s for r in results), default=0.0)
    s = summarize(results, wall)
    print(f"continuous: {s['n_requests']} requests, "
          f"{s['delivered_tokens']} tokens in {s['wall_s']:.2f} s -> "
          f"{s['goodput_tok_s']:.1f} tok/s goodput, "
          f"p50 {s['p50_latency_s']:.2f} s / p95 {s['p95_latency_s']:.2f} s "
          f"latency ({int(eng.stats['segments'])} segments, "
          f"{int(eng.stats['admitted'])} admissions)")
    dropped = [f"{s[k]} {k[2:]}" for k in ("n_timeout", "n_cancelled",
                                           "n_failed", "n_shed") if s[k]]
    if dropped or args.deadline is not None or not s["n_ok"]:
        slo = (f", SLO attainment {s['slo_attainment']:.0%}"
               if args.deadline is not None else "")
        print(f"lifecycle : {s['n_ok']} ok"
              + ("".join(f", {d}" for d in dropped)) + slo)
    tel = eng.telemetry
    if tel is not None:
        if args.trace_out:
            tel.write_chrome_trace(args.trace_out)
            print(f"telemetry : {len(tel.events)} trace events -> "
                  f"{args.trace_out} (load in Perfetto / chrome://tracing)")
        if args.metrics_out:
            tel.write_prometheus(args.metrics_out)
            print(f"telemetry : Prometheus snapshot -> {args.metrics_out}")
        progs = sorted({p for p, _, _ in tel.compiles})
        print("compiles  : " + ", ".join(
            f"{p}={tel.compile_count(p)}" for p in progs))
        kr = tel.metrics.value("serving_dsa_keep_rate")
        if isinstance(kr, tuple) and kr[0]:   # plain float 0.0 = no probe
            print(f"sparsity  : {kr[0]} DSA selection samples, "
                  f"mean keep-rate {kr[1]:.2f}")
    if s["n_failed"]:
        raise SystemExit(f"{s['n_failed']} request(s) failed; last error: "
                         f"{eng.health()['last_error']}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--dsa", action="store_true",
                    help="DSA long-context decode (predicted-key cache)")
    ap.add_argument("--dsa-mode", default="block",
                    choices=["faithful", "block", "kernel"],
                    help="DSA decode path (with --dsa): token top-k | "
                         "XLA block gather | fused Pallas kernel")
    ap.add_argument("--loop", default="scan", choices=["scan", "python"],
                    help="fused on-device generation loop vs legacy "
                         "per-token host loop")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching serving loop over an "
                         "open-loop Poisson arrival process")
    ap.add_argument("--slots", type=int, default=0,
                    help="resident slots for --continuous (default: --batch)")
    ap.add_argument("--seg-len", type=int, default=16,
                    help="decode steps per fused segment (--continuous)")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic requests to serve (--continuous)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate, requests/s (--continuous)")
    ap.add_argument("--spec", type=int, default=0,
                    help="speculative decoding: K draft tokens verified "
                         "per fused dispatch (0 = off; token-exact)")
    ap.add_argument("--moe-prefill", default="capacity",
                    choices=["capacity", "dense"],
                    help="MoE prefill routing: 'dense' makes prefill "
                         "token-exact with chunk/decode steps (enables "
                         "chunked admission for MoE archs)")
    ap.add_argument("--paged", action="store_true",
                    help="page the resident KV cache: block-table "
                         "indirection over a shared refcounted page pool "
                         "(+ copy-on-write prefix reuse for requests "
                         "declaring prefix_len)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="physical pages in the paged pool (0 = enough "
                         "for every slot at max_len)")
    ap.add_argument("--select-dtype", default="float32",
                    choices=["float32", "int8"],
                    help="DSA selection precision (with --dsa): int8 stores "
                         "the predicted-key caches quantized with per-row "
                         "scales and runs the selection matmul int8xint8")
    ap.add_argument("--kv-quant", default=None, choices=["int8", "fp8"],
                    help="quantized K/V cache storage dtype with per-row "
                         "scales, dequantized on gather (default: off; "
                         "gathered top-k attention stays full precision)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request latency budget in seconds "
                         "(--continuous): requests retire with status "
                         "'timeout' past it (default: no deadlines)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="bounded admission queue for --continuous "
                         "(0 = unbounded); overflow sheds per "
                         "--shed-policy with status 'shed'")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "oldest", "lowest-priority"],
                    help="whom to shed when the queue is at --queue-cap")
    ap.add_argument("--max-mode-wait", type=float, default=None,
                    help="seconds a queued other-dsa_mode request may "
                         "wait before forcing a drain/mode-switch "
                         "(--continuous; default: wait for natural idle)")
    ap.add_argument("--mesh", action="store_true",
                    help="shard the engine over a data-parallel serving "
                         "mesh (slots axis over 'data'; bitwise-exact)")
    ap.add_argument("--dp", type=int, default=0,
                    help="devices in the serving mesh (with --mesh; "
                         "0 = all visible devices)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards: builds a 2-D "
                         "(data, model) serving mesh and shards weights + "
                         "KV heads over 'model' (validated against the "
                         "arch config; token-exact vs unsharded)")
    ap.add_argument("--nodes", type=int, default=1,
                    help="cooperating processes for a multi-controller "
                         "launch (jax.distributed.initialize; run the "
                         "same command on every node)")
    ap.add_argument("--coordinator", default="127.0.0.1:12321",
                    help="host:port of node 0 for --nodes > 1")
    ap.add_argument("--node-id", type=int, default=0,
                    help="this process's index in [0, --nodes)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON timeline of the "
                         "--continuous run here (perfetto-loadable; "
                         "enables telemetry)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a Prometheus text-format metrics snapshot "
                         "of the --continuous run here (enables telemetry)")
    ap.add_argument("--telemetry-sample", type=int, default=0,
                    help="sample the DSA block selection once per N decode "
                         "segments (> 0 enables telemetry even without "
                         "--trace-out/--metrics-out; default 0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    # multi-controller: every process enumerates the GLOBAL device set
    # after this, so it must run before any jax device use below
    if args.nodes > 1:
        init_serving_processes(args.coordinator, args.nodes, args.node_id)
        print(f"node {args.node_id}/{args.nodes}: "
              f"{jax.local_device_count()} local / "
              f"{jax.device_count()} global devices")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params, _ = init_model(jax.random.PRNGKey(args.seed), cfg)
    max_len = args.max_len or (args.prompt_len + args.new_tokens + 16)
    dsa_on = args.dsa and cfg.dsa.enabled
    if args.paged:
        page = cfg.dsa.block_k if dsa_on else 16
        max_len = -(-max_len // page) * page
    mesh = (make_serving_mesh(args.dp, tp=args.tp, cfg=cfg)
            if (args.mesh or args.dp or args.tp > 1) else None)
    if mesh is not None:
        print(f"serving mesh: {dict(mesh.shape)} over "
              f"{len(mesh.devices.flat)} devices")
    tel = None
    if args.trace_out or args.metrics_out or args.telemetry_sample:
        tel = Telemetry(sample_every=args.telemetry_sample or 16)
    config = _serving_config(cfg, args, max_len, dsa_on, mesh,
                             telemetry=tel)
    if args.continuous:
        return _serve_continuous(cfg, args, params, config)
    eng = Engine(cfg, params, config=config)
    if mesh is not None and eng.tp > 1:
        print(f"tensor parallel: tp={eng.tp}, "
              f"{eng.weight_bytes_per_device() / 2**20:.2f} MiB "
              f"weights/device")
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(1, cfg.vocab - 4,
                           size=(args.batch, args.prompt_len)).astype(np.int32)
    extras = {}
    if cfg.enc_dec:
        extras["enc_x"] = rng.normal(
            size=(args.batch, cfg.enc_seq_len, cfg.d_model)).astype(np.float32)
    if cfg.cross_attn_period:
        extras["img"] = rng.normal(
            size=(args.batch, cfg.n_image_tokens, cfg.d_model)).astype(np.float32)
    spec = args.spec
    if spec and not can_speculate(cfg, eng.decode_flags.dsa_mode, spec):
        print(f"note: spec={spec} outside the speculation envelope for "
              f"{cfg.name}; using plain decode")
        spec = 0
    res = eng.generate(prompts, args.new_tokens, extras=extras or None,
                       spec=spec)
    print(f"prefill: {res.prefill_s*1e3:.1f} ms   "
          f"decode: {res.decode_s:.2f} s   "
          f"throughput: {res.tokens_per_s:.1f} tok/s   "
          f"({res.decode_steps} steps in {res.decode_dispatches} "
          f"dispatch{'es' if res.decode_dispatches != 1 else ''})")
    if res.spec_rounds:
        print(f"speculative: {res.spec_rounds} verify rounds, "
              f"accept hist {res.spec_accept_hist}")
    print("first new tokens:", res.tokens[:, :8].tolist())
    return res


if __name__ == "__main__":
    main()
