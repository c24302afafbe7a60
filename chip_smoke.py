"""Chip smoke test: DSA serving at published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the tensor-parallel path on four chips

One chip: ``stablelm_3b`` at its published widths (32 layers, d_model 2560,
MHA 32x80, vocab 50304, bf16) with random weights from ``--seed``, served
through ``ContinuousEngine`` with ``ServingConfig(max_len=2048, slots=4,
paged=True, long_context=True)`` — chunked admission into the paged cache,
DSA on — once with ``dsa_mode="kernel"`` (the Pallas gather kernels) and
once with ``dsa_mode="block"`` (their XLA twins).  Eight greedy requests
with prompts of 513-999 tokens (one 1024 bucket, so every prompt takes
the DSA block path) ask for 32 new tokens each.  Every request must end
``ok`` with in-vocabulary tokens and no dispatch failure, the kernel-mode
decode segment must hold ``tpu_custom_call``, and the two modes' logits
must agree within ``LOGIT_TOL`` (see ``step_logits`` for which).

``--chips 4``: the same requests served at tp=4 on a (1, 4) mesh
(``Engine.tp == 4``, so no silent fallback to replicated weights), then
unsharded on one of the four chips, compared the same way.

Everything runs in this one process: a chip belongs to one process.  The
script refuses to run anywhere but on a TPU.  Compile seconds, serving wall
time and peak device memory are printed for orientation only — they are
not benchmark metrics.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ARCH = "stablelm_3b"
SERVING = dict(max_len=2048, slots=4, paged=True, long_context=True)
N_REQUESTS, PROMPT_LENS, N_NEW = 8, (513, 1000), 32
# max |a - b| / max |b| over a batch of logits.  Both sides run the bf16
# model; the paths differ in accumulation order (kernel vs XLA gather, or
# tp=4 all-reduces vs unsharded), which moves a bf16 value by about one
# rounding step (2^-8) per op and compounds over 32 layers: about 2e-2.
LOGIT_TOL = 5e-2
# Cache blocks a decode step keeps in the 1024 bucket: the predicted one,
# the trailing 64-token local window's, and one more (attention._dsa_decode).
# Past that depth WHICH block is predicted flips under bf16 rounding, and a
# flipped block moves that step's logits far past LOGIT_TOL; prompts of
# PROBE_BLOCKS blocks or less have every block selected on both paths.
PROBE_BLOCKS = 3


# max |kernel - twin| / max |twin| for one kernel call on bf16 inputs,
# against the XLA twin run in float32 at "highest" matmul precision
KERNEL_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what) -> None:
    """Fail the run unless ``ok`` (an ``assert`` that ``python -O`` keeps)."""
    if not ok:
        raise AssertionError(what)


def kernel_twins(cfg, batch: int, seq: int, seed: int) -> dict:
    """Each serving kernel (decode and chunk prefill, dense and paged) at
    the model's head widths and DSA blocks on random bf16 inputs, against
    its XLA twin in float32.  Returns {kernel: relative error}."""
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    from repro.core import masks as M
    from repro.kernels import ops
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    bq, bk = cfg.dsa.block_q, cfg.dsa.block_k
    n_kb, nb, c = seq // bk, min(seq // bk, 4), 2 * bq
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    q1 = jax.random.normal(ks[0], (batch, 1, hq, hd), bf)
    qc = jax.random.normal(ks[1], (batch, c, hq, hd), bf)
    kc = jax.random.normal(ks[2], (batch, seq, hkv, hd), bf)
    vc = jax.random.normal(ks[3], (batch, seq, hkv, hd), bf)
    f32 = [a.astype(jnp.float32) for a in (q1, qc, kc, vc)]
    rows = np.arange(batch)
    kv_len = jnp.asarray(seq - 37 * rows, jnp.int32)
    idx, ok = M.decode_block_topk_indices(
        jax.random.normal(ks[4], (batch, n_kb)), nb, kv_len=kv_len,
        block_k=bk, local=bk)
    q_off = jnp.asarray((rows % 2) * bq, jnp.int32)
    c_len = q_off + c - 5 * jnp.asarray(rows, jnp.int32)
    cidx, cok = M.chunk_block_topk_indices(
        jax.random.normal(ks[5], (batch, c // bq, n_kb)), nb,
        q_block_offset=q_off // bq)
    # the pool: each row's blocks on pages in reverse order, page 0 unused
    tbl = 1 + rows[:, None] * n_kb + np.arange(n_kb)[::-1][None]
    pool = [jnp.zeros((batch * n_kb + 1, bk, hkv, hd), bf).at[
        tbl.reshape(-1)].set(a.reshape(-1, bk, hkv, hd)).reshape(-1, hkv, hd)
        for a in (kc, vc)]
    pidx = jnp.take_along_axis(jnp.asarray(tbl), idx, axis=1)
    cpidx = jnp.take_along_axis(jnp.asarray(tbl)[:, None].repeat(
        cidx.shape[1], 1), cidx, axis=2)
    with jax.default_matmul_precision("highest"):
        ref_d = A.dsa_decode_block_attention(f32[0], f32[2], f32[3], idx, ok,
                                             block_k=bk, kv_len=kv_len)
        ref_c = A.dsa_chunk_block_attention(
            f32[1], f32[2], f32[3], cidx, cok, block_q=bq, block_k=bk,
            q_offset=q_off, kv_len=c_len)
    got = {
        "decode": (ops.dsa_decode(q1, kc, vc, idx, ok, kv_len, block_k=bk),
                   ref_d),
        "decode_paged": (ops.dsa_decode_paged(q1, *pool, idx, pidx, ok,
                                              kv_len, block_k=bk), ref_d),
        "chunk": (ops.dsa_chunk_prefill(qc, kc, vc, cidx, cok, q_off, c_len,
                                        block_q=bq, block_k=bk), ref_c),
        "chunk_paged": (ops.dsa_chunk_prefill_paged(
            qc, *pool, cidx, cpidx, cok, q_off, c_len, block_q=bq,
            block_k=bk), ref_c),
    }
    errs = {k: logit_err(np.asarray(a, np.float32), np.asarray(b))
            for k, (a, b) in got.items()}
    log("kernel vs float32 XLA twin at "
        f"{hq}x{hd} heads ({hkv} kv), blocks {bq}x{bk}: "
        + ", ".join(f"{k} {v:.2e}" for k, v in errs.items())
        + f" (limit {KERNEL_TOL:.0e})")
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    check(not bad, bad)
    return errs


def build_params(cfg, seed: int):
    """Random weights from ``seed``, initialized on the device (jitted, so
    no float32 copy of the model is ever materialized)."""
    import jax
    from repro.models.transformer import init_model
    params = jax.jit(lambda k: init_model(k, cfg)[0])(jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def make_requests(cfg, n: int, lens, n_new: int, seed: int):
    """``n`` greedy requests, all arriving at t=0, with seeded prompts."""
    from repro.inference.scheduler import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, n_new=n_new, seed=seed + i,
                    prompt=rng.integers(1, cfg.vocab - 4,
                                        size=int(rng.integers(*lens))
                                        ).astype(np.int32))
            for i in range(n)]


def serve(cfg, params, config, requests):
    """Warm up and serve ``requests`` once through ``ContinuousEngine``.
    Returns (engine, {rid: tokens}, report); raises on any failure."""
    from repro.inference.scheduler import ContinuousEngine
    t0 = time.monotonic()
    eng = ContinuousEngine(cfg, params, config=config)
    eng.warmup([len(r.prompt) for r in requests])
    t1 = time.monotonic()
    results = eng.serve(requests)
    t2 = time.monotonic()
    bad = [(r.rid, r.status) for r in results if r.status != "ok"]
    check(not bad, f"requests not ok: {bad}; {eng.health()['last_error']}")
    check(len(results) == len(requests), (len(results), len(requests)))
    check(eng.stats["dispatch_failures"] == 0, eng.health())
    tokens = {r.rid: np.asarray(r.tokens) for r in results}
    for r in requests:
        t = tokens[r.rid]
        check(t.shape == (r.n_new,), (r.rid, t.shape))
        check(((t >= 0) & (t < cfg.vocab)).all(), (r.rid, t))
    return eng, tokens, {"compile_s": t1 - t0, "serve_s": t2 - t1,
                         "n_tokens": sum(r.n_new for r in requests)}


def step_logits(eng, requests, first=None) -> dict:
    """Logits of the first ``slots`` requests through the engine's chunk
    and decode programs, in the bucket they are served in: the prompt
    logits and first decode-step logits of the whole prompts, and of the
    prompts cut to PROBE_BLOCKS blocks (the probe).  The decode steps are
    fed ``first`` (another run's first tokens, from its ``firsts``) or
    their own greedy ones.  Asserts every logit is finite."""
    reqs = requests[:eng.slots]
    bucket = eng.engine.prompt_bucket(max(len(r.prompt) for r in reqs))
    n_probe = PROBE_BLOCKS * eng.cfg.dsa.block_k - 1
    first = first or (None, None)
    whole = eng.first_step_logits([r.prompt for r in reqs], first[0])
    probe = eng.first_step_logits([r.prompt[:n_probe] for r in reqs],
                                  first[1], bucket=bucket)
    out = {"prompt": whole[0], "step": whole[1], "probe_prompt": probe[0],
           "probe": probe[1]}
    for k, v in out.items():
        check(np.isfinite(v).all(), k)
    return out


def firsts(logits: dict) -> tuple:
    return (logits["prompt"].argmax(-1), logits["probe_prompt"].argmax(-1))


def logit_err(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def greedy_prefix(a: dict, b: dict) -> list:
    """Per request, how many leading greedy tokens two runs share."""
    out = []
    for rid in sorted(a):
        same = a[rid] == b[rid]
        out.append(int(len(same) if same.all() else np.argmin(same)))
    return out


def compare(name: str, a: dict, b: dict) -> dict:
    """Logits and greedy tokens of two runs: the prompt logits and the
    probe's first-step logits must agree within LOGIT_TOL; the whole
    prompts' first-step logits are reported only (their decode block
    selection may differ, see PROBE_BLOCKS)."""
    la, lb = a["logits"], b["logits"]
    err = {k: logit_err(la[k], lb[k]) for k in ("prompt", "probe", "step")}
    prefix = greedy_prefix(a["tokens"], b["tokens"])
    n_new = max(len(t) for t in a["tokens"].values())
    log(f"{name}: logit error (limit {LOGIT_TOL:.0e}) prompt "
        f"{err['prompt']:.3e}, first step {err['probe']:.3e} at "
        f"{PROBE_BLOCKS}-block probes, first step {err['step']:.3e} at whole "
        f"prompts (not checked: block selection may differ); greedy tokens "
        f"agree for {prefix} of {n_new} per request")
    bad = {k: v for k, v in err.items() if k != "step" and not v <= LOGIT_TOL}
    check(not bad, (name, bad))
    return {"logit_err": err, "prefix": prefix}


def dsa_modes(cfg, params, requests, serving: dict) -> dict:
    """Serve ``requests`` in dsa_mode "kernel", then "block"; check each
    and compare them, the block-mode decode steps fed the kernel-mode first
    tokens.  Returns the kernel-mode segment HLO with the rest."""
    from repro.inference.config import ServingConfig
    out, first = {}, None
    for mode in ("kernel", "block"):
        config = ServingConfig(dsa_mode=mode, **serving)
        eng, tokens, rep = serve(cfg, params, config, requests)
        log(f"{mode}: {len(requests)} requests ok, {rep['n_tokens']} tokens; "
            f"warmup (compile) {rep['compile_s']:.1f} s, serving "
            f"{rep['serve_s']:.2f} s")
        logits = step_logits(eng, requests, first)
        first = firsts(logits)
        out[mode] = {"tokens": tokens, "logits": logits, **rep}
        if mode == "kernel":
            out["kernel_hlo"] = eng.segment_hlo()
        del eng
        gc.collect()
    out.update(compare("kernel vs block", out["kernel"], out["block"]))
    return out


def tensor_parallel(cfg, params, requests, serving: dict, tp: int) -> dict:
    """Serve ``requests`` in kernel mode at tensor parallelism ``tp`` on a
    (1, tp) mesh, then unsharded on one device, and compare."""
    from repro.inference.config import ServingConfig
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(dp=1, tp=tp, cfg=cfg)
    out, first = {}, None
    for name, m in (("tp", mesh), ("unsharded", None)):
        config = ServingConfig(mesh=m, dsa_mode="kernel", **serving)
        eng, tokens, rep = serve(cfg, params, config, requests)
        if m is not None:
            check(eng.engine.tp == tp, (eng.engine.tp, tp))
            log(f"tp: Engine.tp == {eng.engine.tp} on mesh "
                f"{dict(mesh.shape)}, "
                f"{eng.weight_bytes_per_device() / 2**30:.3f} GiB "
                "weights/device")
        log(f"{name}: {len(requests)} requests ok, {rep['n_tokens']} tokens; "
            f"warmup (compile) {rep['compile_s']:.1f} s, serving "
            f"{rep['serve_s']:.2f} s")
        logits = step_logits(eng, requests, first)
        first = firsts(logits)
        out[name] = {"tokens": tokens, "logits": logits, **rep}
        del eng
        gc.collect()
    out.update(compare(f"tp={tp} vs unsharded", out["tp"], out["unsharded"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tp=4 path and its unsharded "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {devices[0].platform!r} "
              "devices; this script runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.serve import use_compile_cache
    log(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{use_compile_cache()}")

    cfg = get_config(ARCH)
    t0 = time.monotonic()
    params = build_params(cfg, args.seed)
    weight_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}x{cfg.resolved_head_dim} heads, vocab {cfg.vocab}; "
        f"{weight_bytes / 1e9:.3f} GB of {cfg.param_dtype} weights "
        f"(init {time.monotonic() - t0:.1f} s)")
    requests = make_requests(cfg, N_REQUESTS, PROMPT_LENS, N_NEW, args.seed)
    serving = dict(SERVING, cache_dtype=jnp.dtype(cfg.dtype))
    if args.chips == 4:
        tensor_parallel(cfg, params, requests, serving, tp=4)
    else:
        kernel_twins(cfg, SERVING["slots"], SERVING["max_len"], args.seed)
        out = dsa_modes(cfg, params, requests, serving)
        n_calls = out["kernel_hlo"].count("tpu_custom_call")
        log(f"kernel-mode segment HLO: {n_calls} tpu_custom_call")
        check(n_calls > 0, "kernel mode ran no Pallas kernel")
    peak = int(devices[0].memory_stats()["peak_bytes_in_use"])
    log(f"peak device memory: {peak / 1e9:.3f} GB on device 0 "
        f"(weights {weight_bytes / 1e9:.3f} GB)")
    check(peak > weight_bytes, (peak, weight_bytes))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
