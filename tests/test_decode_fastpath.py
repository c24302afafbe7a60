"""Decode fast path: Pallas decode-kernel equivalence vs the XLA twin
(GQA + ragged kv_len), block-gather exactness vs dense decode, fused
scan-loop vs legacy python-loop token equivalence, decode dispatch
accounting, block score-cache consistency, chunk-append prefill (the
chunk-prefill Pallas kernel vs its XLA twin, and chunk_step's bitwise
equivalence to whole-prompt bucketed prefill across dense/DSA/kernel
paths), and SWA ring-buffer + window semantics at cache wrap-around."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import attention as A
from repro.core import masks as M
from repro.inference.engine import Engine
from repro.kernels.ops import dsa_chunk_prefill, dsa_decode
from repro.models.attention import RunFlags
from repro.models.transformer import (chunk_step, decode_step, forward,
                                      init_cache, init_model,
                                      truncate_cache)


def _mk_decode_case(key, b, s, hq, hkv, hd, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, hd)).astype(dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, hd)).astype(dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, hd)).astype(dtype)
    return q, kc, vc, ks[3]


# ---------------------------------------------------------------------------
# kernel vs XLA twin
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])       # MHA + GQA
@pytest.mark.parametrize("s,bk", [(104, 16), (256, 32),    # ragged tail,
                                  (100, 16)])              # non-divisible S
def test_dsa_decode_kernel_matches_xla_twin(rng, hq, hkv, s, bk):
    b, hd = 2, 32
    q, kc, vc, k2 = _mk_decode_case(rng, b, s, hq, hkv, hd)
    kv_len = jnp.array([s, max(1, s - 37)], jnp.int32)     # ragged batch
    n_kb = -(-s // bk)
    sb = jax.random.normal(k2, (b, n_kb))
    nb = min(n_kb, 5)
    idx, ok = M.decode_block_topk_indices(sb, nb, kv_len=kv_len,
                                          block_k=bk, local=32)
    out = dsa_decode(q, kc, vc, idx, ok, kv_len, block_k=bk)
    ref = A.dsa_decode_block_attention(q, kc, vc, idx, ok, block_k=bk,
                                       kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-3),
                                       (jnp.bfloat16, 3e-2)])
def test_dsa_decode_kernel_dtypes(rng, dtype, tol):
    b, s, hq, hkv, hd, bk = 2, 128, 8, 2, 64, 32
    q, kc, vc, k2 = _mk_decode_case(rng, b, s, hq, hkv, hd, dtype)
    kv_len = jnp.array([128, 77], jnp.int32)
    idx, ok = M.decode_block_topk_indices(
        jax.random.normal(k2, (b, s // bk)), 3, kv_len=kv_len,
        block_k=bk, local=32)
    out = dsa_decode(q, kc, vc, idx, ok, kv_len, block_k=bk)
    ref = A.dsa_decode_block_attention(q, kc, vc, idx, ok, block_k=bk,
                                       kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_block_gather_equals_dense_when_all_blocks_kept(rng):
    """Selecting every valid block reduces both the XLA twin and the Pallas
    kernel to exact dense decode (mechanism correctness)."""
    b, s, hq, hkv, hd, bk = 2, 96, 4, 2, 16, 16
    q, kc, vc, k2 = _mk_decode_case(rng, b, s, hq, hkv, hd)
    kv_len = jnp.array([96, 50], jnp.int32)
    idx, ok = M.decode_block_topk_indices(
        jax.random.normal(k2, (b, s // bk)), s // bk, kv_len=kv_len,
        block_k=bk, local=16)
    full = A.decode_attention(q, kc, vc, kv_len=kv_len)
    blk = A.dsa_decode_block_attention(q, kc, vc, idx, ok, block_k=bk,
                                       kv_len=kv_len)
    kern = dsa_decode(q, kc, vc, idx, ok, kv_len, block_k=bk)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(full), atol=1e-3)


# ---------------------------------------------------------------------------
# chunk-append prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])       # MHA + GQA
@pytest.mark.parametrize("s,c,bq,bk", [(128, 32, 16, 16),
                                       (96, 32, 16, 32),   # rect blocks
                                       (104, 16, 16, 16)])  # ragged tail S
def test_dsa_chunk_kernel_matches_xla_twin(rng, hq, hkv, s, c, bq, bk):
    """Fused chunk-prefill kernel == XLA gather twin: GQA, per-row global
    chunk offsets, ragged kv_len, sorted block index lists."""
    b, hd = 2, 32
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (b, c, hq, hd))
    kc = jax.random.normal(ks[1], (b, s, hkv, hd))
    vc = jax.random.normal(ks[2], (b, s, hkv, hd))
    q_off = jnp.array([32, 16], jnp.int32)                 # ragged depths
    kv_len = q_off + jnp.array([c, c - 7], jnp.int32)
    n_kb = -(-s // bk)
    bs = jax.random.normal(ks[3], (b, c // bq, n_kb))
    idx, ok = M.chunk_block_topk_indices(bs, min(n_kb, 4),
                                         q_block_offset=q_off // bq)
    out = dsa_chunk_prefill(q, kc, vc, idx, ok, q_off, kv_len,
                            block_q=bq, block_k=bk)
    ref = A.dsa_chunk_block_attention(q, kc, vc, idx, ok, block_q=bq,
                                      block_k=bk, q_offset=q_off,
                                      kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("arch,dsa_mode,long_ctx",
                         [("stablelm_3b", "off", False),
                          ("yi_6b", "block", True),
                          ("yi_6b", "kernel", True),
                          ("yi_6b", "faithful", True)])
@pytest.mark.parametrize("c", [16, 32])
def test_chunk_step_bitwise_matches_whole_prefill(rng, arch, dsa_mode,
                                                  long_ctx, c):
    """Chunked prefill == whole-prompt bucketed prefill: cache leaves
    (k/v/kt/ktb/pos after truncate) and the last-position logits that
    sample the first token, for chunk sizes that don't divide the (ragged,
    per-row) prompt lengths, across dense / DSA-block / fused kernel /
    faithful paths.  The two run matmuls of different shapes, so float
    leaves agree to f32 rounding (observed <= 8e-6 absolute) and the
    integer positions exactly."""
    bucket, plen = 96, 70
    cfg = reduced(get_config(arch))
    params, _ = init_model(rng, cfg)
    pf = RunFlags(mode="prefill", dsa_mode=dsa_mode, with_mse=False,
                  long_context=long_ctx)
    df = RunFlags(mode="decode", dsa_mode=dsa_mode, with_mse=False,
                  long_context=long_ctx)
    lengths = np.asarray([plen, plen - 13], np.int32)
    toks = np.zeros((2, bucket), np.int32)
    gen = np.random.default_rng(0)
    for r in range(2):
        toks[r, :lengths[r]] = gen.integers(1, cfg.vocab - 4,
                                            size=(lengths[r],))
    cache = init_cache(cfg, 2, bucket, df, dtype=jnp.float32)
    logits_w, _, cache_w = forward(params, cfg, pf,
                                   {"tokens": jnp.asarray(toks)},
                                   caches=cache)
    cache_w = truncate_cache(cfg, cache_w, jnp.asarray(lengths))
    last_w = np.take_along_axis(np.asarray(logits_w),
                                (lengths - 1)[:, None, None], axis=1)[:, 0]
    cache_c = init_cache(cfg, 2, bucket, df, dtype=jnp.float32)
    last_c = np.zeros_like(last_w)
    for j in range(-(-int(lengths.max()) // c)):
        ct = np.zeros((2, c), np.int32)
        sl = toks[:, j * c:(j + 1) * c]
        ct[:, :sl.shape[1]] = sl
        cl = np.clip(lengths - j * c, 0, c).astype(np.int32)
        logits_c, cache_c = chunk_step(params, cfg, df, jnp.asarray(ct),
                                       cache_c, jnp.asarray(cl))
        lc = np.asarray(logits_c)
        for r in range(2):
            if cl[r] > 0 and lengths[r] <= (j + 1) * c:
                last_c[r] = lc[r, cl[r] - 1]
    for (path, vw), (_, vc) in zip(
            jax.tree_util.tree_leaves_with_path(cache_w),
            jax.tree_util.tree_leaves_with_path(cache_c)):
        msg = f"{arch}/{dsa_mode} c={c}: {jax.tree_util.keystr(path)}"
        if jnp.issubdtype(vw.dtype, jnp.floating):
            np.testing.assert_allclose(np.asarray(vw), np.asarray(vc),
                                       atol=5e-5, rtol=1e-4, err_msg=msg)
        else:
            np.testing.assert_array_equal(np.asarray(vw), np.asarray(vc),
                                          err_msg=msg)
    np.testing.assert_allclose(last_w, last_c, atol=5e-5, rtol=1e-4)


def test_chunk_step_freezes_inactive_slots(rng):
    """active=False rows of a chunk step write nothing and don't advance
    pos — the slot-freeze contract the interleaved scheduler relies on."""
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    df = RunFlags(mode="decode", dsa_mode="block", with_mse=False,
                  long_context=True)
    cache = init_cache(cfg, 2, 64, df, dtype=jnp.float32)
    toks = jnp.ones((2, 16), jnp.int32)
    cl = jnp.array([16, 16], jnp.int32)
    active = jnp.array([True, False])
    _, new = chunk_step(params, cfg, df, toks, cache, cl, active=active)
    c0 = new["groups"]["b0"]["attn"]          # stacked: (n_groups, B, ...)
    np.testing.assert_array_equal(
        np.asarray(c0["pos"]), np.broadcast_to([16, 0], c0["pos"].shape))
    for name in ("k", "v", "kt", "ktb"):
        np.testing.assert_array_equal(np.asarray(c0[name][:, 1]), 0.0,
                                      err_msg=name)
    assert np.any(np.asarray(c0["k"][:, 0]) != 0.0)


# ---------------------------------------------------------------------------
# fused generation loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dsa_mode,long_ctx", [("off", False),
                                               ("block", True),
                                               ("kernel", True)])
def test_scan_loop_matches_python_loop(rng, dsa_mode, long_ctx):
    """Token-for-token: fused scan generation == legacy per-token loop,
    greedy and sampled (fixed seed), across decode paths."""
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab - 4, size=(2, 32)).astype(np.int32)
    kw = dict(max_len=96, dsa_mode=dsa_mode, long_context=long_ctx)
    e_scan = Engine(cfg, params, loop="scan", **kw)
    e_py = Engine(cfg, params, loop="python", **kw)
    r_scan = e_scan.generate(prompts, 8)
    r_py = e_py.generate(prompts, 8)
    np.testing.assert_array_equal(r_scan.tokens, r_py.tokens)
    r_scan = e_scan.generate(prompts, 8, greedy=False, seed=7)
    r_py = e_py.generate(prompts, 8, greedy=False, seed=7)
    np.testing.assert_array_equal(r_scan.tokens, r_py.tokens)


def test_decode_dispatch_accounting(rng):
    """decode_steps counts steps EXECUTED: the scan path runs the bucketed
    scan length (pow2, floor STEP_BUCKET_FLOOR) in one fused dispatch and
    truncates surplus tokens; the legacy loop runs exactly n_new - 1 jitted
    dispatches.  Tokens are identical either way."""
    from repro.inference.engine import STEP_BUCKET_FLOOR, pow2_bucket
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    prompts = np.ones((2, 16), np.int32)
    for n_new in (6, 8):       # off-bucket and exact-bucket step counts
        r_scan = Engine(cfg, params, max_len=64, loop="scan").generate(
            prompts, n_new)
        r_py = Engine(cfg, params, max_len=64, loop="python").generate(
            prompts, n_new)
        assert r_scan.tokens.shape == (2, n_new)
        assert r_scan.decode_steps == pow2_bucket(n_new - 1,
                                                  STEP_BUCKET_FLOOR)
        assert r_scan.decode_dispatches == 1
        assert r_py.decode_steps == n_new - 1
        assert r_py.decode_dispatches == n_new - 1
        np.testing.assert_array_equal(r_scan.tokens, r_py.tokens)
    # step_buckets=False restores the exact scan length
    r_exact = Engine(cfg, params, max_len=64, loop="scan",
                     step_buckets=False).generate(prompts, 6)
    assert r_exact.decode_steps == 5
    # n_new=1 needs no decode dispatch at all
    r_one = Engine(cfg, params, max_len=64, loop="scan").generate(prompts, 1)
    assert r_one.tokens.shape == (2, 1) and r_one.decode_dispatches == 0


def test_tokens_per_s_counts_executed_decode_steps(rng):
    """Satellite regression: tokens_per_s is B * decode_steps / decode_s on
    BOTH loops — the first token comes from prefill logits and is never
    attributed to decode time, and the scan path counts its bucketed
    (executed) steps, not the delivered n_new."""
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    prompts = np.ones((2, 16), np.int32)
    for loop in ("scan", "python"):
        res = Engine(cfg, params, max_len=64, loop=loop).generate(prompts, 6)
        expect = 2 * res.decode_steps / res.decode_s
        assert res.tokens_per_s == pytest.approx(expect, rel=1e-6), loop
        assert res.tokens.shape == (2, 6)
    # n_new=1: zero decode steps -> rate reported as 0, not inf
    res = Engine(cfg, params, max_len=64).generate(prompts, 1)
    assert res.decode_steps == 0 and res.tokens_per_s == 0.0


def test_engine_kernel_mode_end_to_end(rng):
    """dsa_mode="kernel" works through Engine.generate and agrees with the
    XLA block twin token-for-token (identical selection, same gather)."""
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    prompts = np.random.default_rng(1).integers(
        1, cfg.vocab - 4, size=(2, 48)).astype(np.int32)
    kw = dict(max_len=96, long_context=True, loop="scan")
    r_blk = Engine(cfg, params, dsa_mode="block", **kw).generate(prompts, 8)
    r_ker = Engine(cfg, params, dsa_mode="kernel", **kw).generate(prompts, 8)
    assert r_ker.tokens.shape == (2, 8)
    np.testing.assert_array_equal(r_ker.tokens, r_blk.tokens)


# ---------------------------------------------------------------------------
# block score cache consistency
# ---------------------------------------------------------------------------


def test_block_score_cache_tracks_token_cache(rng):
    """After prefill + decode steps, ktb equals the block sums of kt."""
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    toks = jax.random.randint(rng, (2, 40), 0, cfg.vocab)
    pf = RunFlags(mode="prefill", dsa_mode="block", with_mse=False,
                  long_context=True)
    df = RunFlags(mode="decode", dsa_mode="block", with_mse=False,
                  long_context=True)
    cache = init_cache(cfg, 2, 72, df, dtype=jnp.float32)
    c0 = cache["groups"]["b0"]["attn"]
    assert "kt" in c0 and "ktb" in c0
    bkd = cfg.dsa.block_k
    assert c0["ktb"].shape[2] == -(-c0["kt"].shape[2] // bkd)
    _, _, cache = forward(params, cfg, pf, {"tokens": toks[:, :32]},
                          caches=cache)
    for i in range(4):
        _, cache = decode_step(params, cfg, df, toks[:, 32 + i:33 + i], cache)
    c = cache["groups"]["b0"]["attn"]
    kt, ktb = np.asarray(c["kt"]), np.asarray(c["ktb"])
    n_kb = ktb.shape[2]
    pad = n_kb * bkd - kt.shape[2]
    ktp = np.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    expect = ktp.reshape(*kt.shape[:2], n_kb, bkd, kt.shape[-1]).sum(axis=3)
    np.testing.assert_allclose(ktb, expect, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# SWA ring buffer + window semantics (satellite regression)
# ---------------------------------------------------------------------------


def test_swa_window_ring_wrap(rng):
    """Pin ring-buffer + window semantics when the cache EQUALS the window:
    the buffer enforces the window structurally, so decode across the
    wrap-around point must keep matching teacher forcing — a positional
    window mask over slot indices would corrupt logits right here."""
    cfg = reduced(get_config("h2o_danube_1_8b"))       # swa_window=64 reduced
    params, _ = init_model(rng, cfg)
    win = cfg.swa_window
    n = 4
    for s0 in (win - 2, win, 2 * win + 3):             # pre/at/post wrap
        toks = jax.random.randint(jax.random.fold_in(rng, s0),
                                  (1, s0 + n), 0, cfg.vocab)
        tf = RunFlags(mode="train", dsa_mode="off", with_mse=False)
        full_logits, _, _ = forward(params, cfg, tf, {"tokens": toks})
        pf = RunFlags(mode="prefill", dsa_mode="off", with_mse=False)
        df = RunFlags(mode="decode", dsa_mode="off", with_mse=False)
        cache = init_cache(cfg, 1, s0 + n, df, dtype=jnp.float32)
        assert cache["groups"]["b0"]["attn"]["k"].shape[2] == win
        _, _, cache = forward(params, cfg, pf, {"tokens": toks[:, :s0]},
                              caches=cache)
        for i in range(n):
            logits, cache = decode_step(params, cfg, df,
                                        toks[:, s0 + i:s0 + i + 1], cache)
            np.testing.assert_allclose(
                np.asarray(logits[:, 0]), np.asarray(full_logits[:, s0 + i]),
                atol=2e-3, rtol=2e-3, err_msg=f"s0={s0} step={i}")


def test_decode_attention_window_masks_slots_pre_wrap(rng):
    """The explicit window arg of decode_attention is a *slot-positional*
    mask: correct only pre-wrap (kv_len <= cache size).  Pin that contract
    so external callers with over-sized caches keep working."""
    b, s, h, hd, win = 1, 32, 2, 8, 8
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    kc = jax.random.normal(ks[1], (b, s, h, hd))
    vc = jax.random.normal(ks[2], (b, s, h, hd))
    kv_len = jnp.array([20], jnp.int32)
    out = A.decode_attention(q, kc, vc, kv_len=kv_len, window=win)
    # reference: dense attention over exactly the window's slots
    ref = A.decode_attention(q, kc[:, 12:20], vc[:, 12:20],
                             kv_len=jnp.array([8], jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
