"""Per-kernel allclose vs the ref.py jnp oracles, swept over shapes and
dtypes (assignment requirement), in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masks as M
from repro.kernels import ref
from repro.kernels.ops import (dsa_attention, dsa_chunk_prefill,
                               dsa_chunk_prefill_paged, dsa_decode,
                               dsa_decode_paged, wkv6)


def _mk_qkv(key, b, l, hq, hkv, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, l, hq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, l, hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, l, hkv, hd)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("l,bq,bk,nb", [(128, 16, 16, 3), (256, 32, 32, 4),
                                        (256, 64, 32, 5), (512, 64, 64, 3)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_dsa_attention_shapes(rng, l, bq, bk, nb, hq, hkv):
    b, hd = 2, 32
    q, k, v = _mk_qkv(rng, b, l, hq, hkv, hd, jnp.float32)
    bs = jax.random.normal(jax.random.fold_in(rng, 1), (b, l // bq, l // bk))
    idx, ok = M.block_topk_indices(bs, nb, causal=True, local_blocks=1)
    out = dsa_attention(q, k, v, idx, ok, block_q=bq, block_k=bk, causal=True)
    r = ref.dsa_block_sparse_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), idx, ok, block_q=bq, block_k=bk,
        causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_dsa_attention_dtypes(rng, dtype, tol):
    b, l, hq, hkv, hd, bq = 2, 256, 4, 2, 64, 32
    q, k, v = _mk_qkv(rng, b, l, hq, hkv, hd, dtype)
    bs = jax.random.normal(jax.random.fold_in(rng, 2), (b, l // bq, l // bq))
    idx, ok = M.block_topk_indices(bs, 4, causal=True)
    out = dsa_attention(q, k, v, idx, ok, block_q=bq, block_k=bq)
    r = ref.dsa_block_sparse_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), idx, ok, block_q=bq,
        block_k=bq).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


def test_dsa_attention_window(rng):
    b, l, h, hd, bq = 1, 256, 2, 32, 32
    q, k, v = _mk_qkv(rng, b, l, h, h, hd, jnp.float32)
    bs = jax.random.normal(jax.random.fold_in(rng, 3), (b, l // bq, l // bq))
    idx, ok = M.block_topk_indices(bs, 5, causal=True,
                                   window_blocks=2, local_blocks=1)
    out = dsa_attention(q, k, v, idx, ok, block_q=bq, block_k=bq,
                        causal=True, window=64)
    r = ref.dsa_block_sparse_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), idx, ok, block_q=bq, block_k=bq,
        causal=True, window=64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-5)


# -- paged gather kernels ----------------------------------------------------
#
# The paged variants steer the k/v BlockSpec through a second scalar-
# prefetched PHYSICAL index stream while masking with the logical one; on a
# pool that scatters the dense cache's blocks across permuted pages they
# must reproduce the dense gather kernel BITWISE (same arithmetic, same
# block values — only the fetch address changes).


def _scatter_to_pool(cache, tbl, bk):
    """Scatter each batch row's logical blocks to its pool pages."""
    b, s = cache.shape[:2]
    n_kb = s // bk
    pool = jnp.zeros((int(tbl.max()) + 1, bk) + cache.shape[2:],
                     cache.dtype)
    blocks = cache.reshape(b, n_kb, bk, *cache.shape[2:])
    pool = pool.at[tbl.reshape(-1)].set(
        blocks.reshape(b * n_kb, bk, *cache.shape[2:]))
    return pool.reshape(-1, *cache.shape[2:])


def _permuted_tbl(key, b, n_kb):
    """Per-row page tables: disjoint page sets, permuted within each row,
    page 0 left reserved (the zero page)."""
    perm = jnp.stack([jax.random.permutation(jax.random.fold_in(key, i),
                                             n_kb) for i in range(b)])
    return (1 + jnp.arange(b)[:, None] * n_kb + perm).astype(jnp.int32)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])       # MHA + GQA
@pytest.mark.parametrize("s,bk", [(128, 16), (256, 32)])
def test_dsa_decode_paged_matches_dense_kernel(rng, s, bk, hq, hkv):
    b, hd = 2, 32
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, hd))
    kc = jax.random.normal(ks[1], (b, s, hkv, hd))
    vc = jax.random.normal(ks[2], (b, s, hkv, hd))
    kv_len = jnp.array([s, max(1, s - 37)], jnp.int32)     # ragged batch
    n_kb = s // bk
    sb = jax.random.normal(ks[3], (b, n_kb))
    idx, ok = M.decode_block_topk_indices(sb, min(n_kb, 5), kv_len=kv_len,
                                          block_k=bk, local=32)
    tbl = _permuted_tbl(jax.random.fold_in(rng, 7), b, n_kb)
    kp = _scatter_to_pool(kc, tbl, bk)
    vp = _scatter_to_pool(vc, tbl, bk)
    pidx = jnp.take_along_axis(tbl, idx, axis=1)
    out = dsa_decode_paged(q, kp, vp, idx, pidx, ok, kv_len, block_k=bk)
    dense = dsa_decode(q, kc, vc, idx, ok, kv_len, block_k=bk)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))


@pytest.mark.parametrize("s,c,bq,bk", [(128, 32, 16, 16), (96, 32, 16, 32)])
def test_dsa_chunk_paged_matches_dense_kernel(rng, s, c, bq, bk):
    b, hq, hkv, hd = 2, 4, 2, 32
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
    tbl = _permuted_tbl(jax.random.fold_in(rng, 9), b, n_kb)
    kp = _scatter_to_pool(kc, tbl, bk)
    vp = _scatter_to_pool(vc, tbl, bk)
    pidx = jnp.take_along_axis(tbl[:, None].repeat(idx.shape[1], 1), idx,
                               axis=2)
    out = dsa_chunk_prefill_paged(q, kp, vp, idx, pidx, ok, q_off, kv_len,
                                  block_q=bq, block_k=bk)
    dense = dsa_chunk_prefill(q, kc, vc, idx, ok, q_off, kv_len,
                              block_q=bq, block_k=bk)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))


# -- quantized-cache gather kernels ------------------------------------------
#
# With k_scale/v_scale the kernels stream an int8/fp8 cache and dequantize
# per gathered block (row value * per-(row, head) scale) before the same
# f32 flash loop.  Dequantizing the whole cache in XLA and running the
# UNQUANTIZED kernel on it feeds the same block values through the same
# loop; the compiler may fuse the dequant multiply differently in the two
# programs, so they agree to f32 rounding (observed <= 2e-7).


@pytest.mark.parametrize("qd", ["int8", "fp8"])
@pytest.mark.parametrize("s,bk", [(128, 16), (256, 32)])
def test_dsa_decode_quant_matches_dequant_reference(rng, s, bk, qd):
    from repro.core.quantization import dequant, quant_store
    b, hq, hkv, hd = 2, 4, 2, 32
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, hd))
    kc = jax.random.normal(ks[1], (b, s, hkv, hd))
    vc = jax.random.normal(ks[2], (b, s, hkv, hd))
    kv_len = jnp.array([s, max(1, s - 21)], jnp.int32)
    n_kb = s // bk
    sb = jax.random.normal(ks[3], (b, n_kb))
    idx, ok = M.decode_block_topk_indices(sb, min(n_kb, 5), kv_len=kv_len,
                                          block_k=bk, local=32)
    kq, ksc = quant_store(kc, dtype=qd)
    vq, vsc = quant_store(vc, dtype=qd)
    out = dsa_decode(q, kq, vq, idx, ok, kv_len, block_k=bk,
                     k_scale=ksc, v_scale=vsc)
    ref_out = dsa_decode(q, dequant(kq, ksc), dequant(vq, vsc), idx, ok,
                         kv_len, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-6, rtol=1e-5)


def test_dsa_decode_paged_quant_matches_dense_quant(rng):
    from repro.core.quantization import quant_store
    b, s, bk, hq, hkv, hd = 2, 128, 16, 4, 2, 32
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, hd))
    kc = jax.random.normal(ks[1], (b, s, hkv, hd))
    vc = jax.random.normal(ks[2], (b, s, hkv, hd))
    kv_len = jnp.array([s, s - 37], jnp.int32)
    n_kb = s // bk
    sb = jax.random.normal(ks[3], (b, n_kb))
    idx, ok = M.decode_block_topk_indices(sb, 5, kv_len=kv_len,
                                          block_k=bk, local=32)
    kq, ksc = quant_store(kc)
    vq, vsc = quant_store(vc)
    tbl = _permuted_tbl(jax.random.fold_in(rng, 11), b, n_kb)
    pidx = jnp.take_along_axis(tbl, idx, axis=1)
    out = dsa_decode_paged(
        q, _scatter_to_pool(kq, tbl, bk), _scatter_to_pool(vq, tbl, bk),
        idx, pidx, ok, kv_len, block_k=bk,
        k_scale=_scatter_to_pool(ksc, tbl, bk),
        v_scale=_scatter_to_pool(vsc, tbl, bk))
    dense = dsa_decode(q, kq, vq, idx, ok, kv_len, block_k=bk,
                       k_scale=ksc, v_scale=vsc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))


@pytest.mark.parametrize("qd", ["int8", "fp8"])
def test_dsa_chunk_quant_matches_dequant_reference(rng, qd):
    from repro.core.quantization import dequant, quant_store
    b, s, c, bq, bk, hq, hkv, hd = 2, 128, 32, 16, 16, 4, 2, 32
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (b, c, hq, hd))
    kc = jax.random.normal(ks[1], (b, s, hkv, hd))
    vc = jax.random.normal(ks[2], (b, s, hkv, hd))
    q_off = jnp.array([32, 16], jnp.int32)
    kv_len = q_off + jnp.array([c, c - 7], jnp.int32)
    n_kb = s // bk
    bs = jax.random.normal(ks[3], (b, c // bq, n_kb))
    idx, ok = M.chunk_block_topk_indices(bs, 4, q_block_offset=q_off // bq)
    kq, ksc = quant_store(kc, dtype=qd)
    vq, vsc = quant_store(vc, dtype=qd)
    out = dsa_chunk_prefill(q, kq, vq, idx, ok, q_off, kv_len, block_q=bq,
                            block_k=bk, k_scale=ksc, v_scale=vsc)
    ref_out = dsa_chunk_prefill(q, dequant(kq, ksc), dequant(vq, vsc), idx,
                                ok, q_off, kv_len, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-6, rtol=1e-5)


def test_dsa_chunk_paged_quant_matches_dense_quant(rng):
    from repro.core.quantization import quant_store
    b, s, c, bq, bk, hq, hkv, hd = 2, 128, 32, 16, 16, 4, 2, 32
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (b, c, hq, hd))
    kq, ksc = quant_store(jax.random.normal(ks[1], (b, s, hkv, hd)))
    vq, vsc = quant_store(jax.random.normal(ks[2], (b, s, hkv, hd)))
    q_off = jnp.array([32, 16], jnp.int32)
    kv_len = q_off + jnp.array([c, c - 7], jnp.int32)
    n_kb = s // bk
    bs = jax.random.normal(ks[3], (b, c // bq, n_kb))
    idx, ok = M.chunk_block_topk_indices(bs, 4, q_block_offset=q_off // bq)
    tbl = _permuted_tbl(jax.random.fold_in(rng, 13), b, n_kb)
    pidx = jnp.take_along_axis(tbl[:, None].repeat(idx.shape[1], 1), idx,
                               axis=2)
    out = dsa_chunk_prefill_paged(
        q, _scatter_to_pool(kq, tbl, bk), _scatter_to_pool(vq, tbl, bk),
        idx, pidx, ok, q_off, kv_len, block_q=bq, block_k=bk,
        k_scale=_scatter_to_pool(ksc, tbl, bk),
        v_scale=_scatter_to_pool(vsc, tbl, bk))
    dense = dsa_chunk_prefill(q, kq, vq, idx, ok, q_off, kv_len, block_q=bq,
                              block_k=bk, k_scale=ksc, v_scale=vsc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))


@pytest.mark.parametrize("s,chunk,hd", [(64, 16, 16), (128, 32, 64),
                                        (256, 32, 32), (96, 32, 64)])
def test_wkv6_shapes(rng, s, chunk, hd):
    b, h = 2, 3
    if s % chunk:
        pytest.skip("not chunk-divisible")
    ks = jax.random.split(rng, 5)
    r = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd)) * 0.3
    v = jax.random.normal(ks[2], (b, s, h, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, s, h, hd)) * 0.5 - 2))
    u = jax.random.normal(ks[4], (h, hd)) * 0.1
    y = wkv6(r, k, v, w, u, chunk=chunk)
    yr, _ = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-3)


def test_wkv6_strong_decay(rng):
    """Numerics guard: decay products to ~1e-9 within a chunk stay finite."""
    b, s, h, hd, chunk = 1, 64, 2, 32, 32
    ks = jax.random.split(rng, 5)
    r = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd)) * 0.3
    v = jax.random.normal(ks[2], (b, s, h, hd))
    w = jnp.full((b, s, h, hd), 0.52)       # 0.52^32 ~ 8e-10
    u = jax.random.normal(ks[4], (h, hd)) * 0.1
    y = wkv6(r, k, v, w, u, chunk=chunk)
    yr, _ = ref.wkv6_ref(r, k, v, w, u)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-3, rtol=1e-2)


@pytest.mark.parametrize("backend,env,want", [
    ("cpu", "", True), ("tpu", "", False), ("gpu", "1", True),
    ("tpu", "true", True), ("gpu", "", RuntimeError)])
def test_default_interpret_only_on_cpu(monkeypatch, backend, env, want):
    """Kernels are interpreted on the CPU backend or when
    JAX_PALLAS_INTERPRET asks; any other non-TPU backend raises instead of
    silently running the interpreter."""
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", env)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="target the TPU"):
            ops._default_interpret()
    else:
        assert ops._default_interpret() is want
