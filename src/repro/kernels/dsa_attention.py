"""DSA block-sparse flash attention — Pallas TPU kernel.

TPU-native adaptation of the paper's SDDMM -> sparse-softmax -> SpMM chain
(DESIGN.md §2): one fused kernel walks ONLY the key blocks selected by the
prediction path.  The dynamic block indices arrive through scalar prefetch
(PrefetchScalarGridSpec), so the grid is static — the paper's row-uniform
top-k (§5.2 load balance) is exactly what makes that possible — while the
HBM->VMEM traffic and MXU work scale with (1 - sparsity).

Grid: (B, Hq, nQb, nb_keep); the innermost axis accumulates online softmax
in VMEM scratch (never materializes Lq x Lk), finalizing on the last step.
Block indices are pre-sorted ascending by the mask builder — the Pallas
analogue of the paper's §5.2 compute reordering (contiguous HBM streams).

  q: (B, Hq, Lq, hd)   k/v: (B, Hkv, Lk, hd)   idx/valid: (B, nQb, nb)
  out: (B, Hq, Lq, hd)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(idx_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, block_q: int, block_k: int,
            nb: int, causal: bool, window: int, scale: float):
    b, h, qb, j = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                   pl.program_id(3))

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    kb = idx_ref[b, qb, j]
    ok = valid_ref[b, qb, j]

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (Bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (Bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Bq, Bk)
    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.full((block_q, block_k), ok > 0)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]                                    # (Bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                 # (Bq, Bk)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                    # (Bk, hd)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _fini():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def dsa_block_sparse_attention(q, k, v, idx, valid, *, block_q: int = 128,
                               block_k: int = 128, causal: bool = True,
                               window: int = 0,
                               interpret: bool = False) -> jax.Array:
    """q: (B,Hq,Lq,hd); k/v: (B,Hkv,Lk,hd); idx/valid: (B,nQb,nb)."""
    b, hq, lq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    nb = idx.shape[-1]
    n_qb = lq // block_q
    scale = hd ** -0.5
    grid = (b, hq, n_qb, nb)

    def qmap(bi, hi, qi, ji, idx_ref, valid_ref):
        return (bi, hi, qi, 0)

    def kmap(bi, hi, qi, ji, idx_ref, valid_ref):
        return (bi, hi // g, idx_ref[bi, qi, ji], 0)

    def omap(bi, hi, qi, ji, idx_ref, valid_ref):
        return (bi, hi, qi, 0)

    kern = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                             nb=nb, causal=causal, window=window, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), qmap),
            pl.BlockSpec((1, 1, block_k, hd), kmap),
            pl.BlockSpec((1, 1, block_k, hd), kmap),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), omap),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, lq, hd), q.dtype),
        interpret=interpret, name="dsa_attention",
    )
    return fn(idx, valid.astype(jnp.int32), q, k, v)
