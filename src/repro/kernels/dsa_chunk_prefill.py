"""Fused DSA chunk-prefill kernel — gather + attend for a C-token chunk.

Chunk-append companion of repro.kernels.dsa_attention (whole-sequence
prefill) and repro.kernels.dsa_decode (single-token decode): one Pallas
kernel attends a chunk of C fresh queries against ONLY the KV-cache blocks
selected by the block-pooled prediction path, with online softmax in VMEM
scratch.  The selected block indices, their validity bits, the per-row
GLOBAL chunk offsets, and the ragged per-row cache lengths all arrive via
scalar prefetch (PrefetchScalarGridSpec), so the grid stays static while
HBM->VMEM traffic scales with the number of selected blocks.

The "intra-chunk tile" (fresh queries attending each other causally) needs
no special casing: the mask builder force-keeps the local/diagonal blocks,
so the chunk's own freshly-written cache blocks are always among the
gathered blocks and the per-token causal mask below handles the triangle.

Layouts (the engine's own — no transposes at the call site):

  q:       (B, C, Hq, hd)     chunk queries, C a multiple of block_q
  k/v:     (B, S, Hkv, hd)    KV cache (S padded to a multiple of block_k)
  idx/ok:  (B, nQb, nb) i32   selected cache-block indices + validity
                              per chunk query block (nQb = C / block_q)
  q_off:   (B,) int32         global position of the chunk's first query
                              (the slot's cache depth; ragged per row)
  kv_len:  (B,) int32         valid cache rows (written so far, incl. the
                              chunk); frozen/pad slots pass 0
  out:     (B, C, Hq, hd)     written head-major, (B, Hq, C, hd), and
                              transposed back once: Mosaic cannot store a
                              packed bf16 head slice strided

Grid: (B, nQb, nb); the innermost axis accumulates online softmax and
finalizes on the last selected block.  As in repro.kernels.dsa_decode, each
step streams one cache row-block with every KV head, ``(1, block_k, Hkv,
hd)``, and the head loop runs inside the kernel, so a selected block is
DMA'd once per query block rather than once per query head.  GQA: query
head h reads KV head h // (Hq // Hkv).  Selected indices are pre-sorted
ascending by masks.chunk_block_topk_indices (contiguous HBM streams, the
paper's §5.2 reordering analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(*refs, block_q: int, block_k: int, nb: int, n_kv: int,
            scale: float, quant: bool, n_prefetch: int):
    # a paged call prefetches a 5th stream, the physical pages: it steers
    # the index maps only — the body masks from idx, the LOGICAL blocks,
    # which carry the key positions
    idx_ref, ok_ref, qoff_ref, kvl_ref = refs[:4]
    refs = refs[n_prefetch:]
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b, qb, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = q_ref.shape[2] // n_kv

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    kb = idx_ref[b, qb, j]
    ok = ok_ref[b, qb, j]
    q_pos = (qoff_ref[b] + qb * block_q
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = (ok > 0) & (k_pos <= q_pos) & (k_pos < kvl_ref[b])

    for hk in range(n_kv):
        k = k_ref[0, :, hk, :].astype(jnp.float32)         # (Bk, hd)
        v = v_ref[0, :, hk, :].astype(jnp.float32)
        if quant:
            # dequant-on-gather: int8/fp8 cache rows land in VMEM narrow
            # and return to f32 against their per-row scales only here
            k = k * ks_ref[0, :, hk:hk + 1]
            v = v * vs_ref[0, :, hk:hk + 1]
        for h in range(hk * g, (hk + 1) * g):
            q = q_ref[0, :, h, :].astype(jnp.float32) * scale  # (Bq, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG)                    # (Bq, Bk)
            m_prev = m_ref[:, h:h + 1]                     # (Bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # explicit zero under the mask: a fully-masked row (pad queries
            # of the final partial chunk) would otherwise contribute
            # exp(NEG - NEG) = 1
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[:, h:h + 1] = (l_ref[:, h:h + 1] * alpha
                                 + jnp.sum(p, axis=1, keepdims=True))
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:, h:h + 1] = m_new

    @pl.when(j == nb - 1)
    def _fini():
        for h in range(n_kv * g):
            denom = jnp.maximum(l_ref[:, h:h + 1], 1e-30)
            o_ref[0, h] = (acc_ref[h] / denom).astype(o_ref.dtype)


def _call(q, k, v, k_scale, v_scale, prefetch, block_row, *, block_q,
          block_k, interpret, name):
    """One pallas_call for the dense and paged variants.  ``prefetch`` is
    (idx, ok, q_off, kv_len[, pidx]); ``block_row(b, qb, j, *refs)`` gives
    the (batch, block) coordinates of the cache row-block to stream;
    ``name`` names the kernel in compiled programs and profiles."""
    b, c, hq, hd = q.shape
    hkv = k.shape[2]
    nb = prefetch[0].shape[-1]
    n_qb = c // block_q
    assert n_qb * block_q == c, (c, block_q)
    quant = k_scale is not None

    def qmap(bi, qi, ji, *refs):
        return (bi, qi, 0, 0)

    def omap(bi, qi, ji, *refs):
        return (bi, 0, qi, 0)

    def kmap(bi, qi, ji, *refs):
        return block_row(bi, qi, ji, *refs) + (0, 0)

    def smap(bi, qi, ji, *refs):
        return block_row(bi, qi, ji, *refs) + (0,)

    in_specs = [pl.BlockSpec((1, block_q, hq, hd), qmap),
                pl.BlockSpec((1, block_k, hkv, hd), kmap),
                pl.BlockSpec((1, block_k, hkv, hd), kmap)]
    args = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, block_k, hkv), smap)] * 2
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    kern = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                             nb=nb, n_kv=hkv, scale=hd ** -0.5, quant=quant,
                             n_prefetch=len(prefetch))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_qb, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, block_q, hd), omap),
        scratch_shapes=[
            pltpu.VMEM((hq, block_q, hd), jnp.float32),
            # running max / denominator, heads on lanes: (hq, block_q, 1)
            # would pad each head's column to 128 lanes (2 MiB each)
            pltpu.VMEM((block_q, hq), jnp.float32),
            pltpu.VMEM((block_q, hq), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, c, hd), q.dtype),
        interpret=interpret, name=name,
    )
    out = fn(*(p.astype(jnp.int32) for p in prefetch), *args)
    return out.transpose(0, 2, 1, 3)


def dsa_chunk_paged_gather_attention(q, k_pool, v_pool, idx, pidx, ok,
                                     q_off, kv_len, *, block_q: int = 128,
                                     block_k: int = 128,
                                     k_scale=None, v_scale=None,
                                     interpret: bool = False) -> jax.Array:
    """Paged twin of ``dsa_chunk_gather_attention``: the cache is one FLAT
    physical page pool (P*block_k, Hkv, hd) shared by all slots, and the
    selection arrives as DUAL scalar-prefetched streams — idx
    (B, nQb, nb) the LOGICAL block indices (position masking, unchanged
    kernel body) and pidx the same selection translated to PHYSICAL pages
    through each slot's page table (HBM->VMEM gather steering).
    k_scale/v_scale: optional (P*block_k, Hkv) per-row scales of an
    int8/fp8 pool (dequant-on-gather).  q: (B,C,Hq,hd).  Returns
    (B,C,Hq,hd)."""
    # pool rows are page-aligned by construction — no tail padding
    assert k_pool.shape[0] % block_k == 0, (k_pool.shape, block_k)

    def block_row(bi, qi, ji, idx_ref, ok_ref, qoff_ref, kvl_ref, pidx_ref):
        return (0, pidx_ref[bi, qi, ji])

    quant = k_scale is not None
    return _call(q, k_pool[None], v_pool[None],
                 k_scale[None] if quant else None,
                 v_scale[None] if quant else None,
                 (idx, ok, q_off, kv_len, pidx), block_row, block_q=block_q,
                 block_k=block_k, interpret=interpret,
                 name="dsa_chunk_prefill_paged")


def dsa_chunk_gather_attention(q, k_cache, v_cache, idx, ok, q_off, kv_len,
                               *, block_q: int = 128, block_k: int = 128,
                               k_scale=None, v_scale=None,
                               interpret: bool = False) -> jax.Array:
    """q: (B,C,Hq,hd); k/v cache: (B,S,Hkv,hd); idx/ok: (B,C//block_q,nb);
    q_off/kv_len: (B,).  k_scale/v_scale: optional (B,S,Hkv) per-row
    scales of an int8/fp8 cache (dequant-on-gather).  Returns
    (B,C,Hq,hd)."""
    s_len = k_cache.shape[1]
    pad = -(-s_len // block_k) * block_k - s_len
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))

    def block_row(bi, qi, ji, idx_ref, ok_ref, qoff_ref, kvl_ref):
        return (bi, idx_ref[bi, qi, ji])

    return _call(q, k_cache, v_cache, k_scale, v_scale,
                 (idx, ok, q_off, kv_len), block_row, block_q=block_q,
                 block_k=block_k, interpret=interpret,
                 name="dsa_chunk_prefill")
