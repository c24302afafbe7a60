"""Fused DSA decode kernel — gather + attend over predicted cache blocks.

Decode-step companion of repro.kernels.dsa_attention (the prefill/train
block-sparse kernel): one Pallas kernel walks ONLY the cache blocks selected
by the block-pooled prediction path, with online softmax accumulated in VMEM
scratch.  The dynamic block indices, their validity bits, and the ragged
per-row cache lengths all arrive through scalar prefetch
(PrefetchScalarGridSpec), so the grid stays static while HBM->VMEM traffic
scales with the number of selected blocks — the paper's decode-time FLOP
saving made visible to the memory system.

Layouts (the engine's own — no transposes at the call site):

  q:       (B, 1, Hq, hd)     current query token, all heads
  k/v:     (B, S, Hkv, hd)    KV cache (S padded to a multiple of block_k)
  idx/ok:  (B, nb) int32      selected cache-block indices + validity
  kv_len:  (B,) int32         valid cache rows — ragged per row: batches mix
                              prompt lengths, and under continuous batching
                              every resident slot decodes at its own cache
                              depth (retired/unadmitted slots pass 0 and
                              contribute no valid attention support)
  out:     (B, 1, Hq, hd)

Grid: (B, nb); the innermost axis accumulates online softmax and finalizes
on the last selected block.  Each step streams ONE row-block of the cache
with every KV head, ``(1, block_k, Hkv, hd)``, so a selected block is DMA'd
once however many query heads read it; the head loop runs inside the
kernel.  The block's two minor dims are the cache's own (Hkv, hd), which is
what Mosaic's TPU tiling asks of a block.  GQA: query heads
[h*g, (h+1)*g) read KV head h — no head repetition is ever materialized.
Selected indices are pre-sorted ascending by the mask builder (contiguous
HBM streams, paper §5.2 reordering analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(*refs, block_k: int, nb: int, n_kv: int, scale: float,
            quant: bool, n_prefetch: int):
    # a paged call prefetches a 4th stream, the physical pages: it steers
    # the index maps only — the body masks from idx, the LOGICAL blocks,
    # which carry the key positions
    idx_ref, ok_ref, kvl_ref = refs[:3]
    refs = refs[n_prefetch:]
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b, j = pl.program_id(0), pl.program_id(1)
    g = q_ref.shape[2] // n_kv

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    kb = idx_ref[b, j]
    ok = ok_ref[b, j]
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (g, block_k), 1)
    mask = (kpos < kvl_ref[b]) & (ok > 0)
    # one load of the whole query block; each KV head slices its g rows
    q_all = q_ref[0, 0].astype(jnp.float32) * scale        # (Hq, hd)

    for h in range(n_kv):
        rows = slice(h * g, (h + 1) * g)
        q = q_all[rows]
        k = k_ref[0, :, h, :].astype(jnp.float32)          # (Bk, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quant:
            # dequant-on-gather: int8/fp8 cache rows land in VMEM narrow
            # and return to f32 against their per-row scales only here
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (g, Bk)
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[rows]                               # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero under the mask: a fully-invalid block would
        # otherwise contribute exp(NEG - NEG) = 1 while m is still at NEG
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (g, Bk)
        l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    @pl.when(j == nb - 1)
    def _fini():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _call(q, k, v, k_scale, v_scale, prefetch, block_row, *, block_k,
          interpret, name):
    """One pallas_call for the dense and paged variants.  ``prefetch`` is
    (idx, ok, kv_len[, pidx]); ``block_row(b, j, *prefetch_refs)`` gives the
    (batch, block) coordinates of the cache row-block to stream; ``name``
    names the kernel in compiled programs and profiles."""
    b, _, hq, hd = q.shape
    hkv = k.shape[2]
    nb = prefetch[0].shape[-1]
    quant = k_scale is not None

    def qmap(bi, ji, *refs):
        return (bi, 0, 0, 0)

    def kmap(bi, ji, *refs):
        return block_row(bi, ji, *refs) + (0, 0)

    def smap(bi, ji, *refs):
        return block_row(bi, ji, *refs) + (0,)

    in_specs = [pl.BlockSpec((1, 1, hq, hd), qmap),
                pl.BlockSpec((1, block_k, hkv, hd), kmap),
                pl.BlockSpec((1, block_k, hkv, hd), kmap)]
    args = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, block_k, hkv), smap)] * 2
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    kern = functools.partial(_kernel, block_k=block_k, nb=nb, n_kv=hkv,
                             scale=hd ** -0.5, quant=quant,
                             n_prefetch=len(prefetch))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hq, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((hq, hd), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hq, hd), q.dtype),
        interpret=interpret, name=name,
    )
    return fn(*(p.astype(jnp.int32) for p in prefetch), *args)


def dsa_decode_paged_gather_attention(q, k_pool, v_pool, idx, pidx, ok,
                                      kv_len, *, block_k: int = 128,
                                      k_scale=None, v_scale=None,
                                      interpret: bool = False) -> jax.Array:
    """Paged twin of ``dsa_decode_gather_attention``: the cache is one FLAT
    physical page pool (P*block_k, Hkv, hd) shared by all slots, and the
    selection arrives as DUAL scalar-prefetched streams — idx (B, nb) the
    LOGICAL block indices (position masking, unchanged kernel body) and
    pidx (B, nb) the same selection translated to PHYSICAL pages through
    the slot's page table (HBM->VMEM gather steering).  k_scale/v_scale:
    optional (P*block_k, Hkv) per-row scales of an int8/fp8 pool, streamed
    through the same physical-page index maps (dequant-on-gather).
    q: (B,1,Hq,hd).  Returns (B,1,Hq,hd)."""
    # pool rows are page-aligned by construction — no tail padding
    assert k_pool.shape[0] % block_k == 0, (k_pool.shape, block_k)

    def block_row(bi, ji, idx_ref, ok_ref, kvl_ref, pidx_ref):
        return (0, pidx_ref[bi, ji])

    quant = k_scale is not None
    return _call(q, k_pool[None], v_pool[None],
                 k_scale[None] if quant else None,
                 v_scale[None] if quant else None,
                 (idx, ok, kv_len, pidx), block_row, block_k=block_k,
                 interpret=interpret, name="dsa_decode_paged")


def dsa_decode_gather_attention(q, k_cache, v_cache, idx, ok, kv_len, *,
                                block_k: int = 128,
                                k_scale=None, v_scale=None,
                                interpret: bool = False) -> jax.Array:
    """q: (B,1,Hq,hd); k/v cache: (B,S,Hkv,hd); idx/ok: (B,nb);
    kv_len: (B,).  k_scale/v_scale: optional (B,S,Hkv) per-row scales of
    an int8/fp8 cache (dequant-on-gather).  Returns (B,1,Hq,hd)."""
    s_len = k_cache.shape[1]
    pad = -(-s_len // block_k) * block_k - s_len
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))

    def block_row(bi, ji, idx_ref, ok_ref, kvl_ref):
        return (bi, idx_ref[bi, ji])

    return _call(q, k_cache, v_cache, k_scale, v_scale, (idx, ok, kv_len),
                 block_row, block_k=block_k, interpret=interpret,
                 name="dsa_decode")
