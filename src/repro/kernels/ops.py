"""jit'd public wrappers for the Pallas kernels.

The kernels compile for the TPU.  ``interpret`` defaults to True on the
CPU backend only, so the same call sites run in CPU tests through the
Pallas interpreter; any other backend that is not a TPU is an error rather
than a silent detour through the interpreter.  Setting the environment
variable ``JAX_PALLAS_INTERPRET=1`` forces interpret mode — CI uses it in a
dedicated job so kernel-vs-XLA-twin equivalence is exercised explicitly.
Model code calls these through RunFlags(dsa_mode="kernel").

GSPMD cannot partition a Mosaic kernel, so under a serving mesh each
attention kernel runs per shard (``_per_shard``): it reads whole slot rows
and whole heads, and runs unchanged on the slots ("data") and heads
("model") its shard holds.
"""
from __future__ import annotations

import functools
import os

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, resolve_spec
from repro.kernels.dsa_attention import dsa_block_sparse_attention
from repro.kernels.dsa_chunk_prefill import (dsa_chunk_gather_attention,
                                             dsa_chunk_paged_gather_attention)
from repro.kernels.dsa_decode import (dsa_decode_gather_attention,
                                      dsa_decode_paged_gather_attention)
from repro.kernels.wkv6 import wkv6_chunked


def _default_interpret() -> bool:
    if os.environ.get("JAX_PALLAS_INTERPRET", "").lower() in ("1", "true"):
        return True
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels target the TPU; backend {backend!r} has no "
            "lowering (set JAX_PALLAS_INTERPRET=1 to run them interpreted)")
    return backend == "cpu"


def _per_shard(call, operands, n_heads):
    """``call(*arrays)`` on each shard of the active mesh (a plain call
    without one).  ``operands``: (array or None, axes) pairs, where axes
    name each dim "B" (slots: the mesh's batch axis), "H" (heads: the
    kv-head axis, resolved on ``n_heads`` KV heads so query and KV heads
    always split alike) or None (whole on every shard).  The output is
    (B, L, Hq, hd)."""
    arrays = [a for a, _ in operands if a is not None]
    mesh = current_mesh()
    if mesh is None:
        return call(*arrays)

    def ax(size, name):
        return (tuple(resolve_spec((size,), (name,), mesh=mesh)) + (None,))[0]

    b_ax = ax(arrays[0].shape[0], "batch")
    h_ax = ax(n_heads, "kv_heads")
    pick = {"B": b_ax, "H": h_ax, None: None}
    specs = tuple(P(*(pick[n] for n in axes))
                  for a, axes in operands if a is not None)
    return jax.shard_map(call, mesh=mesh, in_specs=specs,
                         out_specs=P(b_ax, None, h_ax, None),
                         check_vma=False)(*arrays)


def _scales(sc):
    return dict(zip(("k_scale", "v_scale"), sc))


_ROWS = ("B", None, "H", None)          # (B, L, H, hd) q / cache / output
_POOL = (None, "H", None)               # (P*block_k, Hkv, hd) page pool


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "window", "interpret"))
def dsa_attention(q, k, v, idx, valid, *, block_q=128, block_k=128,
                  causal=True, window=0, interpret=None):
    """q: (B,Lq,Hq,hd) [model layout]; k/v: (B,Lk,Hkv,hd);
    idx/valid: (B,nQb,nb).  Returns (B,Lq,Hq,hd)."""
    interpret = _default_interpret() if interpret is None else interpret

    def call(q, k, v, idx, valid):
        out = dsa_block_sparse_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), idx, valid, block_q=block_q,
            block_k=block_k, causal=causal, window=window,
            interpret=interpret)
        return out.transpose(0, 2, 1, 3)
    return _per_shard(call, [(q, _ROWS), (k, _ROWS), (v, _ROWS),
                             (idx, ("B", None, None)),
                             (valid, ("B", None, None))], k.shape[2])


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def dsa_decode(q, k_cache, v_cache, idx, ok, kv_len, *, block_k=128,
               k_scale=None, v_scale=None, interpret=None):
    """Fused DSA decode step (decode fast path).

    q: (B,1,Hq,hd) [model layout]; k/v cache: (B,S,Hkv,hd); idx/ok: (B,nb)
    selected cache-block indices; kv_len: (B,).  k_scale/v_scale: optional
    (B,S,Hkv) per-row scales of an int8/fp8 cache (dequant-on-gather
    inside the kernel).  Returns (B,1,Hq,hd).
    The pure-XLA twin is core.attention.dsa_decode_block_attention.
    """
    interpret = _default_interpret() if interpret is None else interpret

    def call(q, k, v, idx, ok, kv_len, *sc):
        return dsa_decode_gather_attention(q, k, v, idx, ok, kv_len,
                                           block_k=block_k,
                                           interpret=interpret, **_scales(sc))
    return _per_shard(call, [(q, _ROWS), (k_cache, _ROWS), (v_cache, _ROWS),
                             (idx, ("B", None)), (ok, ("B", None)),
                             (kv_len, ("B",)), (k_scale, ("B", None, "H")),
                             (v_scale, ("B", None, "H"))], k_cache.shape[2])


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def dsa_decode_paged(q, k_pool, v_pool, idx, pidx, ok, kv_len, *,
                     block_k=128, k_scale=None, v_scale=None,
                     interpret=None):
    """Fused DSA decode step over a PAGED cache (flat physical page pool).

    q: (B,1,Hq,hd) [model layout]; k/v pool: (P*block_k,Hkv,hd); idx/ok:
    (B,nb) selected LOGICAL cache-block indices; pidx: (B,nb) the same
    selection as PHYSICAL pages; kv_len: (B,).  k_scale/v_scale: optional
    (P*block_k,Hkv) per-row pool scales.  Returns (B,1,Hq,hd).
    The pure-XLA twin is core.attention.dsa_decode_paged_block_attention.
    """
    interpret = _default_interpret() if interpret is None else interpret

    def call(q, k, v, idx, pidx, ok, kv_len, *sc):
        return dsa_decode_paged_gather_attention(
            q, k, v, idx, pidx, ok, kv_len, block_k=block_k,
            interpret=interpret, **_scales(sc))
    return _per_shard(call, [(q, _ROWS), (k_pool, _POOL), (v_pool, _POOL),
                             (idx, ("B", None)), (pidx, ("B", None)),
                             (ok, ("B", None)), (kv_len, ("B",)),
                             (k_scale, (None, "H")), (v_scale, (None, "H"))],
                      k_pool.shape[1])


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def dsa_chunk_prefill(q, k_cache, v_cache, idx, ok, q_off, kv_len, *,
                      block_q=128, block_k=128, k_scale=None, v_scale=None,
                      interpret=None):
    """Fused DSA chunk-prefill step (chunk-append fast path).

    q: (B,C,Hq,hd) [model layout]; k/v cache: (B,S,Hkv,hd); idx/ok:
    (B,C//block_q,nb) selected cache-block indices per chunk query block;
    q_off: (B,) global chunk start positions; kv_len: (B,).
    k_scale/v_scale: optional (B,S,Hkv) per-row scales of an int8/fp8
    cache.  Returns (B,C,Hq,hd).  The pure-XLA twin is
    core.attention.dsa_chunk_block_attention.
    """
    interpret = _default_interpret() if interpret is None else interpret

    def call(q, k, v, idx, ok, q_off, kv_len, *sc):
        return dsa_chunk_gather_attention(
            q, k, v, idx, ok, q_off, kv_len, block_q=block_q,
            block_k=block_k, interpret=interpret, **_scales(sc))
    return _per_shard(call, [(q, _ROWS), (k_cache, _ROWS), (v_cache, _ROWS),
                             (idx, ("B", None, None)),
                             (ok, ("B", None, None)), (q_off, ("B",)),
                             (kv_len, ("B",)), (k_scale, ("B", None, "H")),
                             (v_scale, ("B", None, "H"))], k_cache.shape[2])


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def dsa_chunk_prefill_paged(q, k_pool, v_pool, idx, pidx, ok, q_off,
                            kv_len, *, block_q=128, block_k=128,
                            k_scale=None, v_scale=None, interpret=None):
    """Fused DSA chunk-prefill step over a PAGED cache.

    q: (B,C,Hq,hd) [model layout]; k/v pool: (P*block_k,Hkv,hd); idx/ok:
    (B,C//block_q,nb) selected LOGICAL cache-block indices; pidx the same
    selection as PHYSICAL pages; q_off/kv_len: (B,).  k_scale/v_scale:
    optional (P*block_k,Hkv) per-row pool scales.  Returns (B,C,Hq,hd).
    """
    interpret = _default_interpret() if interpret is None else interpret

    def call(q, k, v, idx, pidx, ok, q_off, kv_len, *sc):
        return dsa_chunk_paged_gather_attention(
            q, k, v, idx, pidx, ok, q_off, kv_len, block_q=block_q,
            block_k=block_k, interpret=interpret, **_scales(sc))
    return _per_shard(call, [(q, _ROWS), (k_pool, _POOL), (v_pool, _POOL),
                             (idx, ("B", None, None)),
                             (pidx, ("B", None, None)),
                             (ok, ("B", None, None)), (q_off, ("B",)),
                             (kv_len, ("B",)), (k_scale, (None, "H")),
                             (v_scale, (None, "H"))], k_pool.shape[1])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk=32, interpret=None):
    """r,k,v,w: (B,S,H,hd) [model layout]; u: (H,hd) -> (B,S,H,hd)."""
    interpret = _default_interpret() if interpret is None else interpret
    rt, kt2, vt, wt = (t.transpose(0, 2, 1, 3) for t in (r, k, v, w))
    y = wkv6_chunked(rt, kt2, vt, wt, u, chunk=chunk, interpret=interpret)
    return y.transpose(0, 2, 1, 3)
