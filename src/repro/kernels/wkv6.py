"""Chunked RWKV6 (wkv) linear attention — Pallas TPU kernel.

The sequential recurrence  S_t = diag(w_t) S_{t-1} + k_t^T v_t,
y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)  is O(S) steps of rank-1 updates —
terrible MXU utilization.  The chunked form processes C tokens per grid
step with three (C x hd) matmuls:

  cum_t = sum_{i<=t} log w_i                       (within chunk)
  y     = (r*e^{cum-logw}) S_0                      inter-chunk (state)
        + tril_strict[(r*e^{cum-logw}) (k*e^{-cum})^T] v     intra
        + diag((r*u*k).sum(-1)) v                   bonus term
  S_C   = diag(e^{cum_C}) S_0 + (k*e^{cum_C - cum})^T v

The state lives in VMEM scratch across the (innermost, sequential) chunk
axis of the grid.  cum is clamped at -30 so e^{-cum} stays in f32 range
(valid for per-chunk decay products down to ~1e-13; chunk=32 default).

  r,k,v,w: (B, H, S, hd)  ->  y: (B, H, S, hd)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLAMP = -30.0


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
            chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)              # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                 # (1, hd)

    logw = jnp.log(jnp.maximum(w, 1e-38))
    cum = jnp.cumsum(logw, axis=0)
    cum_c = jnp.clip(cum, CLAMP, 0.0)
    rr = r * jnp.exp(cum_c - logw)                   # r_t * A_{t-1}
    kk = k * jnp.exp(-cum_c)                         # k_s / A_s
    s0 = s_ref[...]                                  # (hd, hd)

    y_state = jax.lax.dot_general(rr, s0, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    scores = jax.lax.dot_general(rr, kk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(si < ti, scores, 0.0)         # strict lower triangle
    diag = jnp.sum(r * u * k, axis=1)                # (C,)
    y = y_state + jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y + diag[:, None] * v
    o_ref[0, 0] = y.astype(o_ref.dtype)

    cum_last = cum[-1:, :]                           # (1, hd)
    k_hat = k * jnp.exp(jnp.clip(cum_last - cum, CLAMP, 0.0))
    s_new = (jnp.exp(jnp.clip(cum_last, CLAMP, 0.0)).T * s0
             + jax.lax.dot_general(k_hat, v, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    s_ref[...] = s_new


def wkv6_chunked(r, k, v, w, u, *, chunk: int = 32,
                 interpret: bool = False) -> jax.Array:
    """r,k,v,w: (B,H,S,hd); u: (H,hd) -> y (B,H,S,hd)."""
    b, h, s, hd = r.shape
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    grid = (b, h, n_chunks)

    def xmap(bi, hi, ci):
        return (bi, hi, ci, 0)

    def umap(bi, hi, ci):
        return (hi, 0)

    kern = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    fn = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, chunk, hd), xmap)] * 4
        + [pl.BlockSpec((1, hd), umap)],
        out_specs=pl.BlockSpec((1, 1, chunk, hd), xmap),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret, name="wkv6",
    )
    return fn(r, k, v, w, u)
