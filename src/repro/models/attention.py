"""Attention modules (GQA / SWA / MLA / cross) with first-class DSA.

Each ``init_*`` returns ``(params, specs)`` where specs is a parallel tree of
logical-axis tuples consumed by repro.distributed.sharding.

DSA integration (paper §3): when ``cfg.dsa.enabled`` and the run flags ask
for it, the module computes approximate scores S~ through the prediction
path, derives the dynamic sparse pattern, executes the sparse attention, and
returns the MSE term for the joint loss (Eq. 7) in ``aux``.

Decode fast path (RunFlags(mode="decode", long_context=True)): the KV cache
carries the predicted-key cache ``kt`` (B, S, k) AND its block-pooled twin
``ktb`` (B, ceil(S/block_k), k) — running block sums, so per-step selection
is a top-k over S/block_k block scores instead of S token scores.
``dsa_mode`` picks the execution path per step:

Continuous batching: the cache position ``pos`` is PER SLOT — a (B,) vector
rather than a shared scalar — so every batch row decodes at its own cache
depth (its own RoPE position, write slot, and ragged ``kv_len``).  An
optional ``active`` (B,) bool gates each slot: inactive slots freeze their
``pos``, drop their cache writes (out-of-bounds scatter indices, which JAX
drops), and attend with ``kv_len = 0`` so a retired/unadmitted slot costs no
attention support and can never leak state into a later tenant.

  faithful  token-granularity top-k over the full ``kt`` cache
            (core.attention.dsa_decode_attention — paper-faithful)
  block     block-granularity selection over ``ktb`` + XLA block gather
            (core.attention.dsa_decode_block_attention)
  kernel    same selection, fused Pallas gather+attend kernel
            (repro.kernels.dsa_decode via kernels.ops.dsa_decode)

The long-context cache never wraps (it is only allocated when
``cfg.swa_window == 0`` and sized to max_len), so block sums stay exact —
each cache slot is written once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import attention as A
from repro.core import masks as M
from repro.core import prediction as PRED
from repro.core import quantization as Q
from repro.distributed.sharding import shard
from repro.models.common import dense_init, rms_norm, rope


# Trailing tokens always attended at decode (keeps softmax support and the
# local neighbourhood regardless of prediction quality; DESIGN.md §4).
DECODE_LOCAL = 64

# The canonical DSA execution modes.  Engines, the scheduler, and Request
# all validate against THIS set (an unknown string used to fall through to
# silent dense behavior).
DSA_MODES = ("off", "faithful", "block", "kernel")

# Mixed-precision serving knobs (Energon, arXiv 2110.09310): the narrow
# dtypes the SELECTION caches (kt/ktb) and the resident KV cache may be
# stored in.  Selection is ranking-only so block top-k INDICES are the
# exactness surface; gathered top-k attention always runs full precision.
SELECT_DTYPES = ("float32", "int8")
KV_QUANT_DTYPES = (None, "int8", "fp8")
_KV_QUANT_JNP = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}

# Page granularity of the PAGED resident cache when the arch has no DSA
# decode cache (with one, the page size is cfg.dsa.block_k so pages line up
# with the block-pooled ktb rows and the gather kernels' block streams).
PAGE_SIZE = 16


def cache_page_size(cfg: ArchConfig, flags: RunFlags) -> int:
    """Row count of one physical page of a paged resident cache."""
    dsa_decode = (cfg.dsa.enabled and flags.long_context
                  and not cfg.swa_window)
    return cfg.dsa.block_k if dsa_decode else PAGE_SIZE


@dataclasses.dataclass(frozen=True)
class RunFlags:
    """Runtime execution choices (not architecture).

    dsa_mode at decode selects the long-context execution path (see module
    docstring): "faithful" = token top-k, "block" = block-pooled selection +
    XLA gather, "kernel" = block-pooled selection + fused Pallas kernel.
    """
    mode: str = "train"            # train | prefill | decode
    dsa_mode: str = "block"        # off | faithful | block | kernel
    with_mse: bool = True          # compute L_MSE (training)
    long_context: bool = False     # DSA decode over predicted-key cache
    mse_stride_cap: int = 512      # subsampled-MSE rows in block mode
    decode_window: int = 0         # ring-buffer cache size override
    # speculative decoding: route the chunk-append path through the
    # per-row DECODE-exact verify attention (repro.inference.speculative)
    spec_verify: bool = False
    # serving MoE option: route prefill through the decode-dense expert
    # path so whole-prompt prefill and chunk steps are token-exact
    # (Engine(moe_prefill="dense"))
    moe_dense: bool = False
    # mixed-precision serving (Energon): "int8" stores the predicted-key
    # score caches kt/ktb as int8 with per-row scales and runs the per-step
    # selection matmul in int8, dequantizing only at the top-k reduction
    select_dtype: str = "float32"
    # int8/fp8 KV-cache storage with dequant-on-gather; None = full precision
    kv_quant: Optional[str] = None
    # observability (inference.telemetry): stash the DSA block-selection
    # outputs into the returned cache under "sel_idx"/"sel_ok"/"sel_kv".
    # Only the sampled telemetry PROBE dispatch sets this — never a
    # scan-carried segment (the extra keys make the cache tree asymmetric
    # in/out, which a scan carry would reject).
    sel_probe: bool = False


def dsa_active(cfg: ArchConfig, flags: RunFlags) -> bool:
    return cfg.dsa.enabled and flags.dsa_mode != "off"


def _int8_select_scores(q_t, key_q, key_s, *, block_k: int = 1):
    """Predicted-score matmul against an int8-stored key cache.

    Quantizes the predicted queries per row, accumulates int8 x int8 in
    int32, and dequantizes only at the top-k reduction (the Energon rule:
    selection is ranking-only, so this is the whole low-precision path).
    q_t (B, R, kp) float; key_q (B, N, kp) int8 with per-row scales key_s
    (B, N) -> (B, R, N) float32 scores (divided by block_k for pooled
    block caches)."""
    qq, qs = Q.quant_store(q_t, axis=-1)
    s_int = jnp.einsum("brk,bnk->brn", qq, key_q,
                       preferred_element_type=jnp.int32)
    return M.dequant_topk_scores(
        s_int, qs[..., None] * key_s[:, None, :], block_k=block_k)


# ---------------------------------------------------------------------------
# GQA attention (yi / danube / qwen / stablelm / mixtral / jamba-attn / ...)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, cross: bool = False,
                   dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    params = {
        "wq": dense_init(ks[0], (d, nq), dtype=dtype),
        "wk": dense_init(ks[1], (d, nkv), dtype=dtype),
        "wv": dense_init(ks[2], (d, nkv), dtype=dtype),
        "wo": dense_init(ks[3], (nq, d), dtype=dtype),
    }
    specs = {
        "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"), "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        params.update(bq=jnp.zeros((nq,), dtype), bk=jnp.zeros((nkv,), dtype),
                      bv=jnp.zeros((nkv,), dtype))
        specs.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    if cfg.dsa.enabled and not cross:
        params["dsa"] = PRED.init_predictor(ks[4], d, cfg.dsa.sigma, dtype)
        specs["dsa"] = PRED.predictor_specs()
    return params, specs


def _proj_qkv(params, cfg: ArchConfig, x, x_kv=None):
    hd = cfg.resolved_head_dim
    xk = x if x_kv is None else x_kv
    q = x @ params["wq"]
    k = xk @ params["wk"]
    v = xk @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    b, lq = x.shape[:2]
    lk = xk.shape[1]
    q = q.reshape(b, lq, cfg.n_heads, hd)
    k = k.reshape(b, lk, cfg.n_kv_heads, hd)
    v = v.reshape(b, lk, cfg.n_kv_heads, hd)
    return q, k, v


def _mean_head_scores(q, k, stride: int = 1):
    """Mean-over-heads QK^T — the MSE target S of Eq. 6 (GQA: kv repeated)."""
    hq, hkv = q.shape[2], k.shape[2]
    g = hq // hkv
    qs = q[:, ::stride]
    s = jnp.einsum("bqhgd,bkhd->bqk",
                   qs.reshape(*qs.shape[:2], hkv, g, -1), k)
    return s / hq


def _dsa_train_mask_and_aux(params, cfg: ArchConfig, flags: RunFlags,
                            x, q, k, causal: bool, x_kv=None):
    """Compute the DSA pattern + MSE aux for train/prefill."""
    dsa = cfg.dsa
    b, lq = x.shape[:2]
    lk = (x if x_kv is None else x_kv).shape[1]
    aux: Dict[str, jax.Array] = {}
    # token-granularity path: the paper-faithful mode, also the fallback
    # when the sequence isn't block-divisible (whisper's 1500-frame encoder)
    if (flags.dsa_mode == "faithful" or lq % dsa.block_q
            or lk % dsa.block_k):
        s_t = PRED.predict_scores(params["dsa"], x, x_kv, bits=dsa.quant_bits)
        pm = A._pos_mask(lq, lk, causal, cfg.swa_window)
        valid = None if pm is None else jnp.broadcast_to(pm, (b, lq, lk))
        keep = M.keep_count(lk, dsa.sparsity)
        mask = M.row_topk_mask(s_t, keep, valid)
        if flags.with_mse:
            aux["mse"] = PRED.mse_loss(_mean_head_scores(q, k), s_t)
        return ("token", mask), aux
    # block mode (TPU-native)
    bs = PRED.predict_block_scores(
        params["dsa"], x, x_kv, bits=dsa.quant_bits,
        block_q=dsa.block_q, block_k=dsa.block_k, pooled=True)
    n_kb = lk // dsa.block_k
    nb_keep = min(n_kb, max(dsa.min_blocks + dsa.local_blocks,
                            M.keep_count(n_kb, dsa.sparsity)))
    wb = cfg.swa_window // dsa.block_k if cfg.swa_window else 0
    idx, ok = M.block_topk_indices(
        bs, nb_keep, causal=causal, window_blocks=wb,
        local_blocks=dsa.local_blocks, sort=dsa.sort_indices)
    if flags.with_mse:
        stride = max(1, lq // flags.mse_stride_cap)
        q_t, k_t = PRED.predict_qk(params["dsa"], x, x_kv, dsa.quant_bits)
        s_t_sub = jnp.einsum("bqk,bsk->bqs", q_t[:, ::stride], k_t)
        aux["mse"] = PRED.mse_loss(_mean_head_scores(q, k, stride), s_t_sub)
    return ("block", (idx, ok)), aux


def apply_attention(params, cfg: ArchConfig, flags: RunFlags, x, *,
                    x_kv=None, cache=None, causal=True, use_rope=True,
                    pos_offset=0, active=None, chunk_len=None,
                    sel_len=None):
    """Returns (out, new_cache, aux).  x: (B, S, d).

    active: optional (B,) bool slot mask (decode only) — see module
    docstring; inactive slots freeze their cache and attend nothing.
    chunk_len: optional (B,) — chunk-append mode (see _apply_chunk): x is a
    C-token chunk appended at each slot's ``pos``; rows past chunk_len are
    padding.  sel_len: optional static int — the chunk mode's
    attention/selection geometry (default: the full cache length).
    """
    dsa = cfg.dsa
    hd = cfg.resolved_head_dim
    aux: Dict[str, jax.Array] = {}
    cross = x_kv is not None or (cache is not None and "ck" in cache)

    if flags.mode == "decode" and not cross:
        if cache is not None and "page_tbl" in cache:
            # paged resident cache: single-token decode only — chunked
            # prefill and speculative verify run on dense staging caches
            # (the scheduler gates them; inference.engine.can_page)
            assert chunk_len is None, "paged caches decode 1 token at a time"
            return _apply_paged_decode(params, cfg, flags, x, cache,
                                       use_rope, active)
        if chunk_len is not None:
            if flags.spec_verify:
                return _apply_verify(params, cfg, flags, x, cache, use_rope,
                                     active, chunk_len)
            return _apply_chunk(params, cfg, flags, x, cache, use_rope,
                                active, chunk_len, sel_len)
        return _apply_decode(params, cfg, flags, x, cache, use_rope, active)

    if cross and flags.mode == "decode":   # cross decode: static enc k/v cache
        q = (x @ params["wq"]).reshape(*x.shape[:2], cfg.n_heads, hd)
        if cfg.qkv_bias:
            q = q + params["bq"].reshape(cfg.n_heads, hd)
        out = A.decode_attention(q, cache["ck"], cache["cv"])
        return out.reshape(*x.shape[:2], -1) @ params["wo"], cache, aux

    q, k, v = _proj_qkv(params, cfg, x, x_kv)
    if use_rope and not cross:
        pos = jnp.arange(x.shape[1]) + pos_offset
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", "qkv")
    k = shard(k, "batch", "seq", "kv_heads", "qkv")

    if dsa_active(cfg, flags) and not cross:
        (kind, pat), aux = _dsa_train_mask_and_aux(
            params, cfg, flags, x, q, k, causal, x_kv)
        if kind == "token":
            out = A.dense_attention(q, k, v, causal=causal,
                                    window=cfg.swa_window, token_mask=pat)
        elif flags.dsa_mode == "kernel":
            from repro.kernels.ops import dsa_attention as dsa_kernel
            idx, ok = pat
            out = dsa_kernel(q, k, v, idx, ok, block_q=dsa.block_q,
                             block_k=dsa.block_k, causal=causal,
                             window=cfg.swa_window)
        else:
            idx, ok = pat
            out = A.dsa_sparse_attention(
                q, k, v, idx, ok, block_q=dsa.block_q, block_k=dsa.block_k,
                causal=causal, window=cfg.swa_window)
    elif x.shape[1] <= 1024:
        out = A.dense_attention(q, k, v, causal=causal, window=cfg.swa_window)
    else:
        out = A.flash_attention(q, k, v, causal=causal, window=cfg.swa_window)

    new_cache = cache
    if flags.mode == "prefill" and cache is not None:
        if cross:
            new_cache = dict(cache, ck=k.astype(cache["ck"].dtype),
                             cv=v.astype(cache["cv"].dtype))
        else:
            new_cache = _fill_cache(cfg, flags, cache, k, v, params, x)
    out = shard(out, "batch", "seq", "heads", "qkv")
    out = out.reshape(*x.shape[:2], -1) @ params["wo"]
    return out, new_cache, aux


def init_cache_attention(cfg: ArchConfig, batch: int, max_len: int,
                         flags: RunFlags, dtype=jnp.bfloat16, pages=None):
    hd = cfg.resolved_head_dim
    s = min(max_len, flags.decode_window or max_len,
            cfg.swa_window or max_len)
    dsa_decode = cfg.dsa.enabled and flags.long_context and not cfg.swa_window
    if dsa_decode:
        # round the cache up to a block_k multiple: the block-gather decode
        # paths would otherwise jnp.pad the ENTIRE cache every step (an
        # O(S) copy inside the generation scan)
        s = -(-s // cfg.dsa.block_k) * cfg.dsa.block_k
    # mixed-precision layout: narrow storage dtypes + float32 per-row scale
    # leaves ("k_s"/"v_s"/"kt_s"/"ktb_s").  Scale-leaf PRESENCE is what the
    # apply paths branch on — structure is static under jit, so every
    # (flags, cache) pair keeps one compiled program and the compile set
    # stays fixed.
    kv_dt = _KV_QUANT_JNP[flags.kv_quant] if flags.kv_quant else dtype
    sel_q = flags.select_dtype == "int8"
    kt_dt = jnp.int8 if sel_q else dtype
    if pages is not None:
        # PAGED resident layout: one FLAT physical pool of ``pages`` pages
        # of ``bk`` rows each (page p owns pool rows [p*bk, (p+1)*bk)),
        # indirected by a per-slot page table over the logical [0, s)
        # geometry.  Page 0 is the permanent ZERO page — never allocated,
        # never written — so unmapped table entries resolve to zero rows
        # and a gathered logical view is byte-identical to the dense
        # zero-initialized cache.  Requires a non-wrapping cache
        # (inference.engine.can_page gates SWA/windowed archs out).
        assert not cfg.swa_window and not flags.decode_window, \
            "paged caches require a non-wrapping layout"
        bk = cfg.dsa.block_k if dsa_decode else PAGE_SIZE
        assert s % bk == 0, (s, bk)
        c = {
            "k": jnp.zeros((pages * bk, cfg.n_kv_heads, hd), kv_dt),
            "v": jnp.zeros((pages * bk, cfg.n_kv_heads, hd), kv_dt),
            "pos": jnp.zeros((batch,), jnp.int32),
            "page_tbl": jnp.zeros((batch, s // bk), jnp.int32),
        }
        if flags.kv_quant:
            c["k_s"] = jnp.zeros((pages * bk, cfg.n_kv_heads), jnp.float32)
            c["v_s"] = jnp.zeros((pages * bk, cfg.n_kv_heads), jnp.float32)
        if dsa_decode:
            kp = PRED.predictor_k(cfg.d_model, cfg.dsa.sigma)
            c["kt"] = jnp.zeros((pages * bk, kp), kt_dt)
            # one ktb row per PAGE (page size == block_k): the block-pooled
            # score cache pages with the rows it summarizes
            c["ktb"] = jnp.zeros((pages, kp), kt_dt)
            if sel_q:
                c["kt_s"] = jnp.zeros((pages * bk,), jnp.float32)
                c["ktb_s"] = jnp.zeros((pages,), jnp.float32)
        return c
    c = {
        "k": jnp.zeros((batch, s, cfg.n_kv_heads, hd), kv_dt),
        "v": jnp.zeros((batch, s, cfg.n_kv_heads, hd), kv_dt),
        # per-slot cache depth: (B,) so continuous batching can decode rows
        # at independent positions (slot-ragged batches)
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if flags.kv_quant:
        c["k_s"] = jnp.zeros((batch, s, cfg.n_kv_heads), jnp.float32)
        c["v_s"] = jnp.zeros((batch, s, cfg.n_kv_heads), jnp.float32)
    if dsa_decode:
        kp = PRED.predictor_k(cfg.d_model, cfg.dsa.sigma)
        c["kt"] = jnp.zeros((batch, s, kp), kt_dt)
        # block-pooled twin: running sums of kt per block_k-sized cache
        # block; per-step selection reads these S/block_k scores instead of
        # S token scores (decode fast path)
        c["ktb"] = jnp.zeros((batch, s // cfg.dsa.block_k, kp), kt_dt)
        if sel_q:
            c["kt_s"] = jnp.zeros((batch, s), jnp.float32)
            c["ktb_s"] = jnp.zeros((batch, s // cfg.dsa.block_k),
                                   jnp.float32)
    return c


def cache_specs_attention(cache) -> Dict:
    if "page_tbl" in cache:
        out = {"k": ("pages", "kv_heads", "qkv"),
               "v": ("pages", "kv_heads", "qkv"),
               "pos": ("batch",), "page_tbl": ("batch", None)}
        if "k_s" in cache:
            out["k_s"] = ("pages", "kv_heads")
            out["v_s"] = ("pages", "kv_heads")
        if "kt" in cache:
            out["kt"] = ("pages", "pred_k")
            out["ktb"] = ("pages", "pred_k")
        if "kt_s" in cache:
            out["kt_s"] = ("pages",)
            out["ktb_s"] = ("pages",)
        return out
    out = {"k": ("batch", "cache_seq", "kv_heads", "qkv"),
           "v": ("batch", "cache_seq", "kv_heads", "qkv"),
           "pos": ("batch",)}
    if "k_s" in cache:
        out["k_s"] = ("batch", "cache_seq", "kv_heads")
        out["v_s"] = ("batch", "cache_seq", "kv_heads")
    if "kt" in cache:
        out["kt"] = ("batch", "cache_seq", "pred_k")
    if "ktb" in cache:
        out["ktb"] = ("batch", "blocks", "pred_k")
    if "kt_s" in cache:
        out["kt_s"] = ("batch", "cache_seq")
        out["ktb_s"] = ("batch", "blocks")
    return out


def _fill_cache(cfg, flags, cache, k, v, params, x):
    if cache is None:
        return None
    s = cache["k"].shape[1]
    t = k.shape[1]

    def ring(buf):
        """Place token i at cache slot i % s (ring-aligned for decode)."""
        if t <= s:
            return buf
        tail = buf[:, -s:]
        return jnp.roll(tail, (t - s) % s, axis=1)

    kc, vc = ring(k), ring(v)
    new = dict(cache)
    if "k_s" in cache:
        # quantized KV storage: narrow rows + per-(token, head) scales
        kq, ks = Q.quant_store(kc, axis=-1, dtype=flags.kv_quant)
        vq, vs = Q.quant_store(vc, axis=-1, dtype=flags.kv_quant)
        new["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], kq, 0, axis=1)
        new["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], vq, 0, axis=1)
        new["k_s"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_s"], ks, 0, axis=1)
        new["v_s"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_s"], vs, 0, axis=1)
    else:
        new["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"].astype(kc.dtype), kc.astype(cache["k"].dtype), 0,
            axis=1)
        new["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"].astype(vc.dtype), vc.astype(cache["v"].dtype), 0,
            axis=1)
    new["pos"] = jnp.full((k.shape[0],), t, jnp.int32)
    if "kt" in cache:
        _, k_t = PRED.predict_qk(params["dsa"], x, None, cfg.dsa.quant_bits)
        bkd = cfg.dsa.block_k
        n_kb = cache["ktb"].shape[1]
        pad = n_kb * bkd - s
        if "kt_s" in cache:
            ktq, kts = Q.quant_store(ring(k_t), axis=-1)
            new["kt"] = jax.lax.dynamic_update_slice_in_dim(
                cache["kt"], ktq, 0, axis=1)
            new["kt_s"] = jax.lax.dynamic_update_slice_in_dim(
                cache["kt_s"], kts, 0, axis=1)
            # block sums' source of truth is the DEQUANTIZED kt rows, so
            # chunked fills and truncate rebuilds reproduce them exactly
            ktd = Q.dequant(new["kt"], new["kt_s"])
            ktp = (jnp.pad(ktd, ((0, 0), (0, pad), (0, 0))) if pad else ktd)
            sums = ktp.reshape(ktp.shape[0], n_kb, bkd, -1).sum(axis=2)
            new["ktb"], new["ktb_s"] = Q.quant_store(sums, axis=-1)
            return new
        new["kt"] = jax.lax.dynamic_update_slice_in_dim(
            cache["kt"].astype(k_t.dtype), ring(k_t).astype(cache["kt"].dtype),
            0, axis=1)
        # rebuild the block-pooled score cache from the freshly filled kt
        # (unwritten tail slots are zero, so plain block sums are exact)
        ktp = jnp.pad(new["kt"], ((0, 0), (0, pad), (0, 0))) if pad else new["kt"]
        new["ktb"] = ktp.reshape(ktp.shape[0], n_kb, bkd, -1).sum(axis=2)
    return new


def _slot_pos(cache, b):
    """Per-slot cache depth (B,); tolerates legacy scalar ``pos`` caches."""
    pos = cache["pos"]
    return jnp.full((b,), pos, jnp.int32) if pos.ndim == 0 else pos


def _kv_views(cache, kc, vc):
    """Full-precision views of (possibly quantized) k/v caches for the
    NON-gathered attend paths; gathered paths dequant after their gathers
    (core.attention twins / the Pallas kernels) instead."""
    if "k_s" in cache:
        return Q.dequant(kc, cache["k_s"]), Q.dequant(vc, cache["v_s"])
    return kc, vc


def _apply_decode(params, cfg: ArchConfig, flags: RunFlags, x, cache,
                  use_rope, active=None):
    """Single-token decode with KV cache (ring buffer under SWA).

    ``pos`` is per slot, so each batch row decodes at its own depth.  With
    ``active`` (B,) given, inactive rows freeze: their write slot is pushed
    out of bounds (JAX drops OOB scatter updates), pos does not advance,
    and kv_len is zeroed so they contribute no attention support.
    """
    b = x.shape[0]
    pos = _slot_pos(cache, b)                              # (B,)
    with jax.named_scope("qkv"):
        q, k, v = _proj_qkv(params, cfg, x)
        if use_rope:
            p = pos[:, None]                               # per-row positions
            q = rope(q, p, cfg.rope_theta)
            k = rope(k, p, cfg.rope_theta)
    # slot-axis data parallelism (serving mesh): q and the cache carry stay
    # sharded over "batch" so the fused segment scan never gathers them
    q = shard(q, "batch", None, "heads", "qkv")
    s = cache["k"].shape[1]
    with jax.named_scope("kv_write"):
        slot = jnp.where(jnp.asarray(s) > pos, pos, pos % s)  # SWA ring
        wslot = slot if active is None else jnp.where(active, slot, s)
        rows = jnp.arange(b)
        if "k_s" in cache:
            k1, ks = Q.quant_store(k[:, 0], axis=-1, dtype=flags.kv_quant)
            v1, vs = Q.quant_store(v[:, 0], axis=-1, dtype=flags.kv_quant)
        else:
            k1, v1 = k[:, 0].astype(cache["k"].dtype), v[:, 0].astype(
                cache["v"].dtype)
        kc = cache["k"].at[rows, wslot].set(k1, mode="drop")
        vc = cache["v"].at[rows, wslot].set(v1, mode="drop")
        kc = shard(kc, "batch", "cache_seq", "kv_heads", "qkv")
        vc = shard(vc, "batch", "cache_seq", "kv_heads", "qkv")
        new_pos = (pos + 1 if active is None
                   else pos + active.astype(jnp.int32))
        new = dict(cache, k=kc, v=vc, pos=new_pos)
        if "k_s" in cache:
            new["k_s"] = shard(
                cache["k_s"].at[rows, wslot].set(ks, mode="drop"),
                "batch", "cache_seq", "kv_heads")
            new["v_s"] = shard(
                cache["v_s"].at[rows, wslot].set(vs, mode="drop"),
                "batch", "cache_seq", "kv_heads")
    kv_len = jnp.minimum(pos + 1, s).astype(jnp.int32)
    if active is not None:
        kv_len = jnp.where(active, kv_len, 0)
    if "kt" in cache:
        out = _dsa_decode(params, cfg, flags, x, q, kc, vc, new, wslot,
                          kv_len)
    else:
        with jax.named_scope("attend"):
            kc, vc = _kv_views(new, kc, vc)
            # SWA window semantics: init_cache_attention sizes the ring
            # buffer at s = min(max_len, decode_window, swa_window) slots, so
            # with SWA on (s <= window) the buffer can never hold more than
            # one window of live tokens — the window is enforced
            # STRUCTURALLY and masking reduces to kv_len validity.  A
            # positional window over *slot* indices would be wrong after
            # wrap-around (slot order != temporal order); the explicit mask
            # below is only correct for externally built caches that are
            # larger than the window and not yet wrapped.  Pinned by
            # tests/test_decode_fastpath.py::test_swa_window_ring_wrap.
            win = cfg.swa_window or 0
            out = A.decode_attention(q, kc, vc, kv_len=kv_len,
                                     window=win if win and s > win else 0)
    with jax.named_scope("attend"):
        out = shard(out, "batch", None, "heads", "qkv")
        out = out.reshape(b, 1, -1) @ params["wo"]
    return out, new, {}


def _dsa_decode(params, cfg: ArchConfig, flags: RunFlags, x, q, kc, vc,
                new, wslot, kv_len):
    """DSA long-context decode step: update the prediction-path caches,
    select cache rows/blocks from predicted scores, gather + attend.

    Mutates ``new`` in place with the updated kt/ktb caches and returns the
    attention output (B, 1, Hq, hd).  Sub-quadratic: O(S*k) ("faithful") or
    O(S/block_k * k) ("block"/"kernel") prediction + O(gathered * d) attend.
    ``wslot`` is the per-row write slot; out-of-bounds rows (frozen slots)
    drop their kt/ktb updates.
    """
    dsa = cfg.dsa
    b, s = kc.shape[0], kc.shape[1]
    rows = jnp.arange(b)
    with jax.named_scope("dsa_predict"):
        q_t, k_t = PRED.predict_qk(params["dsa"], x, None, dsa.quant_bits)
        if "kt_s" in new:
            ktq, kts = Q.quant_store(k_t[:, 0], axis=-1)
            new["kt"] = shard(new["kt"].at[rows, wslot].set(ktq, mode="drop"),
                              "batch", "cache_seq", "pred_k")
            new["kt_s"] = shard(
                new["kt_s"].at[rows, wslot].set(kts, mode="drop"),
                "batch", "cache_seq")
        else:
            new["kt"] = shard(new["kt"].at[rows, wslot].set(
                k_t[:, 0].astype(new["kt"].dtype), mode="drop"),
                "batch", "cache_seq", "pred_k")
    k_scale = new.get("k_s")
    v_scale = new.get("v_s")
    keep = M.keep_count(s, dsa.sparsity)
    if flags.dsa_mode == "off":
        # per-request dsa_mode override on a long-context engine: dense
        # decode over the full cache; kt stays maintained (ktb, like the
        # faithful path, is rebuilt at each admission's prefill)
        with jax.named_scope("attend"):
            kd, vd = _kv_views(new, kc, vc)
            return A.decode_attention(q, kd, vd, kv_len=kv_len)
    if flags.dsa_mode == "faithful":
        # paper-faithful token granularity: top-k over all S cached scores
        with jax.named_scope("dsa_select"):
            if "kt_s" in new:
                s_tilde = _int8_select_scores(q_t, new["kt"],
                                              new["kt_s"])[:, 0]
            else:
                s_tilde = jnp.einsum("bok,bsk->bs", q_t.astype(jnp.float32),
                                     new["kt"].astype(jnp.float32))
        with jax.named_scope("attend"):
            kd, vd = _kv_views(new, kc, vc)
            return A.dsa_decode_attention(q, kd, vd, s_tilde, keep=keep,
                                          kv_len=kv_len, local=DECODE_LOCAL)
    # block granularity (decode fast path): maintain running block sums of
    # kt, score S/block_k blocks, select, then gather whole blocks.  The
    # long-context cache never wraps (module docstring), so the slot being
    # written was zero and a plain scatter-add keeps the block sum exact
    # (frozen rows carry an OOB block index and drop their add).
    bkd = dsa.block_k
    jb = wslot // bkd
    n_kb = new["ktb"].shape[1]
    if "ktb_s" in new:
        # int8 block sums can't scatter-add across scales: gather the
        # touched block, dequantize, add the new row, requantize, set
        with jax.named_scope("dsa_predict"):
            jc = jnp.minimum(jb, n_kb - 1)
            old = Q.dequant(new["ktb"][rows, jc], new["ktb_s"][rows, jc])
            bq_, bs_ = Q.quant_store(old + k_t[:, 0], axis=-1)
            new["ktb"] = shard(new["ktb"].at[rows, jb].set(bq_, mode="drop"),
                               "batch", "blocks", "pred_k")
            new["ktb_s"] = shard(
                new["ktb_s"].at[rows, jb].set(bs_, mode="drop"),
                "batch", "blocks")
        with jax.named_scope("dsa_select"):
            s_blk = _int8_select_scores(q_t, new["ktb"], new["ktb_s"],
                                        block_k=bkd)[:, 0]
    else:
        with jax.named_scope("dsa_predict"):
            new["ktb"] = shard(new["ktb"].at[rows, jb].add(
                k_t[:, 0].astype(new["ktb"].dtype), mode="drop"),
                "batch", "blocks", "pred_k")
        with jax.named_scope("dsa_select"):
            s_blk = jnp.einsum("bok,bjk->bj", q_t.astype(jnp.float32),
                               new["ktb"].astype(jnp.float32)) / bkd
    nb_keep = min(n_kb, -(-keep // bkd) + -(-DECODE_LOCAL // bkd) + 1)
    with jax.named_scope("dsa_select"):
        idx, ok = M.decode_block_topk_indices(s_blk, nb_keep, kv_len=kv_len,
                                              block_k=bkd,
                                              local=DECODE_LOCAL)
    if flags.sel_probe:
        new["sel_idx"], new["sel_ok"], new["sel_kv"] = idx, ok, kv_len
    with jax.named_scope("attend"):
        if flags.dsa_mode == "kernel":
            from repro.kernels.ops import dsa_decode as dsa_decode_kernel
            return dsa_decode_kernel(q, kc, vc, idx, ok, kv_len, block_k=bkd,
                                     k_scale=k_scale, v_scale=v_scale)
        return A.dsa_decode_block_attention(q, kc, vc, idx, ok, block_k=bkd,
                                            kv_len=kv_len, k_scale=k_scale,
                                            v_scale=v_scale)


# ---------------------------------------------------------------------------
# paged decode (block-table indirection over a shared physical page pool)
# ---------------------------------------------------------------------------


def _paged_view_rows(tbl, bk: int):
    """(B, S) pool-row index of every logical cache row of every slot.

    Gathering a pool with this matrix materializes the dense logical view:
    byte-identical to the dense resident cache (unmapped blocks point at
    the zero page), which is what makes every O(S) read path bitwise."""
    b, n_kb = tbl.shape
    return (tbl[:, :, None] * bk
            + jnp.arange(bk)[None, None, :]).reshape(b, n_kb * bk)


def _apply_paged_decode(params, cfg: ArchConfig, flags: RunFlags, x, cache,
                        use_rope, active=None):
    """Single-token decode on a PAGED resident cache.

    The cache k/v/kt leaves are flat pools (pool_rows, ...) shared by all
    slots; ``page_tbl`` (B, n_kb) maps each slot's logical block to its
    physical page.  Writes translate the logical write slot to a flat pool
    row through the table; frozen slots — and any slot whose table entry is
    unmapped (page 0, the permanent zero page) — push the write out of
    bounds so mode="drop" discards it.  O(S) read paths gather the dense
    logical view (byte-identical to the dense cache), so their math is
    bitwise the dense path's; block/kernel DSA paths instead translate the
    SELECTED logical block indices to physical pages after top-k and gather
    only those pages.
    """
    b = x.shape[0]
    pos = _slot_pos(cache, b)                              # (B,)
    with jax.named_scope("qkv"):
        q, k, v = _proj_qkv(params, cfg, x)
        if use_rope:
            p = pos[:, None]
            q = rope(q, p, cfg.rope_theta)
            k = rope(k, p, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", "qkv")
    tbl = cache["page_tbl"]
    n_kb = tbl.shape[1]
    bk = cfg.dsa.block_k if "kt" in cache else PAGE_SIZE
    s = n_kb * bk                                          # logical length
    nrows = cache["k"].shape[0]                            # pool rows
    with jax.named_scope("kv_write"):
        wslot = pos if active is None else jnp.where(active, pos, s)
        rows = jnp.arange(b)
        pg = tbl[rows, jnp.clip(wslot // bk, 0, n_kb - 1)]
        okw = (wslot < s) & (pg > 0)
        flat = jnp.where(okw, pg * bk + wslot % bk, nrows)
        if "k_s" in cache:
            k1, ks = Q.quant_store(k[:, 0], axis=-1, dtype=flags.kv_quant)
            v1, vs = Q.quant_store(v[:, 0], axis=-1, dtype=flags.kv_quant)
        else:
            k1, v1 = k[:, 0].astype(cache["k"].dtype), v[:, 0].astype(
                cache["v"].dtype)
        kc = cache["k"].at[flat].set(k1, mode="drop")
        vc = cache["v"].at[flat].set(v1, mode="drop")
        kc = shard(kc, "pages", "kv_heads", "qkv")
        vc = shard(vc, "pages", "kv_heads", "qkv")
        new_pos = (pos + 1 if active is None
                   else pos + active.astype(jnp.int32))
        new = dict(cache, k=kc, v=vc, pos=new_pos)
        if "k_s" in cache:
            new["k_s"] = shard(cache["k_s"].at[flat].set(ks, mode="drop"),
                               "pages", "kv_heads")
            new["v_s"] = shard(cache["v_s"].at[flat].set(vs, mode="drop"),
                               "pages", "kv_heads")
    kv_len = jnp.minimum(pos + 1, s).astype(jnp.int32)
    if active is not None:
        kv_len = jnp.where(active, kv_len, 0)
    view = _paged_view_rows(tbl, bk)                       # (B, S)
    if "kt" in cache:
        out = _dsa_paged_decode(params, cfg, flags, x, q, kc, vc, new,
                                flat, okw, pg, kv_len, view, bk)
    else:
        with jax.named_scope("attend"):
            if "k_s" in new:
                kd = Q.dequant(kc[view], new["k_s"][view])
                vd = Q.dequant(vc[view], new["v_s"][view])
            else:
                kd, vd = kc[view], vc[view]
            out = A.decode_attention(q, kd, vd, kv_len=kv_len)
    with jax.named_scope("attend"):
        out = shard(out, "batch", None, "heads", "qkv")
        out = out.reshape(b, 1, -1) @ params["wo"]
    return out, new, {}


def _dsa_paged_decode(params, cfg: ArchConfig, flags: RunFlags, x, q, kc,
                      vc, new, flat, okw, pg, kv_len, view, bk):
    """DSA decode step on the paged pools — the paged twin of _dsa_decode.

    kt writes reuse the translated flat row; the ktb pool has ONE row per
    physical page (page size == block_k), so the scatter-add's target block
    IS the write's page.  Selection scores the logical ktb view
    ``ktb[tbl]`` (bitwise the dense ktb) and the selected LOGICAL block
    indices are translated to physical pages only for the gather.
    """
    dsa = cfg.dsa
    s = view.shape[1]
    with jax.named_scope("dsa_predict"):
        q_t, k_t = PRED.predict_qk(params["dsa"], x, None, dsa.quant_bits)
        if "kt_s" in new:
            ktq, kts = Q.quant_store(k_t[:, 0], axis=-1)
            ktc = new["kt"].at[flat].set(ktq, mode="drop")
            kts_c = new["kt_s"].at[flat].set(kts, mode="drop")
            new["kt"] = shard(ktc, "pages", "pred_k")
            new["kt_s"] = shard(kts_c, "pages")
        else:
            ktc = new["kt"].at[flat].set(k_t[:, 0].astype(new["kt"].dtype),
                                         mode="drop")
            new["kt"] = shard(ktc, "pages", "pred_k")
    k_scale = new.get("k_s")
    v_scale = new.get("v_s")

    def kv_view():
        if "k_s" in new:
            return (Q.dequant(kc[view], new["k_s"][view]),
                    Q.dequant(vc[view], new["v_s"][view]))
        return kc[view], vc[view]

    keep = M.keep_count(s, dsa.sparsity)
    if flags.dsa_mode == "off":
        with jax.named_scope("attend"):
            kd, vd = kv_view()
            return A.decode_attention(q, kd, vd, kv_len=kv_len)
    if flags.dsa_mode == "faithful":
        with jax.named_scope("dsa_select"):
            if "kt_s" in new:
                s_tilde = _int8_select_scores(q_t, ktc[view],
                                              kts_c[view])[:, 0]
            else:
                s_tilde = jnp.einsum("bok,bsk->bs", q_t.astype(jnp.float32),
                                     ktc[view].astype(jnp.float32))
        with jax.named_scope("attend"):
            kd, vd = kv_view()
            return A.dsa_decode_attention(q, kd, vd, s_tilde,
                                          keep=keep, kv_len=kv_len,
                                          local=DECODE_LOCAL)
    npages = new["ktb"].shape[0]
    tbl = new["page_tbl"]
    n_kb = tbl.shape[1]
    if "ktb_s" in new:
        # per-page int8 block sums: dequant the touched page's row, add,
        # requant, set (frozen rows gather the zero page and drop the set)
        with jax.named_scope("dsa_predict"):
            src = jnp.where(okw, pg, 0)
            old = Q.dequant(new["ktb"][src], new["ktb_s"][src])
            bq_, bs_ = Q.quant_store(old + k_t[:, 0], axis=-1)
            tgt = jnp.where(okw, pg, npages)
            ktb = new["ktb"].at[tgt].set(bq_, mode="drop")
            ktb_s = new["ktb_s"].at[tgt].set(bs_, mode="drop")
            new["ktb"] = shard(ktb, "pages", "pred_k")
            new["ktb_s"] = shard(ktb_s, "pages")
        with jax.named_scope("dsa_select"):
            s_blk = _int8_select_scores(q_t, ktb[tbl], ktb_s[tbl],
                                        block_k=bk)[:, 0]
    else:
        with jax.named_scope("dsa_predict"):
            ktb = new["ktb"].at[jnp.where(okw, pg, npages)].add(
                k_t[:, 0].astype(new["ktb"].dtype), mode="drop")
            new["ktb"] = shard(ktb, "pages", "pred_k")
        with jax.named_scope("dsa_select"):
            s_blk = jnp.einsum("bok,bjk->bj", q_t.astype(jnp.float32),
                               ktb[tbl].astype(jnp.float32)) / bk
    nb_keep = min(n_kb, -(-keep // bk) + -(-DECODE_LOCAL // bk) + 1)
    with jax.named_scope("dsa_select"):
        idx, ok = M.decode_block_topk_indices(s_blk, nb_keep, kv_len=kv_len,
                                              block_k=bk,
                                              local=DECODE_LOCAL)
        pidx = jnp.take_along_axis(tbl, idx, axis=1)      # physical pages
    if flags.sel_probe:
        # logical block indices (pre page translation): comparable across
        # steps even when the physical mapping changes
        new["sel_idx"], new["sel_ok"], new["sel_kv"] = idx, ok, kv_len
    with jax.named_scope("attend"):
        if flags.dsa_mode == "kernel":
            from repro.kernels.ops import dsa_decode_paged as dsa_paged_kernel
            return dsa_paged_kernel(q, kc, vc, idx, pidx, ok, kv_len,
                                    block_k=bk, k_scale=k_scale,
                                    v_scale=v_scale)
        return A.dsa_decode_paged_block_attention(q, kc, vc, idx, pidx, ok,
                                                  block_k=bk, kv_len=kv_len,
                                                  k_scale=k_scale,
                                                  v_scale=v_scale)


# ---------------------------------------------------------------------------
# chunk-append forward path (chunked prefill)
# ---------------------------------------------------------------------------


def _apply_chunk(params, cfg: ArchConfig, flags: RunFlags, x, cache,
                 use_rope, active, chunk_len, sel_len=None):
    """C-token chunk append: the decode step generalized from 1 token.

    x: (B, C, d) — each slot's next C prompt tokens, right-padded with pad
    embeddings; chunk_len: (B,) true token count per slot (rows past it are
    padding, their logits garbage).  Writes C KV rows at the per-slot
    ``pos`` (pad rows write ZEROS — exactly the state
    ``transformer.truncate_cache`` leaves), advances ``pos`` by chunk_len,
    extends the DSA score caches incrementally, and attends each chunk
    query to the cache prefix + the intra-chunk causal triangle.

    ``sel_len`` (static; default the cache length) is the
    selection/attention GEOMETRY: masks, softmax reduction shapes, and the
    DSA granularity choice + block top-k all see exactly sel_len keys, so
    running chunks with sel_len = the prompt bucket reproduces a
    whole-prompt bucketed prefill token-bitwise (the chunked-admission
    exactness contract, pinned in tests) — the physical cache may be
    longer (the DSA cache rounds up to a block_k multiple).  Frozen slots
    (``active`` False) drop writes and don't advance, like single-token
    decode.  Requires a non-wrapping cache (no SWA) and, when the DSA
    caches are present, C and pos multiples of block_q/block_k (the
    scheduler's pow2 block-floored chunk buckets guarantee this).
    """
    assert not cfg.swa_window, "chunk append needs a non-wrapping cache"
    b, c = x.shape[:2]
    sel = cache["k"].shape[1] if sel_len is None else sel_len
    pos = _slot_pos(cache, b)                              # (B,)
    offs = jnp.arange(c)
    p = pos[:, None] + offs[None, :]                       # (B, C) global
    with jax.named_scope("qkv"):
        q, k, v = _proj_qkv(params, cfg, x)
        if use_rope:
            q = rope(q, p, cfg.rope_theta)
            k = rope(k, p, cfg.rope_theta)
    s = cache["k"].shape[1]
    live = offs[None, :] < chunk_len[:, None]              # (B, C)
    if active is not None:
        live = live & active[:, None]
    # frozen slots push ALL their writes out of bounds; pad rows of live
    # slots write explicit zeros at their true position instead (rows past
    # the cache end drop OOB either way)
    wslot = p if active is None else jnp.where(active[:, None], p, s)
    rows = jnp.arange(b)[:, None]
    q = shard(q, "batch", None, "heads", "qkv")
    with jax.named_scope("kv_write"):
        if "k_s" in cache:
            # pad rows quantize to (0, scale 0.0): dequant reproduces the exact
            # zero rows truncate_cache leaves
            kq, ks = Q.quant_store(jnp.where(live[..., None, None], k, 0),
                                   axis=-1, dtype=flags.kv_quant)
            vq, vs = Q.quant_store(jnp.where(live[..., None, None], v, 0),
                                   axis=-1, dtype=flags.kv_quant)
            kc = cache["k"].at[rows, wslot].set(kq, mode="drop")
            vc = cache["v"].at[rows, wslot].set(vq, mode="drop")
        else:
            kc = cache["k"].at[rows, wslot].set(
                jnp.where(live[..., None, None], k, 0).astype(
                    cache["k"].dtype), mode="drop")
            vc = cache["v"].at[rows, wslot].set(
                jnp.where(live[..., None, None], v, 0).astype(
                    cache["v"].dtype), mode="drop")
        kc = shard(kc, "batch", "cache_seq", "kv_heads", "qkv")
        vc = shard(vc, "batch", "cache_seq", "kv_heads", "qkv")
        adv = chunk_len if active is None else jnp.where(active, chunk_len, 0)
        new = dict(cache, k=kc, v=vc, pos=pos + adv)
        if "k_s" in cache:
            new["k_s"] = shard(
                cache["k_s"].at[rows, wslot].set(ks, mode="drop"),
                "batch", "cache_seq", "kv_heads")
            new["v_s"] = shard(
                cache["v_s"].at[rows, wslot].set(vs, mode="drop"),
                "batch", "cache_seq", "kv_heads")
    kv_len = (pos + adv).astype(jnp.int32)

    def sel_kv():
        if "k_s" in new:
            return (Q.dequant(kc[:, :sel], new["k_s"][:, :sel]),
                    Q.dequant(vc[:, :sel], new["v_s"][:, :sel]))
        return kc[:, :sel], vc[:, :sel]

    if "kt" in cache:
        q_t, kt_sel, kt_sel_s = _chunk_fill_pred(params, cfg, x, new,
                                                 wslot, live, pos, active)
        if dsa_active(cfg, flags):
            out = _dsa_chunk_attend(
                cfg, flags, q, kc[:, :sel], vc[:, :sel], q_t,
                kt_sel[:, :sel], p, pos, kv_len,
                kt_sel_s=None if kt_sel_s is None else kt_sel_s[:, :sel],
                k_scale=new["k_s"][:, :sel] if "k_s" in new else None,
                v_scale=new["v_s"][:, :sel] if "v_s" in new else None)
        else:
            with jax.named_scope("attend"):
                out = A.chunk_attention(q, *sel_kv(), p)
    else:
        with jax.named_scope("attend"):
            out = A.chunk_attention(q, *sel_kv(), p)
    with jax.named_scope("attend"):
        out = shard(out, "batch", None, "heads", "qkv")
        out = out.reshape(b, c, -1) @ params["wo"]
    return out, new, {}


@jax.named_scope("dsa_predict")
def _chunk_fill_pred(params, cfg: ArchConfig, x, new, wslot, live, pos,
                     active):
    """Extend the predicted-key cache ``kt`` and its block-pooled twin
    ``ktb`` with a chunk — no truncate_cache rebuild.

    Pad rows write zero kt rows and contribute zeros to the block sums, so
    the persisted caches match a whole-prompt prefill + truncate exactly;
    ktb gets one scatter-ADD of the chunk's per-block partial sums (the
    chunk is block_k-aligned, so each touched block is summed with the
    same reduction shape the truncate rebuild uses).  Returns the chunk's
    predicted queries Q~ and ``kt_sel``, the kt cache with the chunk's
    rows UNMASKED — whole-prompt prefill scores real pad-row K~ during
    selection (causality hides them), so the chunk's selection view must
    too.
    """
    dsa = cfg.dsa
    b, c = x.shape[:2]
    rows = jnp.arange(b)[:, None]
    q_t, k_t = PRED.predict_qk(params["dsa"], x, None, dsa.quant_bits)
    ktv = jnp.where(live[..., None], k_t, 0)
    bkd = dsa.block_k
    assert c % bkd == 0, (c, bkd)
    n_kb = new["ktb"].shape[1]
    jb = (pos // bkd)[:, None] + jnp.arange(c // bkd)[None, :]
    if active is not None:
        jb = jnp.where(active[:, None], jb, n_kb)
    if "kt_s" in new:
        ktq, kts = Q.quant_store(k_t, axis=-1)
        ktv_q = jnp.where(live[..., None], ktq, 0)
        ktv_s = jnp.where(live, kts, 0.0)
        kt_sel = new["kt"].at[rows, wslot].set(ktq, mode="drop")
        kt_sel_s = new["kt_s"].at[rows, wslot].set(kts, mode="drop")
        new["kt"] = shard(new["kt"].at[rows, wslot].set(ktv_q, mode="drop"),
                          "batch", "cache_seq", "pred_k")
        new["kt_s"] = shard(
            new["kt_s"].at[rows, wslot].set(ktv_s, mode="drop"),
            "batch", "cache_seq")
        # the chunk is block-aligned and the cache never wraps, so every
        # touched block is freshly covered: the quantized partial sums can
        # scatter-SET where the float path scatter-adds into zeros
        part = Q.dequant(ktv_q, ktv_s).reshape(b, c // bkd, bkd, -1).sum(
            axis=2)
        pq, ps = Q.quant_store(part, axis=-1)
        new["ktb"] = shard(new["ktb"].at[rows, jb].set(pq, mode="drop"),
                           "batch", "blocks", "pred_k")
        new["ktb_s"] = shard(
            new["ktb_s"].at[rows, jb].set(ps, mode="drop"),
            "batch", "blocks")
        return q_t, kt_sel, kt_sel_s
    kt_sel = new["kt"].at[rows, wslot].set(
        k_t.astype(new["kt"].dtype), mode="drop")
    new["kt"] = shard(new["kt"].at[rows, wslot].set(
        ktv.astype(new["kt"].dtype), mode="drop"),
        "batch", "cache_seq", "pred_k")
    part = ktv.reshape(b, c // bkd, bkd, -1).sum(axis=2)
    new["ktb"] = shard(new["ktb"].at[rows, jb].add(
        part.astype(new["ktb"].dtype), mode="drop"),
        "batch", "blocks", "pred_k")
    return q_t, kt_sel, None


def _dsa_chunk_attend(cfg: ArchConfig, flags: RunFlags, q, kc, vc, q_t,
                      kt_sel, p, pos, kv_len, *, kt_sel_s=None,
                      k_scale=None, v_scale=None):
    """DSA pattern + sparse attention for a chunk — the chunk-resumable
    twin of ``_dsa_train_mask_and_aux`` + the prefill execution paths.

    Mirrors the whole-prompt granularity choice on the CACHE length (the
    prompt bucket): token-granularity when that geometry isn't
    block-divisible or in faithful mode, else block-pooled selection
    feeding the XLA gather twin or the fused Pallas chunk kernel.  Scores
    run against ``kt_sel`` (B, S, k) so selection sees exactly the key
    views whole-prompt prefill saw; ``p`` (B, C) are the chunk queries'
    global positions, ``pos`` (B,) the chunk start.  ``kt_sel_s`` /
    ``k_scale`` / ``v_scale`` carry the per-row scales of int8-stored
    selection / KV caches (None = full-precision storage).
    """
    dsa = cfg.dsa
    b, c = q.shape[:2]
    s = kc.shape[1]
    if flags.dsa_mode == "faithful" or s % dsa.block_q or s % dsa.block_k:
        # token granularity — the whole-prompt path for this geometry
        with jax.named_scope("dsa_select"):
            if kt_sel_s is not None:
                s_t = _int8_select_scores(q_t, kt_sel, kt_sel_s)
            else:
                s_t = jnp.einsum("bqk,bsk->bqs", q_t, kt_sel)
            valid = jnp.arange(s)[None, None, :] <= p[:, :, None]
            keep = M.keep_count(s, dsa.sparsity)
            mask = M.row_topk_mask(s_t, keep, valid)
        with jax.named_scope("attend"):
            if k_scale is not None:
                kc, vc = Q.dequant(kc, k_scale), Q.dequant(vc, v_scale)
            return A.chunk_attention(q, kc, vc, p, token_mask=mask)
    bq, bkd = dsa.block_q, dsa.block_k
    assert c % bq == 0, (c, bq)
    n_kb = s // bkd
    with jax.named_scope("dsa_select"):
        q_blk = q_t.reshape(b, c // bq, bq, -1).mean(axis=2)
        if kt_sel_s is not None:
            sc = _int8_select_scores(q_blk, kt_sel, kt_sel_s)  # (B,nQb,S)
        else:
            sc = jnp.einsum("bqk,bsk->bqs", q_blk, kt_sel)     # (B,nQb,S)
        bs = sc.reshape(b, c // bq, n_kb, bkd).max(axis=-1)
        nb_keep = min(n_kb, max(dsa.min_blocks + dsa.local_blocks,
                                M.keep_count(n_kb, dsa.sparsity)))
        idx, ok = M.chunk_block_topk_indices(
            bs, nb_keep, q_block_offset=pos // bq,
            local_blocks=dsa.local_blocks, sort=dsa.sort_indices)
    with jax.named_scope("attend"):
        if flags.dsa_mode == "kernel":
            from repro.kernels.ops import dsa_chunk_prefill as chunk_kernel
            return chunk_kernel(q, kc, vc, idx, ok, pos, kv_len,
                                block_q=bq, block_k=bkd, k_scale=k_scale,
                                v_scale=v_scale)
        return A.dsa_chunk_block_attention(q, kc, vc, idx, ok, block_q=bq,
                                           block_k=bkd, q_offset=pos,
                                           kv_len=kv_len, k_scale=k_scale,
                                           v_scale=v_scale)


# ---------------------------------------------------------------------------
# speculative-verify forward path (draft-and-verify decode)
# ---------------------------------------------------------------------------


def _apply_verify(params, cfg: ArchConfig, flags: RunFlags, x, cache,
                  use_rope, active, chunk_len):
    """Draft-verify chunk append: C tokens (the pending token + C-1 draft
    tokens) written at the per-slot ``pos`` like ``_apply_chunk``, but each
    row attends with the per-row DECODE numerics — row i reproduces the
    single-token ``_apply_decode`` step at cache depth ``pos + i`` bitwise.

    This is what lets one dispatch verify K drafts: row i's logits equal
    the logits sequential decode would produce after committing rows < i,
    so greedy/sampled acceptance on the host chain is exact.  Differences
    from ``_apply_chunk``: the full PHYSICAL cache is the reduction
    geometry (decode attends the whole buffer, masked by kv_len — there is
    no sel_len), DSA selection is per-row block top-k over the pooled
    score cache (``masks.verify_block_topk_indices``) rather than
    per-query-block chunk selection, and ``ktb`` is NOT extended here —
    every block the chunk touches lies inside each row's DECODE_LOCAL
    force-keep window (requires C <= DECODE_LOCAL, enforced by
    ``speculative.can_speculate``), so selection never reads the stale
    entries and ``transformer.commit_chunk`` rebuilds them deterministically
    after acceptance.  Rejected rows' K/V/kt writes are rolled back by
    ``commit_chunk`` (write-then-invalidate).
    """
    assert not cfg.swa_window, "speculative verify needs a non-wrapping cache"
    b, c = x.shape[:2]
    pos = _slot_pos(cache, b)                              # (B,)
    offs = jnp.arange(c)
    p = pos[:, None] + offs[None, :]                       # (B, C) global
    with jax.named_scope("qkv"):
        q, k, v = _proj_qkv(params, cfg, x)
        if use_rope:
            q = rope(q, p, cfg.rope_theta)
            k = rope(k, p, cfg.rope_theta)
    s = cache["k"].shape[1]
    wslot = p if active is None else jnp.where(active[:, None], p, s)
    rows = jnp.arange(b)[:, None]
    q = shard(q, "batch", None, "heads", "qkv")
    with jax.named_scope("kv_write"):
        if "k_s" in cache:
            k1, ks = Q.quant_store(k, axis=-1, dtype=flags.kv_quant)
            v1, vs = Q.quant_store(v, axis=-1, dtype=flags.kv_quant)
        else:
            k1, v1 = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        kc = cache["k"].at[rows, wslot].set(k1, mode="drop")
        vc = cache["v"].at[rows, wslot].set(v1, mode="drop")
        kc = shard(kc, "batch", "cache_seq", "kv_heads", "qkv")
        vc = shard(vc, "batch", "cache_seq", "kv_heads", "qkv")
        adv = chunk_len if active is None else jnp.where(active, chunk_len, 0)
        new = dict(cache, k=kc, v=vc, pos=pos + adv)
        if "k_s" in cache:
            new["k_s"] = shard(
                cache["k_s"].at[rows, wslot].set(ks, mode="drop"),
                "batch", "cache_seq", "kv_heads")
            new["v_s"] = shard(
                cache["v_s"].at[rows, wslot].set(vs, mode="drop"),
                "batch", "cache_seq", "kv_heads")
    kv_row = (p + 1).astype(jnp.int32)                     # (B, C) per row
    if active is not None:
        kv_row = jnp.where(active[:, None], kv_row, 0)
    if "kt" in cache:
        with jax.named_scope("dsa_predict"):
            q_t, k_t = PRED.predict_qk(params["dsa"], x, None,
                                       cfg.dsa.quant_bits)
            if "kt_s" in cache:
                ktq, kts = Q.quant_store(k_t, axis=-1)
                new["kt"] = shard(new["kt"].at[rows, wslot].set(
                    ktq, mode="drop"), "batch", "cache_seq", "pred_k")
                new["kt_s"] = shard(
                    new["kt_s"].at[rows, wslot].set(kts, mode="drop"),
                    "batch", "cache_seq")
            else:
                new["kt"] = shard(new["kt"].at[rows, wslot].set(
                    k_t.astype(new["kt"].dtype), mode="drop"),
                    "batch", "cache_seq", "pred_k")
        if dsa_active(cfg, flags):
            out = _dsa_verify_attend(cfg, flags, q, kc, vc, q_t, new["kt"],
                                     new["ktb"], p, kv_row,
                                     kt_s=new.get("kt_s"),
                                     ktb_s=new.get("ktb_s"),
                                     k_scale=new.get("k_s"),
                                     v_scale=new.get("v_s"))
        else:
            # dsa_mode "off" on a long-context cache: dense decode over the
            # full buffer (kt maintained, like _dsa_decode's off path)
            with jax.named_scope("attend"):
                out = A.chunk_attention(q, *_kv_views(new, kc, vc), p)
    else:
        with jax.named_scope("attend"):
            out = A.chunk_attention(q, *_kv_views(new, kc, vc), p)
    with jax.named_scope("attend"):
        out = shard(out, "batch", None, "heads", "qkv")
        out = out.reshape(b, c, -1) @ params["wo"]
    return out, new, {}


def _dsa_verify_attend(cfg: ArchConfig, flags: RunFlags, q, kc, vc, q_t,
                       kt_full, ktb, p, kv_row, *, kt_s=None, ktb_s=None,
                       k_scale=None, v_scale=None):
    """Per-row DSA decode selection + attention for a verify chunk — the
    row-exact twin of ``_dsa_decode``'s execution paths.

    q_t: (B, C, k) per-row predicted queries; kt_full/ktb: the kt cache
    with ALL chunk rows written / the PRE-chunk pooled cache (stale only
    in force-kept blocks — see _apply_verify); p: (B, C) global positions;
    kv_row: (B, C) per-row kv_len.  Scores, top-k, gather and softmax all
    run per row with exactly the decode step's shapes and reduction order.
    kt_s/ktb_s: int8-selection scales; k_scale/v_scale: kv_quant scales.
    """
    dsa = cfg.dsa
    b, c = q.shape[:2]
    s = kc.shape[1]
    keep = M.keep_count(s, dsa.sparsity)
    if flags.dsa_mode == "faithful":
        with jax.named_scope("dsa_select"):
            if kt_s is not None:
                s_tilde = _int8_select_scores(q_t, kt_full, kt_s)
            else:
                s_tilde = jnp.einsum("bck,bsk->bcs",
                                     q_t.astype(jnp.float32),
                                     kt_full.astype(jnp.float32))
        with jax.named_scope("attend"):
            if k_scale is not None:
                kc = Q.dequant(kc, k_scale)
                vc = Q.dequant(vc, v_scale)
            return A.dsa_verify_attention(q, kc, vc, s_tilde, keep=keep,
                                          kv_len=kv_row, local=DECODE_LOCAL)
    bkd = dsa.block_k
    n_kb = ktb.shape[1]
    with jax.named_scope("dsa_select"):
        if ktb_s is not None:
            s_blk = _int8_select_scores(q_t, ktb, ktb_s, block_k=bkd)
        else:
            s_blk = jnp.einsum("bck,bjk->bcj", q_t.astype(jnp.float32),
                               ktb.astype(jnp.float32)) / bkd
        nb_keep = min(n_kb, -(-keep // bkd) + -(-DECODE_LOCAL // bkd) + 1)
        idx, ok = M.verify_block_topk_indices(s_blk, nb_keep, kv_len=kv_row,
                                              block_k=bkd,
                                              local=DECODE_LOCAL)
    return _verify_attend(flags, q, kc, vc, idx, ok, kv_row, bkd, c,
                          k_scale, v_scale)


@jax.named_scope("attend")
def _verify_attend(flags: RunFlags, q, kc, vc, idx, ok, kv_row, bkd: int,
                   c: int, k_scale, v_scale):
    """Per-row gather attention over the selected blocks of a verify
    chunk: the fused kernel once per row, or the XLA twin."""
    if flags.dsa_mode == "kernel":
        from repro.kernels.ops import dsa_decode as dsa_decode_kernel
        # one fused-kernel call per row INSIDE the single verify dispatch:
        # each call is shape-identical to the sequential decode step's, so
        # kernel-mode verification is bitwise by construction (C is small
        # and static — the unroll is part of the (slots, K) compile)
        outs = [dsa_decode_kernel(q[:, i:i + 1], kc, vc, idx[:, i],
                                  ok[:, i], kv_row[:, i], block_k=bkd,
                                  k_scale=k_scale, v_scale=v_scale)
                for i in range(c)]
        return jnp.concatenate(outs, axis=1)
    return A.dsa_verify_block_attention(q, kc, vc, idx, ok, block_k=bkd,
                                        kv_len=kv_row, k_scale=k_scale,
                                        v_scale=v_scale)


def init_mla(key, cfg: ArchConfig, dtype=jnp.float32):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_h = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    params = {
        "q_a": dense_init(ks[0], (d, m.q_lora_rank), dtype=dtype),
        "q_a_norm": jnp.ones((m.q_lora_rank,), dtype),
        "q_b": dense_init(ks[1], (m.q_lora_rank, h * qk_h), dtype=dtype),
        "kv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                           dtype=dtype),
        "kv_a_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "kv_b": dense_init(ks[3], (m.kv_lora_rank,
                                   h * (m.qk_nope_head_dim + m.v_head_dim)),
                           dtype=dtype),
        "wo": dense_init(ks[4], (h * m.v_head_dim, d), dtype=dtype),
    }
    specs = {
        "q_a": ("embed", "lora"), "q_a_norm": ("lora",),
        "q_b": ("lora", "heads"),
        "kv_a": ("embed", "lora"), "kv_a_norm": ("lora",),
        "kv_b": ("lora", "heads"), "wo": ("heads", "embed"),
    }
    if cfg.dsa.enabled:
        params["dsa"] = PRED.init_predictor(ks[5], d, cfg.dsa.sigma, dtype)
        specs["dsa"] = PRED.predictor_specs()
    return params, specs


def _mla_qkv(params, cfg: ArchConfig, x, pos):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk_h = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = rms_norm(x @ params["q_a"], params["q_a_norm"]) @ params["q_b"]
    q = q.reshape(b, s, h, qk_h)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = rope(q_rope, pos, cfg.rope_theta)
    kv = x @ params["kv_a"]
    c_kv = rms_norm(kv[..., :m.kv_lora_rank], params["kv_a_norm"])
    k_rope = rope(kv[..., None, m.kv_lora_rank:], pos, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def apply_mla(params, cfg: ArchConfig, flags: RunFlags, x, *, cache=None,
              pos_offset=0, active=None, chunk_len=None, sel_len=None):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    if flags.mode == "decode":
        if chunk_len is not None:
            if flags.spec_verify:
                return _apply_mla_verify(params, cfg, flags, x, cache,
                                         active, chunk_len)
            return _apply_mla_chunk(params, cfg, flags, x, cache, active,
                                    chunk_len, sel_len)
        return _apply_mla_decode(params, cfg, flags, x, cache, active)
    pos = jnp.arange(s) + pos_offset
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, pos)
    kvb = (c_kv @ params["kv_b"]).reshape(
        b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (*k_nope.shape[:3],
                                                   m.qk_rope_head_dim))], -1)
    q = shard(q, "batch", "seq", "heads", "qkv")
    k = shard(k, "batch", "seq", "heads", "qkv")
    aux: Dict[str, jax.Array] = {}
    if dsa_active(cfg, flags):
        (kind, pat), aux = _dsa_train_mask_and_aux(
            params, cfg, flags, x, q, k, True)
        if kind == "token":
            out = A.dense_attention(q, k, v, causal=True, token_mask=pat)
        else:
            idx, ok = pat
            out = A.dsa_sparse_attention(q, k, v, idx, ok,
                                         block_q=cfg.dsa.block_q,
                                         block_k=cfg.dsa.block_k, causal=True)
    elif s <= 1024:
        out = A.dense_attention(q, k, v, causal=True)
    else:
        out = A.flash_attention(q, k, v, causal=True)
    new_cache = cache
    if flags.mode == "prefill" and cache is not None:
        new_cache = dict(cache)
        new_cache["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1)
        new_cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope[:, :, 0].astype(cache["k_rope"].dtype),
            0, axis=1)
        new_cache["pos"] = jnp.full((b,), s, jnp.int32)
    out = out.reshape(b, s, -1) @ params["wo"]
    return out, new_cache, aux


def init_cache_mla(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs_mla(cache) -> Dict:
    return {"c_kv": ("batch", "cache_seq", "lora"),
            "k_rope": ("batch", "cache_seq", None), "pos": ("batch",)}


def _apply_mla_chunk(params, cfg: ArchConfig, flags: RunFlags, x, cache,
                     active, chunk_len, sel_len=None):
    """Chunk-append MLA: write C latent rows at the per-slot ``pos`` (pad
    rows zeroed, matching truncate_cache), then attend the chunk queries
    NON-absorbed — the cached latents are re-expanded through ``kv_b``
    exactly like whole-prompt prefill, so chunked MLA prefill reproduces
    it bitwise on real rows.  DSA-over-MLA has no predicted-key cache to
    resume from, so chunked admission is gated to dsa_mode="off" for MLA
    (inference.engine.can_chunk_prefill)."""
    m = cfg.mla
    b, c, _ = x.shape
    h = cfg.n_heads
    pos = _slot_pos(cache, b)                              # (B,)
    offs = jnp.arange(c)
    p = pos[:, None] + offs[None, :]
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, cfg, x, p)
    s_cache = cache["c_kv"].shape[1]
    live = offs[None, :] < chunk_len[:, None]
    if active is not None:
        live = live & active[:, None]
    wslot = p if active is None else jnp.where(active[:, None], p, s_cache)
    rows = jnp.arange(b)[:, None]
    ckc = cache["c_kv"].at[rows, wslot].set(
        jnp.where(live[..., None], c_kv_new, 0).astype(cache["c_kv"].dtype),
        mode="drop")
    krc = cache["k_rope"].at[rows, wslot].set(
        jnp.where(live[..., None], k_rope_new[:, :, 0],
                  0).astype(cache["k_rope"].dtype), mode="drop")
    ckc = shard(ckc, "batch", "cache_seq", "lora")
    krc = shard(krc, "batch", "cache_seq", None)
    adv = chunk_len if active is None else jnp.where(active, chunk_len, 0)
    new = dict(cache, c_kv=ckc, k_rope=krc, pos=pos + adv)
    sel = s_cache if sel_len is None else sel_len
    kvb = (ckc[:, :sel].astype(x.dtype) @ params["kv_b"]).reshape(
        b, sel, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        krc[:, :sel].astype(x.dtype)[:, :, None],
        (b, sel, h, m.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = A.chunk_attention(q, k, v, p)
    out = out.reshape(b, c, -1) @ params["wo"]
    return out, new, {}


def _apply_mla_verify(params, cfg: ArchConfig, flags: RunFlags, x, cache,
                      active, chunk_len):
    """Draft-verify chunk append for MLA — the ABSORBED-decode twin of
    ``_apply_mla_chunk``.  Writes C latent rows at the per-slot ``pos``
    like the chunk path, but scores each row in the latent space exactly
    as ``_apply_mla_decode`` does (q_nope absorbed through W_uk, values
    combined in the latent space and expanded through W_uv), with the
    per-row ragged kv_len — row i is bitwise the absorbed decode step at
    depth ``pos + i``, which ``_apply_mla_chunk``'s non-absorbed expansion
    is NOT (different contraction order).  DSA-over-MLA is outside the
    speculation envelope (no predicted-key cache), mirroring
    ``can_chunk_prefill``."""
    m = cfg.mla
    b, c, _ = x.shape
    h = cfg.n_heads
    pos = _slot_pos(cache, b)                              # (B,)
    offs = jnp.arange(c)
    p = pos[:, None] + offs[None, :]
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, cfg, x, p)
    s_cache = cache["c_kv"].shape[1]
    wslot = p if active is None else jnp.where(active[:, None], p, s_cache)
    rows = jnp.arange(b)[:, None]
    ckc = cache["c_kv"].at[rows, wslot].set(
        c_kv_new.astype(cache["c_kv"].dtype), mode="drop")
    krc = cache["k_rope"].at[rows, wslot].set(
        k_rope_new[:, :, 0].astype(cache["k_rope"].dtype), mode="drop")
    ckc = shard(ckc, "batch", "cache_seq", "lora")
    krc = shard(krc, "batch", "cache_seq", None)
    adv = chunk_len if active is None else jnp.where(active, chunk_len, 0)
    new = dict(cache, c_kv=ckc, k_rope=krc, pos=pos + adv)
    kvb = params["kv_b"].reshape(m.kv_lora_rank, h,
                                 m.qk_nope_head_dim + m.v_head_dim)
    w_uk, w_uv = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    q_eff = jnp.einsum("bchn,rhn->bchr", q_nope, w_uk)     # (B,C,h,r)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s_lat = jnp.einsum("bchr,bsr->bchs", q_eff, ckc.astype(q_eff.dtype))
    s_rope = jnp.einsum("bchn,bsn->bchs", q_rope, krc.astype(q_rope.dtype))
    s_all = (s_lat + s_rope) * scale
    kv_row = (p + 1).astype(jnp.int32)
    if active is not None:
        kv_row = jnp.where(active[:, None], kv_row, 0)
    kj = jnp.arange(ckc.shape[1])[None, None, None, :]
    s_all = jnp.where(kj < kv_row[:, :, None, None], s_all, A.NEG)
    pattn = jax.nn.softmax(s_all.astype(jnp.float32), axis=-1)
    o_lat = jnp.einsum("bchs,bsr->bchr", pattn.astype(ckc.dtype), ckc)
    out = jnp.einsum("bchr,rhv->bchv", o_lat, w_uv.astype(o_lat.dtype))
    out = out.reshape(b, c, -1) @ params["wo"]
    return out, new, {}


def _apply_mla_decode(params, cfg: ArchConfig, flags: RunFlags, x, cache,
                      active=None):
    """Absorbed MLA decode: scores and values live in the latent space,
    cache stores only (c_kv, k_rope) — 576 floats/token for DSv3.
    Per-slot ``pos`` and the ``active`` mask follow _apply_decode."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    pos = _slot_pos(cache, b)                              # (B,)
    p = pos[:, None]
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, cfg, x, p)
    s_cache = cache["c_kv"].shape[1]
    wslot = pos if active is None else jnp.where(active, pos, s_cache)
    rows = jnp.arange(b)
    ckc = cache["c_kv"].at[rows, wslot].set(
        c_kv_new[:, 0].astype(cache["c_kv"].dtype), mode="drop")
    krc = cache["k_rope"].at[rows, wslot].set(
        k_rope_new[:, 0, 0].astype(cache["k_rope"].dtype), mode="drop")
    ckc = shard(ckc, "batch", "cache_seq", "lora")
    krc = shard(krc, "batch", "cache_seq", None)
    new_pos = pos + 1 if active is None else pos + active.astype(jnp.int32)
    new = dict(cache, c_kv=ckc, k_rope=krc, pos=new_pos)
    # absorb kv_b: W_uk (r, h, nope), W_uv (r, h, v)
    kvb = params["kv_b"].reshape(m.kv_lora_rank, h,
                                 m.qk_nope_head_dim + m.v_head_dim)
    w_uk, w_uv = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    q_eff = jnp.einsum("bohn,rhn->bohr", q_nope, w_uk)        # (B,1,h,r)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s_lat = jnp.einsum("bohr,bsr->bhs", q_eff, ckc.astype(q_eff.dtype))
    s_rope = jnp.einsum("bohn,bsn->bhs", q_rope, krc.astype(q_rope.dtype))
    s_all = (s_lat + s_rope) * scale
    kv_len = pos + 1 if active is None else jnp.where(active, pos + 1, 0)
    kj = jnp.arange(ckc.shape[1])[None, None, :]
    s_all = jnp.where(kj < kv_len[:, None, None], s_all, A.NEG)
    pattn = jax.nn.softmax(s_all.astype(jnp.float32), axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pattn.astype(ckc.dtype), ckc)
    out = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(o_lat.dtype))
    out = out.reshape(b, 1, -1) @ params["wo"]
    return out, new, {}
