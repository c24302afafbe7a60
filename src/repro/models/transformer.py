"""Top-level model: embedding -> scanned layer groups -> head.

Public API:
  init_model(key, cfg)                  -> (params, logical_specs)
  forward(params, cfg, flags, batch)    -> (logits, aux)        train/prefill
  decode_step(params, cfg, flags, tok, cache) -> (logits, cache)
  init_cache(cfg, batch, max_len, flags)-> cache (+ cache_logical_specs)

Decode fast path: ``decode_step`` is a pure (tokens, caches) -> (logits,
caches) function of statically-shaped pytrees, which is what lets the
serving engine fuse whole generations into one ``jax.lax.scan`` over it
(repro.inference.engine) — cache update, DSA prediction/selection, attention
and sampling all stay on device.  With RunFlags(long_context=True) the
attention caches also carry the predicted-key cache and its block-pooled
score cache (repro.models.attention module docstring).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import quantization as Q
from repro.distributed.sharding import map_specs, shard
from repro.models import blocks as B
from repro.models.attention import RunFlags
from repro.models.common import dense_init, rms_norm, sinusoidal_embedding

AUX_KEYS = ("mse", "router")


def _norm_aux(aux: Dict) -> Dict[str, jax.Array]:
    return {k: jnp.asarray(aux.get(k, 0.0), jnp.float32) for k in AUX_KEYS}


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def init_model(key, cfg: ArchConfig):
    dt = _dtype(cfg)
    ks = jax.random.split(key, 8)
    ng = B.n_groups(cfg)
    gkeys = jax.random.split(ks[0], ng)
    gp = jax.vmap(lambda k: B.init_group(k, cfg, dtype=dt)[0])(gkeys)
    _, gspec = B.init_group(ks[0], cfg, dtype=dt)
    params: Dict[str, Any] = {
        "embed": dense_init(ks[1], (cfg.vocab, cfg.d_model), dtype=dt),
        "groups": gp,
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    specs: Dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "groups": map_specs(lambda s: ("layers",) + tuple(s), gspec),
        "final_norm": ("embed_act",),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab),
                                       dtype=dt)
        specs["lm_head"] = ("embed", "vocab")
    if cfg.moe is not None and cfg.moe.first_k_dense:
        pro, pro_s = [], []
        dense_cfg = cfg
        for i in range(cfg.moe.first_k_dense):
            d = B.SubBlockDef("mla" if cfg.mla is not None else "attn",
                              moe=False)
            p, s = B.init_subblock(jax.random.fold_in(ks[3], i), dense_cfg,
                                   d, dt)
            pro.append(p)
            pro_s.append(s)
        params["prologue"] = pro
        specs["prologue"] = pro_s
    if cfg.enc_dec:
        ekeys = jax.random.split(ks[4], cfg.n_enc_layers)
        params["enc_groups"] = jax.vmap(
            lambda k: B.init_group(k, cfg, decoder=False, dtype=dt)[0])(ekeys)
        _, egspec = B.init_group(ks[4], cfg, decoder=False, dtype=dt)
        specs["enc_groups"] = map_specs(lambda s: ("layers",) + tuple(s),
                                        egspec)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dt)
        specs["enc_norm"] = ("embed_act",)
    return params, specs


def model_param_specs(cfg: ArchConfig):
    """Logical-axis spec tree parallel to ``init_model(key, cfg)[0]``,
    WITHOUT allocating parameters (abstract ``eval_shape`` trace; the spec
    tuples are plain Python built during tracing and captured through a
    side channel).  The serving engines resolve it against a tensor-
    parallel mesh to land host weights sharded over "model"
    (inference.engine) — they receive only the params tree from callers,
    so the spec tree has to be reconstructible from cfg alone."""
    holder = {}

    def capture(key):
        params, specs = init_model(key, cfg)
        holder["specs"] = specs
        return 0

    jax.eval_shape(capture, jax.random.PRNGKey(0))
    return holder["specs"]


# ---------------------------------------------------------------------------


def _scan_groups(gparams, cfg: ArchConfig, flags: RunFlags, defs, x,
                 caches=None, enc=None, pos_offset=0, decoder=True,
                 active=None, chunk_len=None, sel_len=None):
    """lax.scan over stacked groups; python loop fallback for tiny models."""
    def body(carry, xs):
        xc, aux_c = carry
        p = xs if caches is None else xs[0]
        c = None if caches is None else xs[1]
        xc, newc, aux = B.apply_group(p, cfg, flags, defs, xc, cache=c,
                                      enc=enc, pos_offset=pos_offset,
                                      active=active, chunk_len=chunk_len,
                                      sel_len=sel_len)
        aux = _norm_aux(aux)
        carry = (xc, {k: aux_c[k] + aux[k] for k in AUX_KEYS})
        return carry, (newc if caches is not None else 0)

    if cfg.remat and cfg.remat_policy != "none":
        pol = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
               if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, policy=pol)
    aux0 = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    xs = gparams if caches is None else (gparams, caches)
    if cfg.use_scan:
        (x, aux), ys = jax.lax.scan(body, (x, aux0), xs)
    else:
        n = len(jax.tree.leaves(gparams)) and jax.tree.leaves(gparams)[0].shape[0]
        ys_list = []
        carry = (x, aux0)
        for i in range(n):
            sl = jax.tree.map(lambda a: a[i], xs)
            carry, y = body(carry, sl)
            ys_list.append(y)
        x, aux = carry
        ys = (jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
              if caches is not None else None)
    return x, aux, (ys if caches is not None else None)


def _encode(params, cfg: ArchConfig, flags: RunFlags, enc_x):
    """Whisper encoder over precomputed frame embeddings (frontend stub)."""
    pos = sinusoidal_embedding(enc_x.shape[1], cfg.d_model, enc_x.dtype)
    x = enc_x + pos[None]
    defs = B.group_defs(cfg, decoder=False)
    eflags = RunFlags(mode="train", dsa_mode=flags.dsa_mode,
                      with_mse=flags.with_mse)
    x, aux, _ = _scan_groups(params["enc_groups"], cfg, eflags, defs, x,
                             decoder=False)
    return rms_norm(x, params["enc_norm"].astype(x.dtype), cfg.norm_eps), aux


def unstack_group_caches(caches):
    """Decode fast path: turn the stacked (n_groups, ...) group cache into a
    per-layer list so each group's buffers are separate carry leaves of the
    generation loop — the single-token dynamic_update_slice then updates
    each layer's cache IN PLACE inside ``lax.scan`` instead of restacking
    (copying) the whole KV cache every decode step.  One-time copy; forward
    dispatches on the list structure."""
    gc = caches["groups"]
    ng = jax.tree.leaves(gc)[0].shape[0]
    groups = [jax.tree.map(lambda a, i=i: a[i], gc) for i in range(ng)]
    return dict(caches, groups=groups)


# Cache leaves holding one row per cached token, keyed by their dict name;
# value = seq-axis index counted from the END of the leaf's shape, so the
# same rule covers stacked (n_groups, B, S, ...) and unstacked (B, S, ...)
# layouts.  ktb (and its scale ktb_s) is excluded: rebuilt from the
# masked kt.  *_s leaves are the per-row quantization scales of int8/fp8
# caches (one fewer trailing axis than their data leaf).
_SEQ_AXIS_FROM_END = {"k": 3, "v": 3, "kt": 2, "c_kv": 2, "k_rope": 2,
                      "k_s": 2, "v_s": 2, "kt_s": 1}


def _mask_rows(a, length, axis_from_end: int):
    ax = a.ndim - axis_from_end
    s = a.shape[ax]
    shape = [1] * a.ndim
    shape[ax] = s
    if length.ndim == 0:
        m = jnp.arange(s) < length
    else:                      # per-row lengths: batch axis precedes seq
        m = jnp.arange(s)[None, :] < length[:, None]
        shape[ax - 1] = a.shape[ax - 1]
    return a * m.reshape(shape).astype(a.dtype)


def truncate_cache(cfg: ArchConfig, caches, length):
    """Sanitize a freshly prefilled cache to its true prompt length(s).

    Bucketed prefill right-pads the prompt, so cache rows at positions
    >= length hold pad-token K/V/kt junk.  Dense decode masks them through
    kv_len, but the DSA block-score cache ``ktb`` is a running SUM per
    block — pad rows inside a partial block would poison block selection
    and the in-scan `add` update assumes the next slot is zero.  This
    zeroes all per-token rows at positions >= length, rebuilds ktb from the
    masked kt, and resets every per-slot ``pos`` to ``length``.  Recurrent
    (ssm) and encoder cross-attention leaves are left untouched (prompt
    bucketing is disabled for those architectures).  ``length`` may be
    traced, a scalar or per-row (B,) true lengths (batched admission
    prefill); works on stacked or unstacked group caches.
    """
    length = jnp.asarray(length, jnp.int32)

    def walk(node):
        if isinstance(node, dict):
            if "page_tbl" in node:
                raise ValueError(
                    "truncate_cache does not support paged caches — "
                    "prefill runs on dense staging caches and the paged "
                    "insert maps rows through the page table")
            out = {}
            for name, v in node.items():
                if name == "pos":
                    out[name] = jnp.broadcast_to(length, v.shape).astype(
                        v.dtype)
                elif name in ("ktb", "ktb_s"):
                    continue                    # rebuilt below from kt
                elif name in _SEQ_AXIS_FROM_END:
                    out[name] = _mask_rows(v, length,
                                           _SEQ_AXIS_FROM_END[name])
                else:
                    out[name] = walk(v)
            if "ktb" in node:
                kt = out["kt"]
                if "kt_s" in out:
                    # int8 selection cache: block sums accumulate the
                    # DEQUANTIZED kt rows (same source as the live updates)
                    kt = Q.dequant(out["kt"], out["kt_s"])
                bkd = cfg.dsa.block_k
                n_kb = node["ktb"].shape[-2]
                pad = n_kb * bkd - kt.shape[-2]
                if pad:
                    kt = jnp.pad(kt, [(0, 0)] * (kt.ndim - 2)
                                 + [(0, pad), (0, 0)])
                sums = kt.reshape(*kt.shape[:-2], n_kb, bkd,
                                  kt.shape[-1]).sum(axis=-2)
                if "ktb_s" in node:
                    out["ktb"], out["ktb_s"] = Q.quant_store(sums, axis=-1)
                else:
                    out["ktb"] = sums.astype(node["ktb"].dtype)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(caches)


def _loop_groups_unstacked(gparams, cfg: ArchConfig, flags: RunFlags, defs,
                           x, caches, enc=None, active=None, chunk_len=None,
                           sel_len=None):
    """Python-unrolled twin of _scan_groups over a per-layer cache list
    (decode fast path).  Per-layer param slices are loop-invariant, so XLA
    hoists them out of any enclosing generation scan."""
    aux = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    new_caches = []
    for i, c in enumerate(caches):
        with jax.named_scope("weights"):
            p = jax.tree.map(lambda a, i=i: a[i], gparams)
        x, nc, a = B.apply_group(p, cfg, flags, defs, x, cache=c, enc=enc,
                                 active=active, chunk_len=chunk_len,
                                 sel_len=sel_len)
        a = _norm_aux(a)
        aux = {k: aux[k] + a[k] for k in AUX_KEYS}
        new_caches.append(nc)
    return x, aux, new_caches


def forward(params, cfg: ArchConfig, flags: RunFlags,
            batch: Dict[str, jax.Array], caches=None, active=None,
            chunk_len=None, sel_len=None):
    """batch: {"tokens": (B,S) int32, ["enc_x"|"img"]: (B,T,d)}.
    Returns (logits, aux, new_caches).

    active: optional (B,) bool decode slot mask — continuous batching
    freezes inactive slots' caches (see models.attention docstring).
    chunk_len: optional (B,) — chunk-append decode mode (chunked prefill;
    see chunk_step)."""
    tokens = batch["tokens"]
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    x = shard(x, "batch", "seq_sp", "embed_act")
    enc = None
    aux_enc = None
    if cfg.enc_dec and "enc_x" in batch:
        enc, aux_enc = _encode(params, cfg, flags, batch["enc_x"].astype(dt))
    elif cfg.cross_attn_period and "img" in batch:
        enc = batch["img"].astype(dt)
    if cfg.enc_dec:
        x = x + sinusoidal_embedding(x.shape[1], cfg.d_model, dt)[None]
    new_pro_caches = None
    aux_pro = {}
    if "prologue" in params:
        d = B.SubBlockDef("mla" if cfg.mla is not None else "attn", moe=False)
        new_pro_caches = [] if caches is not None else None
        for i, p in enumerate(params["prologue"]):
            c = None if caches is None else caches["prologue"][i]
            x, nc, a = B.apply_subblock(p, cfg, flags, d, x, cache=c, enc=enc,
                                        active=active, chunk_len=chunk_len,
                                        sel_len=sel_len)
            for k, v in a.items():
                aux_pro[k] = aux_pro.get(k, 0.0) + v
            if new_pro_caches is not None:
                new_pro_caches.append(nc)
    defs = B.group_defs(cfg)
    gc = None if caches is None else caches["groups"]
    if isinstance(gc, (list, tuple)):       # decode fast path (unstacked)
        x, aux, new_gc = _loop_groups_unstacked(params["groups"], cfg, flags,
                                                defs, x, gc, enc=enc,
                                                active=active,
                                                chunk_len=chunk_len,
                                                sel_len=sel_len)
    else:
        x, aux, new_gc = _scan_groups(params["groups"], cfg, flags, defs, x,
                                      caches=gc, enc=enc, active=active,
                                      chunk_len=chunk_len, sel_len=sel_len)
    for extra in (aux_pro, aux_enc or {}):
        for k in AUX_KEYS:
            if k in extra:
                aux[k] = aux[k] + extra[k]
    with jax.named_scope("logits_sample"):
        x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(x.dtype)
        logits = x @ head
        # "vocab_act", not "vocab": training shards logits over "model", but
        # the TP serving rules replicate them here (all-gather of columns
        # each computed whole) so sampling sees a replicated operand, as
        # unsharded
        logits = shard(logits, "batch", None, "vocab_act")
    new_caches = None
    if caches is not None:
        new_caches = dict(caches, groups=new_gc)
        if new_pro_caches is not None:
            new_caches["prologue"] = new_pro_caches
    return logits, aux, new_caches


def decode_step(params, cfg: ArchConfig, flags: RunFlags, tokens, caches,
                enc: Optional[jax.Array] = None,
                active: Optional[jax.Array] = None):
    """tokens: (B, 1).  Returns (logits (B,1,V), new_caches).

    active: optional (B,) bool — continuous-batching slot mask; inactive
    slots freeze their per-slot cache ``pos``, drop cache writes, and
    attend with kv_len=0 (their logits are garbage and must be ignored)."""
    assert flags.mode == "decode"
    logits, _, new_caches = forward(params, cfg, flags,
                                    {"tokens": tokens}, caches=caches,
                                    active=active)
    return logits, new_caches


def chunk_step(params, cfg: ArchConfig, flags: RunFlags, tokens, caches,
               chunk_len, active: Optional[jax.Array] = None,
               sel_len: Optional[int] = None):
    """``decode_step`` generalized from 1 token to a C-token chunk (chunked
    prefill).  tokens: (B, C) — each slot's next C prompt tokens appended
    at its cache ``pos``, right-padded with pad ids; chunk_len: (B,) true
    token count per row.  Returns (logits (B,C,V), new_caches).

    Every layer writes its C cache rows at the per-slot ``pos`` (pad rows
    as zeros — the truncate_cache state), advances ``pos`` by chunk_len,
    extends the DSA block-score cache ``ktb`` by scatter-add, and attends
    chunk queries to the cache prefix plus the intra-chunk causal
    triangle.  The CACHE LENGTH is the attention/selection geometry:
    running a prompt through chunk_steps over a prompt-bucket-sized cache
    leaves bitwise the cache (and final-row logits) of a whole-prompt
    bucketed prefill — the chunked-admission exactness contract.  Logits
    rows at or past chunk_len are garbage; inactive slots freeze entirely.
    On the DSA block path C and the running ``pos`` must be multiples of
    block_q/block_k (pow2 chunk buckets guarantee this).  Not supported
    for recurrent (ssm/rwkv), SWA-ring, or enc-dec caches — the same set
    for which prompt bucketing auto-disables.
    """
    assert flags.mode == "decode"
    logits, _, new_caches = forward(params, cfg, flags,
                                    {"tokens": tokens}, caches=caches,
                                    active=active, chunk_len=chunk_len,
                                    sel_len=sel_len)
    return logits, new_caches


def verify_step(params, cfg: ArchConfig, flags: RunFlags, tokens, caches,
                active: Optional[jax.Array] = None):
    """Speculative draft-verify step: ``chunk_step`` routed through the
    per-row DECODE-exact verify attention (``flags.spec_verify`` must be
    set).  tokens: (B, C) — each slot's pending token followed by C-1
    draft tokens, appended at its cache ``pos``.  Returns (logits (B,C,V),
    new_caches): row i's logits are bitwise the logits a sequential
    ``decode_step`` chain would produce after committing rows < i, so the
    caller can run exact greedy/sampled acceptance and roll back rejected
    rows with ``commit_chunk``.  All C rows are written optimistically
    (K/V/kt; ``ktb`` is deferred to commit) and ``pos`` advances by C for
    active slots — a verify step MUST be followed by ``commit_chunk``.
    Caches must be unstacked (the decode fast path layout)."""
    assert flags.mode == "decode" and flags.spec_verify
    b, c = tokens.shape
    chunk_len = jnp.full((b,), c, jnp.int32)
    logits, _, new_caches = forward(params, cfg, flags, {"tokens": tokens},
                                    caches=caches, active=active,
                                    chunk_len=chunk_len)
    return logits, new_caches


# Cache leaves holding one row per cached token in the UNSTACKED decode
# layout (batch axis 0, token-row axis 1) — the set commit_chunk rolls back.
_COMMIT_ROW_KEYS = ("k", "v", "kt", "c_kv", "k_rope", "k_s", "v_s", "kt_s")


def commit_chunk(cfg: ArchConfig, caches, keep, c: int,
                 active: Optional[jax.Array] = None):
    """Commit the accepted prefix of a ``verify_step`` and roll back the
    rejected tail (write-then-invalidate).

    keep: (B,) accepted row count per slot (0 for frozen slots) — the
    verify wrote C rows at ``start = pos - C`` and advanced ``pos`` to
    ``start + C``; this zeroes every per-token cache row in
    ``[start + keep, start + C)`` (a C-bounded scatter, not an O(S) mask),
    sets ``pos = start + keep``, and rebuilds the DSA block-score cache
    ``ktb`` for the (at most ceil(C/block_k)+1) blocks the chunk touched
    by re-summing their kt rows.  The rebuild — not a scatter-subtract —
    keeps ktb bitwise equal to the incremental per-step adds of sequential
    decode: float subtraction does not invert addition, but a block re-sum
    accumulates the same rows in the same order as the per-row adds (the
    identity ``truncate_cache`` already relies on).  Resulting cache state
    is bitwise the state sequential decode leaves after emitting ``keep``
    tokens.  Unstacked caches only."""
    keep = jnp.asarray(keep, jnp.int32)
    b = keep.shape[0]
    rows = jnp.arange(b)[:, None]
    offs = jnp.arange(c)[None, :]
    act = jnp.ones((b,), bool) if active is None else active

    def walk(node):
        if isinstance(node, dict):
            if "page_tbl" in node:
                raise ValueError(
                    "commit_chunk does not support paged caches — the "
                    "scheduler gates speculative verify off when paged")
            if "pos" not in node:
                return {k: walk(v) for k, v in node.items()}
            pos_now = node["pos"]                      # (B,) == start + adv
            start = pos_now - jnp.where(act, c, 0)
            out = dict(node)
            out["pos"] = (start + keep).astype(pos_now.dtype)
            for name in _COMMIT_ROW_KEYS:
                if name not in node:
                    continue
                leaf = node[name]
                s = leaf.shape[1]
                # rejected rows' slots; committed offsets pushed OOB (drop)
                wslot = jnp.where(
                    (offs < (c - keep)[:, None]) & act[:, None],
                    start[:, None] + keep[:, None] + offs, s)
                zeros = jnp.zeros((b, c) + leaf.shape[2:], leaf.dtype)
                out[name] = leaf.at[rows, wslot].set(zeros, mode="drop")
            if "ktb" in node:
                kt = out["kt"]
                bkd = cfg.dsa.block_k
                n_kb = node["ktb"].shape[1]
                nb_t = -(-c // bkd) + 1               # chunk-touched blocks
                jbs = (start // bkd)[:, None] + jnp.arange(nb_t)[None, :]
                ridx = (jbs[:, :, None] * bkd
                        + jnp.arange(bkd)[None, None, :]).reshape(
                            b, nb_t * bkd)
                rclamp = jnp.minimum(ridx, kt.shape[1] - 1)
                g = jnp.take_along_axis(kt, rclamp[:, :, None], axis=1)
                if "kt_s" in node:
                    gs = jnp.take_along_axis(out["kt_s"], rclamp, axis=1)
                    g = Q.dequant(g, gs)
                sums = g.reshape(b, nb_t, bkd, -1).sum(axis=2)
                sjb = jnp.where((jbs < n_kb) & act[:, None], jbs, n_kb)
                if "ktb_s" in node:
                    bq, bs = Q.quant_store(sums, axis=-1)
                    out["ktb"] = node["ktb"].at[rows, sjb].set(
                        bq, mode="drop")
                    out["ktb_s"] = node["ktb_s"].at[rows, sjb].set(
                        bs, mode="drop")
                else:
                    out["ktb"] = node["ktb"].at[rows, sjb].set(
                        sums.astype(node["ktb"].dtype), mode="drop")
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(caches)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int, flags: RunFlags,
               dtype=jnp.bfloat16, pages: Optional[int] = None):
    """pages: page count of a PAGED resident cache — every attention
    sub-block's k/v (and DSA kt/ktb) leaves become flat physical page
    pools indirected by a per-slot ``page_tbl`` over the logical
    [0, max_len) geometry (see models.attention.init_cache_attention).
    Serving-engine layout only (inference.engine.can_page gates archs)."""
    defs = B.group_defs(cfg)
    ng = B.n_groups(cfg)
    enc_len = cfg.enc_seq_len if cfg.enc_dec else (
        cfg.n_image_tokens if cfg.cross_attn_period else 0)
    one = {f"b{i}": B.init_subblock_cache(cfg, d, batch, max_len, flags,
                                          dtype, enc_len=enc_len,
                                          pages=pages)
           for i, d in enumerate(defs)}
    groups = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (ng,) + a.shape), one)
    caches: Dict[str, Any] = {"groups": groups}
    if cfg.moe is not None and cfg.moe.first_k_dense:
        d = B.SubBlockDef("mla" if cfg.mla is not None else "attn", moe=False)
        caches["prologue"] = [
            B.init_subblock_cache(cfg, d, batch, max_len, flags, dtype,
                                  enc_len=enc_len, pages=pages)
            for _ in range(cfg.moe.first_k_dense)]
    return caches


def cache_specs(cfg: ArchConfig, caches, flags: RunFlags):
    defs = B.group_defs(cfg)

    def strip(a):
        return jax.ShapeDtypeStruct(a.shape[1:], a.dtype)

    one = {f"b{i}": B.subblock_cache_specs(
        cfg, d, jax.tree.map(strip, caches["groups"][f"b{i}"]))
        for i, d in enumerate(defs)}
    specs: Dict[str, Any] = {
        "groups": map_specs(lambda s: ("layers",) + tuple(s), one)}
    if "prologue" in caches:
        d = B.SubBlockDef("mla" if cfg.mla is not None else "attn", moe=False)
        specs["prologue"] = [B.subblock_cache_specs(cfg, d, c)
                             for c in caches["prologue"]]
    return specs


def unstacked_cache_specs(cfg: ArchConfig, caches):
    """Logical-axis spec tree parallel to an UNSTACKED decode cache (the
    per-layer list layout of ``unstack_group_caches``) — what the serving
    engines resolve against the serving mesh to land the resident cache
    sharded over the slots axis (distributed.sharding.shard_put_tree)."""
    defs = B.group_defs(cfg)
    specs: Dict[str, Any] = {"groups": [
        {f"b{i}": B.subblock_cache_specs(cfg, d, g[f"b{i}"])
         for i, d in enumerate(defs)} for g in caches["groups"]]}
    if "prologue" in caches:
        d = B.SubBlockDef("mla" if cfg.mla is not None else "attn", moe=False)
        specs["prologue"] = [B.subblock_cache_specs(cfg, d, c)
                             for c in caches["prologue"]]
    return specs
