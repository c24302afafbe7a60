"""Plain reference of the served model, teacher-forced over served tokens.

It reads the configuration (``bench/configs/<name>.json``) and the
benchmark's weight tree (``bench/weights.py``) and nothing of the serving
program.  The model: token embedding; per layer a pre-norm residual block
of RMSNorm -> attention with RoPE (pairs interleaved) and grouped KV heads
-> RMSNorm -> SwiGLU MLP; final RMSNorm and output head.

Attention is the configuration's dynamic sparse attention (DSA), as served:

- prediction path: xp = q4(h @ P); Q~ = q4(xp @ q4(Wq~)); K~ = q4(xp @
  q4(Wk~)), where q4 is symmetric per-row fake quantization to ``quant_bits``
  bits (round half to even, clipped to [-2^(b-1), 2^(b-1)-1] steps);
- prompt (chunked prefill): the prompt is right-padded with token 0 to a
  whole number of ``block_q`` rows; every query block takes the mean of its
  rows' Q~ (pad rows included), scores each key block j at or before it by
  the max over that block's rows of the dot product with K~ (pad rows
  included), always keeps itself and the ``local_blocks`` blocks before it,
  and keeps ``max(min_blocks + local_blocks, round(n_kb * (1 - sparsity)))``
  blocks in all, where n_kb is the prompt bucket's block count (the
  bucket is the next power of two of the prompt length);
- decode step at position p: the score of key block j is Q~_p . (sum of K~
  over real rows <= p of block j) / block_k; blocks that overlap the last
  ``decode_local`` rows are always kept, and ``ceil(keep / block_k) +
  ceil(decode_local / block_k) + 1`` blocks are kept in all, keep =
  round(S * (1 - sparsity)) for S resident cache rows;
- a query attends the real key rows of its kept blocks at or before its
  own position, softmax(q.k / sqrt(head_dim)).

``precision="float32"`` is the reference; ``"fp8"`` runs every matmul on
operands rounded to float8_e4m3 with a per-row (activations) or
per-output-column (weights) scale: the control of a bf16 configuration,
the precision below the one it states.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def geometry(arch: dict, max_len: int, prompt_len: int) -> dict:
    """Static selection sizes of one request: the prompt bucket and the
    resident cache, as the configuration's DSA states them."""
    dsa = arch["dsa"]
    bq, bk = dsa["block_q"], dsa["block_k"]
    bucket = max(16, 1 << (int(prompt_len) - 1).bit_length())
    s_dec = -(-max_len // bk) * bk
    n_kb_pre = bucket // bk
    keep_pre = max(1, int(round(n_kb_pre * (1.0 - dsa["sparsity"]))))
    keep_dec = max(1, int(round(s_dec * (1.0 - dsa["sparsity"]))))
    local = dsa["decode_local"]
    n_kb_dec = s_dec // bk
    return {
        "bucket": bucket, "block_q": bq, "block_k": bk,
        "nb_keep_pre": min(n_kb_pre, max(dsa["min_blocks"]
                                         + dsa["local_blocks"], keep_pre)),
        "local_blocks": dsa["local_blocks"],
        "n_kb_dec": n_kb_dec, "decode_local": local,
        "nb_keep_dec": min(n_kb_dec, -(-keep_dec // bk) + -(-local // bk) + 1),
    }


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 448.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, precision):
    """a (..., n) @ w (n, m) in float32, or on fp8-rounded operands."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _q(x, bits):
    qmax = 2.0 ** (bits - 1) - 1.0
    s = jnp.max(jnp.abs(x), -1, keepdims=True) / qmax
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def _rope(x, pos, theta):
    """x (T, H, hd), pos (T,): rotate interleaved pairs (x0,x1),(x2,x3)..."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _attend(q, k, v, mask, n_kv):
    """q (T, Hq, hd), k/v (S, Hkv, hd), mask (T, S) -> (T, Hq*hd)."""
    t, hq, hd = q.shape
    g = hq // n_kv
    qg = q.reshape(t, n_kv, g, hd)
    s = jnp.einsum("thgd,shd->hgts", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(mask[None, None], s, NEG)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("hgts,shd->thgd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(t, hq * hd)


def _topk_mask(scores, forced, valid, nb_keep):
    """(..., n) bool: the nb_keep best valid blocks, forced ones first."""
    s = jnp.where(valid, scores, NEG)
    s = jnp.where(forced & valid, jnp.inf, s)
    vals, idx = jax.lax.top_k(s, nb_keep)
    keep = jnp.zeros(s.shape, bool)
    hit = jax.nn.one_hot(idx, s.shape[-1], dtype=bool) & (vals > NEG / 2)[
        ..., None]
    return keep | hit.any(-2)


def predict(w_attn, h, bits: int, mm):
    """The DSA prediction path of normed rows h: (Q~, K~)."""
    xq = _q(mm(h, w_attn["dsa"]["p"]), bits)
    qt = _q(mm(xq, _q(w_attn["dsa"]["wq"].astype(jnp.float32), bits)), bits)
    kt = _q(mm(xq, _q(w_attn["dsa"]["wk"].astype(jnp.float32), bits)), bits)
    return qt, kt


def prompt_scores(qtp, ktp, geo: dict):
    """(nQb, nKb) block scores of the padded prompt rows: each query
    block's mean Q~ against every row's K~, the largest row of each key
    block (pad rows included)."""
    bq, bk = geo["block_q"], geo["block_k"]
    p_rows = qtp.shape[0]
    n_qb, n_kb_p = p_rows // bq, p_rows // bk
    q_blk = qtp.reshape(n_qb, bq, -1).mean(1)
    sc = jnp.einsum("qc,sc->qs", q_blk, ktp,
                    precision=jax.lax.Precision.HIGHEST)
    return sc.reshape(n_qb, n_kb_p, bk).max(-1)


def prompt_keep(bs, geo: dict):
    """(nQb, nKb) bool: the key blocks each prompt query block keeps."""
    n_qb, n_kb_p = bs.shape
    qi = jnp.arange(n_qb)[:, None]
    kj = jnp.arange(n_kb_p)[None, :]
    return _topk_mask(bs, kj > qi - geo["local_blocks"] - 1, kj <= qi,
                      geo["nb_keep_pre"])


def decode_scores(qtd, ktp, ktd, plen, nd, geo: dict):
    """(D, nKb) block scores of the decode rows (positions plen, plen+1,
    ...; the first ``nd`` real): Q~ against each block's K~ sum over the
    real rows the step has seen, over block_k."""
    bk, n_kb = geo["block_k"], geo["n_kb_dec"]
    pos_p = jnp.arange(ktp.shape[0])
    pos_d = plen + jnp.arange(ktd.shape[0])
    blk_p = jax.nn.one_hot(pos_p // bk, n_kb, dtype=jnp.float32)
    ktb_p = jnp.einsum("sj,sc->jc", blk_p * (pos_p < plen)[:, None], ktp,
                       precision=jax.lax.Precision.HIGHEST)
    live_d = (jnp.arange(ktd.shape[0]) < nd)[:, None, None]
    blk_d = jax.nn.one_hot(pos_d // bk, n_kb, dtype=jnp.float32)
    ktb = ktb_p[None] + jnp.cumsum(
        jnp.where(live_d, blk_d[:, :, None] * ktd[:, None, :], 0.0), 0)
    return jnp.einsum("tc,tjc->tj", qtd, ktb,
                      precision=jax.lax.Precision.HIGHEST) / bk


def decode_keep(s_blk, kv_len, geo: dict):
    """(D, nKb) bool: the cache blocks each decode row keeps, at
    ``kv_len`` (D,) rows of cache."""
    bk = geo["block_k"]
    jj = jnp.arange(geo["n_kb_dec"])[None, :]
    valid = jj * bk < kv_len[:, None]
    recent = (jj + 1) * bk > kv_len[:, None] - geo["decode_local"]
    return _topk_mask(s_blk, recent, valid, geo["nb_keep_dec"])


def swiglu(w_mlp, h, arch: dict, mm):
    """The configuration's MLP: SwiGLU."""
    u = jax.nn.silu(mm(h, w_mlp["w1"])) * mm(h, w_mlp["w3"])
    return mm(u, w_mlp["w2"])


@functools.partial(jax.jit, static_argnames=("arch_t", "geo_t", "precision",
                                             "mlp"))
def _layer(w, xp, xd, plen, nd, *, arch_t, geo_t, precision, mlp):
    """One layer over the padded prompt rows xp (P, d) and the decode rows
    xd (D, d): the prompt's real length is ``plen``, the first ``nd`` decode
    rows are real (positions plen, plen+1, ...); ``mlp(w["mlp"], h, arch,
    mm)`` is the block's MLP."""
    arch, geo = arch_t.get(), geo_t.get()
    hq, hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    bq, bk = geo["block_q"], geo["block_k"]
    bits = arch["dsa"]["quant_bits"]
    p_rows, d_rows = xp.shape[0], xd.shape[0]
    pos_p = jnp.arange(p_rows)
    pos_d = plen + jnp.arange(d_rows)
    mm = functools.partial(_mm, precision=precision)

    def proj(x, pos):
        h = _rms(x, w["norm1"].astype(jnp.float32), arch["norm_eps"])
        q = _rope(mm(h, w["attn"]["wq"]).reshape(-1, hq, hd), pos,
                  arch["rope_theta"])
        k = _rope(mm(h, w["attn"]["wk"]).reshape(-1, hkv, hd), pos,
                  arch["rope_theta"])
        v = mm(h, w["attn"]["wv"]).reshape(-1, hkv, hd)
        qt, kt = predict(w["attn"], h, bits, mm)
        return q, k, v, qt, kt

    qp, kp, vp, qtp, ktp = proj(xp, pos_p)
    qd, kd, vd, qtd, ktd = proj(xd, pos_d)

    # prompt rows: block selection per query block (pad rows take part)
    n_qb = p_rows // bq
    keep_p = prompt_keep(prompt_scores(qtp, ktp, geo), geo)  # (nQb, nKb)

    def prompt_block(i):
        rows = i * bq + jnp.arange(bq)
        q = jax.lax.dynamic_slice_in_dim(qp, i * bq, bq)
        mask = (keep_p[i][pos_p // bk][None, :]
                & (pos_p[None, :] <= rows[:, None])
                & (pos_p[None, :] < plen))
        return _attend(q, kp, vp, mask, hkv)

    att_p = jax.lax.map(prompt_block, jnp.arange(n_qb)).reshape(p_rows, -1)

    # decode rows: K~ block sums over the real rows each step has seen
    keep_d = decode_keep(decode_scores(qtd, ktp, ktd, plen, nd, geo),
                         pos_d + 1, geo)                     # (D, nKb)
    kpos = jnp.concatenate([pos_p, pos_d])
    kreal = jnp.concatenate([pos_p < plen, jnp.arange(d_rows) < nd])
    seen = kreal[None, :] & (kpos[None, :] <= pos_d[:, None])   # (D, S)
    mask_d = seen & jnp.take(keep_d, kpos // bk, axis=1)
    att_d = _attend(qd, jnp.concatenate([kp, kd]), jnp.concatenate([vp, vd]),
                    mask_d, hkv)

    def finish(x, att):
        x = x + mm(att, w["attn"]["wo"])
        h = _rms(x, w["norm2"].astype(jnp.float32), arch["norm_eps"])
        return x + mlp(w["mlp"], h, arch, mm)

    return finish(xp, att_p), finish(xd, att_d)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(w_norm, w_head, x, *, eps, precision):
    h = _rms(x, w_norm.astype(jnp.float32), eps)
    return _mm(h, w_head, precision)


class _Static:
    """A configuration dict as a static argument of ``jax.jit``: hashed
    and compared by its content."""

    def __init__(self, d: dict):
        self.key = json.dumps(d, sort_keys=True)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, _Static) and other.key == self.key

    def get(self) -> dict:
        return json.loads(self.key)


def served_logits(weights, arch: dict, max_len: int, prompt, served,
                  max_new: int, precision: str = "float32",
                  mlp=swiglu) -> np.ndarray:
    """Logits (len(served), vocab) float32 at the positions that produced
    each served token: the last prompt row, then each decode step fed the
    previous served token.  Runs layer by layer; ``max_new`` fixes the
    decode rows' static size so every request reuses one compile.  A
    model whose blocks differ only in their MLP passes its own ``mlp``
    (``swiglu``'s signature)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    plen, n = len(prompt), len(served)
    geo = geometry(arch, max_len, plen)
    p_rows = geo["bucket"]
    toks_p = np.zeros((p_rows,), np.int32)
    toks_p[:plen] = prompt
    toks_d = np.zeros((max_new,), np.int32)
    toks_d[:n - 1] = served[:-1]
    emb = weights["embed"]
    xp = emb[jnp.asarray(toks_p)].astype(jnp.float32)
    xd = emb[jnp.asarray(toks_d)].astype(jnp.float32)
    g = weights["groups"]["b0"]
    arch_t, geo_t = _Static(arch), _Static(geo)
    for i in range(arch["n_layers"]):
        wl = jax.tree.map(lambda a: a[i], g)
        xp, xd = _layer(wl, xp, xd, jnp.int32(plen), jnp.int32(n - 1),
                        arch_t=arch_t, geo_t=geo_t, precision=precision,
                        mlp=mlp)
    rows = jnp.concatenate([xp[plen - 1:plen], xd[:n - 1]])
    logits = _head(weights["final_norm"], weights["lm_head"], rows,
                   eps=arch["norm_eps"], precision=precision)
    return np.asarray(logits, np.float32)
