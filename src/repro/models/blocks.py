"""Composable transformer blocks and layer-group construction.

A *group* is the repeating unit scanned over with stacked params:
  dense archs:   group = 1 block                         (scan n_layers)
  deepseek:      3 dense prologue blocks + group = 1 MoE block (scan 58)
  jamba:         group = 8 blocks, kinds [m,m,m,m,a,m,m,m], MoE on odd
  llama-vision:  group = 5 blocks, cross-attn at index 3
  whisper:       encoder groups (self) + decoder groups (self+cross)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, is_moe_layer
from repro.distributed.sharding import shard
from repro.models import ssm
from repro.models.attention import (RunFlags, apply_attention, apply_mla,
                                    cache_specs_attention, cache_specs_mla,
                                    init_attention, init_cache_attention,
                                    init_cache_mla, init_mla)
from repro.models.common import dense_init, rms_norm
from repro.models.moe import apply_moe, init_moe


@dataclasses.dataclass(frozen=True)
class SubBlockDef:
    kind: str          # attn | mla | mamba | rwkv
    moe: bool = False
    cross: bool = False    # has an extra gated cross-attn sub-layer
    causal: bool = True


def group_defs(cfg: ArchConfig, decoder: bool = True) -> List[SubBlockDef]:
    """The repeating sub-block structure of one scan group."""
    if cfg.enc_dec and not decoder:
        return [SubBlockDef("attn", causal=False)]
    if cfg.rwkv is not None:
        return [SubBlockDef("rwkv")]
    if cfg.mamba is not None and cfg.attn_layer_period:
        period = cfg.attn_layer_period
        return [SubBlockDef(
            "attn" if i == cfg.attn_layer_offset else "mamba",
            moe=is_moe_layer(cfg, i)) for i in range(period)]
    if cfg.cross_attn_period:
        period = cfg.cross_attn_period
        return [SubBlockDef("attn", cross=(i == period - 2))
                for i in range(period)]
    if cfg.enc_dec and decoder:
        return [SubBlockDef("attn", cross=True)]
    kind = "mla" if cfg.mla is not None else "attn"
    # uniform MoE pattern (mixtral: every layer; deepseek handled via prologue)
    moe = cfg.moe is not None and cfg.moe.layer_period == 1
    return [SubBlockDef(kind, moe=moe)]


def n_groups(cfg: ArchConfig, decoder: bool = True) -> int:
    if cfg.enc_dec and not decoder:
        return cfg.n_enc_layers
    defs = group_defs(cfg, decoder)
    n = cfg.n_layers - (cfg.moe.first_k_dense if cfg.moe else 0)
    assert n % len(defs) == 0, (cfg.name, n, len(defs))
    return n // len(defs)


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, dtype=jnp.float32):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    params = {"w1": dense_init(ks[0], (d, f), dtype=dtype),
              "w3": dense_init(ks[1], (d, f), dtype=dtype),
              "w2": dense_init(ks[2], (f, d), dtype=dtype)}
    specs = {"w1": ("embed", "mlp"), "w3": ("embed", "mlp"),
             "w2": ("mlp", "embed")}
    return params, specs


def apply_mlp(params, x):
    h = jax.nn.silu(x @ params["w1"].astype(x.dtype))
    h = h * (x @ params["w3"].astype(x.dtype))
    h = shard(h, "batch", "seq", "mlp")
    return h @ params["w2"].astype(x.dtype)


# ---------------------------------------------------------------------------
# sub-block init / apply
# ---------------------------------------------------------------------------


def init_subblock(key, cfg: ArchConfig, d: SubBlockDef, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    params: Dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), dtype),
                              "norm2": jnp.ones((cfg.d_model,), dtype)}
    specs: Dict[str, Any] = {"norm1": ("embed_act",),
                             "norm2": ("embed_act",)}
    if d.kind == "attn":
        params["attn"], specs["attn"] = init_attention(ks[0], cfg, dtype=dtype)
    elif d.kind == "mla":
        params["attn"], specs["attn"] = init_mla(ks[0], cfg, dtype=dtype)
    elif d.kind == "mamba":
        params["attn"], specs["attn"] = ssm.init_mamba(ks[0], cfg, dtype=dtype)
    elif d.kind == "rwkv":
        params["attn"], specs["attn"] = ssm.init_rwkv(ks[0], cfg, dtype=dtype)
    if d.cross:
        params["xattn"], specs["xattn"] = init_attention(
            ks[2], cfg, cross=True, dtype=dtype)
        params["xnorm"] = jnp.ones((cfg.d_model,), dtype)
        params["xgate"] = jnp.zeros((), dtype)
        specs["xnorm"] = ("embed_act",)
        specs["xgate"] = ()
    if d.kind == "rwkv":
        params["mlp"], specs["mlp"] = ssm.init_rwkv_ffn(ks[1], cfg, dtype)
    elif d.moe:
        params["mlp"], specs["mlp"] = init_moe(ks[1], cfg, dtype=dtype)
    else:
        params["mlp"], specs["mlp"] = init_mlp(ks[1], cfg, dtype=dtype)
    return params, specs


def init_subblock_cache(cfg: ArchConfig, d: SubBlockDef, batch: int,
                        max_len: int, flags: RunFlags, dtype=jnp.bfloat16,
                        enc_len: int = 0, pages: Optional[int] = None):
    c: Dict[str, Any] = {}
    if d.kind == "attn":
        c["attn"] = init_cache_attention(cfg, batch, max_len, flags, dtype,
                                         pages=pages)
    elif d.kind == "mla":
        c["attn"] = init_cache_mla(cfg, batch, max_len, dtype)
    elif d.kind == "mamba":
        c["attn"] = ssm.init_cache_mamba(cfg, batch, dtype)
    elif d.kind == "rwkv":
        c["attn"] = ssm.init_cache_rwkv(cfg, batch, dtype)
    if d.cross:
        hd = cfg.resolved_head_dim
        c["xattn"] = {
            "ck": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
            "cv": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype)}
    return c


def subblock_cache_specs(cfg: ArchConfig, d: SubBlockDef, cache):
    s: Dict[str, Any] = {}
    if d.kind == "attn":
        s["attn"] = cache_specs_attention(cache["attn"])
    elif d.kind == "mla":
        s["attn"] = cache_specs_mla(cache["attn"])
    elif d.kind == "mamba":
        s["attn"] = ssm.cache_specs_mamba(cache["attn"])
    elif d.kind == "rwkv":
        s["attn"] = ssm.cache_specs_rwkv(cache["attn"])
    if d.cross:
        s["xattn"] = {"ck": ("batch", None, "kv_heads", "qkv"),
                      "cv": ("batch", None, "kv_heads", "qkv")}
    return s


def apply_subblock(params, cfg: ArchConfig, flags: RunFlags, d: SubBlockDef,
                   x, cache=None, enc=None, pos_offset=0, active=None,
                   chunk_len=None, sel_len=None):
    """Pre-norm residual block.  Returns (x, new_cache, aux).

    active: optional (B,) bool decode slot mask (continuous batching) —
    inactive slots freeze their attention caches; recurrent (ssm) state is
    instead fully overwritten at slot admission.
    chunk_len: optional (B,) — chunk-append decode (chunked prefill): x is
    a C-token chunk per slot, rows past chunk_len are padding (attention
    kinds only; the scheduler gates chunking off for ssm/rwkv archs).
    """
    aux: Dict[str, jax.Array] = {}
    new_cache = dict(cache) if cache is not None else None
    with jax.named_scope("qkv"):
        h = rms_norm(x, params["norm1"].astype(x.dtype), cfg.norm_eps)
    decode = flags.mode == "decode"
    if d.kind == "attn":
        y, c, a = apply_attention(params["attn"], cfg, flags, h,
                                  cache=None if cache is None else cache["attn"],
                                  causal=d.causal, pos_offset=pos_offset,
                                  use_rope=not cfg.enc_dec, active=active,
                                  chunk_len=chunk_len, sel_len=sel_len)
        aux.update(a)
    elif d.kind == "mla":
        y, c, a = apply_mla(params["attn"], cfg, flags, h,
                            cache=None if cache is None else cache["attn"],
                            pos_offset=pos_offset, active=active,
                            chunk_len=chunk_len, sel_len=sel_len)
        aux.update(a)
    elif d.kind == "mamba":
        y, c = ssm.apply_mamba(params["attn"], cfg, h,
                               cache=None if cache is None else cache["attn"],
                               decode=decode)
    else:  # rwkv
        y, c = ssm.apply_rwkv(params["attn"], cfg, h,
                              cache=None if cache is None else cache["attn"],
                              decode=decode)
    if new_cache is not None and c is not None:
        new_cache["attn"] = c
    with jax.named_scope("attend"):
        x = x + y
    if d.cross and enc is not None or (d.cross and decode):
        h = rms_norm(x, params["xnorm"].astype(x.dtype), cfg.norm_eps)
        y, cx, _ = apply_attention(
            params["xattn"], cfg, flags, h, x_kv=enc,
            cache=None if cache is None else cache.get("xattn"),
            causal=False, use_rope=False)
        x = x + jnp.tanh(params["xgate"].astype(x.dtype)) * y
        if new_cache is not None and cx is not None:
            new_cache["xattn"] = cx
    with jax.named_scope("mlp"):
        h = rms_norm(x, params["norm2"].astype(x.dtype), cfg.norm_eps)
        if d.kind == "rwkv":
            prev = None if cache is None else cache["attn"].get("ffn_prev")
            y = ssm.apply_rwkv_ffn(params["mlp"], cfg, h, prev)
            if new_cache is not None:
                new_cache["attn"]["ffn_prev"] = h[:, -1]
        elif d.moe:
            # flags.moe_dense (Engine(moe_prefill="dense")): prefill routes
            # the decode-dense expert path too, so whole-prompt prefill and
            # chunk steps are token-exact and MoE archs can chunk-admit
            y, a = apply_moe(params["mlp"], cfg, h,
                             decode=decode or flags.moe_dense)
            for k, v in a.items():
                aux[k] = aux.get(k, 0.0) + v
        else:
            y = apply_mlp(params["mlp"], h)
        x = x + y
    x = shard(x, "batch", "seq_sp", "embed_act")
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# group init / apply (the scanned unit)
# ---------------------------------------------------------------------------


def init_group(key, cfg: ArchConfig, decoder: bool = True,
               dtype=jnp.float32):
    defs = group_defs(cfg, decoder)
    params, specs = {}, {}
    for i, d in enumerate(defs):
        p, s = init_subblock(jax.random.fold_in(key, i), cfg, d, dtype)
        params[f"b{i}"] = p
        specs[f"b{i}"] = s
    return params, specs


def apply_group(params, cfg: ArchConfig, flags: RunFlags, defs, x,
                cache=None, enc=None, pos_offset=0, active=None,
                chunk_len=None, sel_len=None):
    auxes: Dict[str, jax.Array] = {}
    new_cache = {} if cache is not None else None
    for i, d in enumerate(defs):
        x, c, a = apply_subblock(params[f"b{i}"], cfg, flags, d, x,
                                 cache=None if cache is None else cache[f"b{i}"],
                                 enc=enc, pos_offset=pos_offset, active=active,
                                 chunk_len=chunk_len, sel_len=sel_len)
        if new_cache is not None:
            new_cache[f"b{i}"] = c
        for k, v in a.items():
            auxes[k] = auxes.get(k, 0.0) + v
    return x, new_cache, auxes
