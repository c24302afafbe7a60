"""Random serving weights, made by the benchmark from the seed.

The tree has the layout the serving engine takes (``models.transformer``:
embedding, layer weights stacked on a leading layer axis under
``groups/b0``, final norm, output head) and is built on the device in one
jitted call, in the configuration's parameter dtype.  The reference
(``bench/reference.py``) reads the same tree, so both sides run one model.

With a ``plant`` (a cell's ``"plant"``: ``{"marker_ids": [lo, hi], ...}``)
the DSA prediction path is made decisive.  Residual channel 0 holds 1 for
the marker tokens ``lo <= t < hi`` and 0 for every other token, channel 1
holds 1 for every token, and no layer writes to either (those columns of
``wo`` and ``w2`` are 0).  The projection P reads only those two channels
into prediction columns 0 and 1, at a gain large enough that 4-bit
quantization rounds every other column to 0; W~q maps column 1 and W~k
column 0 onto score column 0.  So Q~ is the same positive multiple of e0
for every query and K~ is a positive multiple of e0 on a marker row and 0
elsewhere: a key block's score is proportional to the number of markers
in it, and the prompts (``generator.plant``) give each block a count
distinct from the others' by a factor of 1.3 or more, far beyond bf16
rounding.  Block selection then has one answer at every decode step, in
the program and in the reference alike.  The output head's columns of the
marker tokens are 0, so no marker is ever served and decode blocks score
0.  Every shape, and so every cost, is unchanged.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def predictor_k(d_model: int, sigma: float) -> int:
    """Width of the DSA prediction path: sigma * d_model in multiples of 8."""
    return max(8, int(round(sigma * d_model / 8)) * 8)


def shapes(arch: dict) -> dict:
    """{path: shape} of every weight, paths joined with '/'."""
    n, d, f, v = arch["n_layers"], arch["d_model"], arch["d_ff"], arch["vocab"]
    hd = arch["head_dim"]
    nq, nkv = arch["n_heads"] * hd, arch["n_kv_heads"] * hd
    k = predictor_k(d, arch["dsa"]["sigma"])
    g = "groups/b0/"
    return {
        "embed": (v, d),
        g + "norm1": (n, d), g + "norm2": (n, d),
        g + "attn/wq": (n, d, nq), g + "attn/wk": (n, d, nkv),
        g + "attn/wv": (n, d, nkv), g + "attn/wo": (n, nq, d),
        g + "attn/dsa/p": (n, d, k), g + "attn/dsa/wq": (n, k, k),
        g + "attn/dsa/wk": (n, k, k),
        g + "mlp/w1": (n, d, f), g + "mlp/w3": (n, d, f),
        g + "mlp/w2": (n, f, d),
        "final_norm": (d,), "lm_head": (d, v),
    }


# the planted prediction path: P's gain on channels 0 and 1, and the
# weight W~q / W~k give them on score column 0
PLANT_GAIN = 1000.0


def _leaf(key, path: str, shape: tuple, dtype, plant=None):
    name = path.rsplit("/", 1)[-1]
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if plant is not None:
        planted = _planted(key, path, shape, plant)
        if planted is not None:
            return planted.astype(dtype)
    if path.endswith("dsa/p"):
        # the paper's constant sparse projection: sqrt(3/k) * {-1, 0, +1}
        # with probabilities 1/6, 2/3, 1/6
        u = jax.random.uniform(key, shape)
        val = jnp.where(u < 1 / 6, -1.0, jnp.where(u < 2 / 6, 1.0, 0.0))
        return (val * math.sqrt(3.0 / shape[-1])).astype(dtype)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _planted(key, path: str, shape: tuple, plant: dict):
    """The leaves a plant changes (module docstring); None for the rest.
    The embedding is standard normal, so channels 0 and 1 are of the size
    of any other residual channel at the first layer."""
    if path == "embed":
        lo, hi = plant["marker_ids"]
        t = jnp.arange(shape[0])
        x = jax.random.normal(key, shape, jnp.float32)
        x = x.at[:, 0].set(((t >= lo) & (t < hi)).astype(jnp.float32))
        return x.at[:, 1].set(1.0)
    if path == "lm_head":
        lo, hi = plant["marker_ids"]
        x = _leaf(key, path, shape, jnp.float32)
        return x.at[:, lo:hi].set(0.0)
    if path.endswith("attn/wo") or path.endswith("mlp/w2"):
        return _leaf(key, path, shape, jnp.float32).at[..., :2].set(0.0)
    if path.endswith("dsa/p"):
        x = _leaf(key, path, shape, jnp.float32)
        x = x.at[..., :2, :].set(0.0).at[..., :, :2].set(0.0)
        return x.at[..., 0, 0].set(PLANT_GAIN).at[..., 1, 1].set(PLANT_GAIN)
    if path.endswith("dsa/wq") or path.endswith("dsa/wk"):
        src = 1 if path.endswith("wq") else 0
        x = _leaf(key, path, shape, jnp.float32).at[..., :2, :].set(0.0)
        return x.at[..., src, 0].set(1.0)
    return None


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, val in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = val
    return out


def make(arch: dict, seed: int, plant: dict = None):
    """The weight tree from ``seed`` (any integer), on the default device;
    with ``plant``, a decisive DSA prediction path (module docstring)."""
    dtype = jnp.dtype(arch["param_dtype"])
    sh = shapes(arch)

    def build(key):
        return _nest({p: _leaf(jax.random.fold_in(key, i), p, s, dtype,
                               plant)
                      for i, (p, s) in enumerate(sorted(sh.items()))})

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    return jax.block_until_ready(jax.jit(build)(key))
