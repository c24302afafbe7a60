"""Random serving weights, made by the benchmark from the seed.

The tree is the serving program's own layout (``layout``: the paths,
shapes, dtypes and logical axes of ``models.transformer.init_model``,
evaluated abstractly), built on the device in one jitted call.  Each leaf
is drawn from its own key, ``fold_in(seed key, i)`` for the i-th path in
sorted order, by its name and rank: a norm holds ones, the DSA projection
``dsa/p`` the paper's sparse +-1, a leaf of rank 2 or more is normal over
the square root of its fan-in (its second-to-last axis), and a leaf of
lower rank (a bias, gate or offset) zeros.  So a new model's leaves need
no edit here.  The reference (the module its configuration names) reads
the same tree, so both sides run one model.

With a ``plant`` (a cell's ``"plant"``: ``{"marker_ids": [lo, hi], ...}``)
the DSA prediction path is made decisive.  Residual channel 0 holds 1 for
the marker tokens ``lo <= t < hi`` and 0 for every other token, channel 1
holds 1 for every token, and no layer writes to either: channels 0 and 1
of every leaf whose last logical axis is ``"embed"`` are 0 (attention
``wo``, MLP and expert ``w2``, shared-expert ``sw2``, mamba ``out_proj``),
the embedding table aside.  The projection P reads only those two
channels into prediction columns 0 and 1, at a gain large enough that
4-bit quantization rounds every other column to 0; W~q maps column 1 and
W~k column 0 onto score column 0.  So Q~ is a positive multiple of e0 for
every query and K~ is a positive multiple of e0 on a marker row and
exactly 0 elsewhere: a key block's score grows with the markers in it,
and the prompts (``generator.plant``) give each block a count distinct
from the others' by a factor of 1.3 or more, far beyond bf16 rounding.
Block selection then has one answer at every decode step, in the program
and in the reference alike.  The output head's columns of the marker
tokens are 0, so no marker is ever served and decode blocks score 0.
Every shape, and so every cost, is unchanged.

A prompt's own selection scores a key block by its largest row, so
markers of one value tie there.  A graded plant (``plant["graded"]``,
``{"levels": [...], "rows": r}``) gives marker id ``lo + g`` the channel-0
value ``levels[g]`` in place of 1: each level above 1 makes channel 0 the
row's largest prediction column, so K~ on that row is its level over the
row's RMS, not a 4-bit step, and a block of ``r`` rows of one level
scores by that level both by its largest row and by its sum.  Levels a
tenth or more apart stay apart beyond bf16 rounding and the RMS spread
of rows; they stay far under sqrt(d_model), where RMSNorm would flatten
them.  Blocks without markers score exactly 0 on both sides, so their
ties break by block index alike.
"""
from __future__ import annotations

import functools
import json
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def predictor_k(d_model: int, sigma: float) -> int:
    """Width of the DSA prediction path: sigma * d_model in multiples of 8."""
    return max(8, int(round(sigma * d_model / 8)) * 8)


@dataclass(frozen=True)
class Leaf:
    """One weight of the program's layout: its shape, dtype and logical
    axes (``init_model``'s spec of it)."""
    shape: tuple
    dtype: Any
    axes: tuple

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))

    @property
    def stacked(self) -> bool:
        """Whether the leaf holds a group of layers on its leading axis."""
        return self.axes[:1] == ("layers",)

    @property
    def core(self) -> tuple:
        """The shape of one layer's weight."""
        return self.shape[1:] if self.stacked else self.shape


def layout(arch: dict) -> dict:
    """{path: Leaf} of every weight the serving program takes for a
    configuration's ``arch``, sorted by path; paths are the tree's keys
    joined with '/', list nodes by their index (``prologue/0/attn/wq``)."""
    return _layout(json.dumps(arch, sort_keys=True))[0]


@functools.lru_cache(maxsize=None)
def _layout(key: str):
    from bench.run import arch_config
    from repro.models.transformer import init_model
    cfg = arch_config(json.loads(key))
    specs = {}

    def params(k):
        p, specs["tree"] = init_model(k, cfg)
        return p

    tree = jax.eval_shape(params, jax.random.PRNGKey(0))
    axes = jax.tree_util.tree_leaves(
        jax.tree.map(lambda a, s: Leaf(a.shape, a.dtype, tuple(s)), tree,
                     specs["tree"]))
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    leaves = dict(zip(paths, axes))
    return ({p: leaves[p] for p in sorted(leaves)},
            jax.tree_util.tree_structure(tree), paths)


# the planted prediction path: P's gain on channels 0 and 1, and the
# weight W~q / W~k give them on score column 0
PLANT_GAIN = 1000.0


def _leaf(key, path: str, leaf: Leaf, plant=None):
    name = path.rsplit("/", 1)[-1]
    shape, dtype = leaf.shape, leaf.dtype
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if plant is not None:
        planted = _planted(key, path, leaf, plant)
        if planted is not None:
            return planted.astype(dtype)
    if path.endswith("dsa/p"):
        # the paper's constant sparse projection: sqrt(3/k) * {-1, 0, +1}
        # with probabilities 1/6, 2/3, 1/6
        u = jax.random.uniform(key, shape)
        val = jnp.where(u < 1 / 6, -1.0, jnp.where(u < 2 / 6, 1.0, 0.0))
        return (val * math.sqrt(3.0 / shape[-1])).astype(dtype)
    if len(leaf.core) < 2:
        # a bias, gate or offset
        return jnp.zeros(shape, dtype)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _planted(key, path: str, leaf: Leaf, plant: dict):
    """The leaves a plant changes (module docstring); None for the rest.
    The embedding is standard normal, so channels 0 and 1 are of the size
    of any other residual channel at the first layer."""
    f32 = Leaf(leaf.shape, jnp.float32, leaf.axes)
    lo, hi = plant["marker_ids"]
    if path == "embed":
        t = jnp.arange(leaf.shape[0])
        x = jax.random.normal(key, leaf.shape, jnp.float32)
        if "graded" in plant:
            levels = jnp.asarray(plant["graded"]["levels"], jnp.float32)
            if len(levels) != hi - lo:
                raise ValueError(f"the plant grades {len(levels)} levels "
                                 f"for {hi - lo} marker ids")
            c0 = jnp.where((t >= lo) & (t < hi),
                           levels[jnp.clip(t - lo, 0, len(levels) - 1)], 0.0)
        else:
            c0 = ((t >= lo) & (t < hi)).astype(jnp.float32)
        return x.at[:, 0].set(c0).at[:, 1].set(1.0)
    if path == "lm_head":
        return _leaf(key, path, f32).at[:, lo:hi].set(0.0)
    if leaf.axes[-1:] == ("embed",):
        # writes the residual stream: not to the plant's two channels
        return _leaf(key, path, f32).at[..., :2].set(0.0)
    if path.endswith("dsa/p"):
        x = _leaf(key, path, f32)
        x = x.at[..., :2, :].set(0.0).at[..., :, :2].set(0.0)
        return x.at[..., 0, 0].set(PLANT_GAIN).at[..., 1, 1].set(PLANT_GAIN)
    if path.endswith("dsa/wq") or path.endswith("dsa/wk"):
        src = 1 if path.endswith("wq") else 0
        x = _leaf(key, path, f32).at[..., :2, :].set(0.0)
        return x.at[..., src, 0].set(1.0)
    return None


def make(arch: dict, seed: int, plant: dict = None):
    """The weight tree from ``seed`` (any integer), on the default device,
    in the program's layout (``layout``); with ``plant``, a decisive DSA
    prediction path (module docstring)."""
    lay, treedef, order = _layout(json.dumps(arch, sort_keys=True))

    def build(key):
        w = {p: _leaf(jax.random.fold_in(key, i), p, leaf, plant)
             for i, (p, leaf) in enumerate(lay.items())}
        return jax.tree_util.tree_unflatten(treedef, [w[p] for p in order])

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    return jax.block_until_ready(jax.jit(build)(key))
