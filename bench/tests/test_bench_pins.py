"""The chat cell reads what it read before the harness took its layout from
the program: the same ``ArchConfig``, the same weight tree bit for bit
(against a frozen copy of the hand-written table and leaf rules that built
it), the same work counts, prompts and schedule, and the same reference
logits.  The frozen numbers were taken with the earlier harness."""
import hashlib
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, reference, run, weights, work

ROOT = Path(__file__).resolve().parents[2]
ARCH = json.loads((ROOT / "bench/configs/stablelm_3b.json").read_text())[
    "arch"]
CELL = json.loads((ROOT / "bench/cells/stablelm_3b.chat.json").read_text())
PLANT = CELL["plant"]
MAX_LEN = CELL["serving"]["max_len"]


# -- the earlier harness, frozen ---------------------------------------------

def frozen_arch_config(arch):
    from repro.configs.base import ArchConfig, DSAConfig
    dsa = dict(arch["dsa"])
    dsa.pop("decode_local")
    fields = {k: v for k, v in arch.items() if k != "dsa"}
    return ArchConfig(family="dense", dsa=DSAConfig(enabled=True, **dsa),
                      **fields)


def frozen_shapes(arch):
    n, d, f, v = arch["n_layers"], arch["d_model"], arch["d_ff"], arch["vocab"]
    hd = arch["head_dim"]
    nq, nkv = arch["n_heads"] * hd, arch["n_kv_heads"] * hd
    k = weights.predictor_k(d, arch["dsa"]["sigma"])
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


def frozen_leaf(key, path, shape, dtype, plant=None):
    name = path.rsplit("/", 1)[-1]
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if plant is not None:
        planted = frozen_planted(key, path, shape, plant)
        if planted is not None:
            return planted.astype(dtype)
    if path.endswith("dsa/p"):
        u = jax.random.uniform(key, shape)
        val = jnp.where(u < 1 / 6, -1.0, jnp.where(u < 2 / 6, 1.0, 0.0))
        return (val * math.sqrt(3.0 / shape[-1])).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(shape[-2])).astype(dtype)


def frozen_planted(key, path, shape, plant):
    if path == "embed":
        lo, hi = plant["marker_ids"]
        t = jnp.arange(shape[0])
        x = jax.random.normal(key, shape, jnp.float32)
        x = x.at[:, 0].set(((t >= lo) & (t < hi)).astype(jnp.float32))
        return x.at[:, 1].set(1.0)
    if path == "lm_head":
        lo, hi = plant["marker_ids"]
        return frozen_leaf(key, path, shape, jnp.float32).at[:, lo:hi].set(0.0)
    if path.endswith("attn/wo") or path.endswith("mlp/w2"):
        return frozen_leaf(key, path, shape, jnp.float32).at[..., :2].set(0.0)
    if path.endswith("dsa/p"):
        x = frozen_leaf(key, path, shape, jnp.float32)
        x = x.at[..., :2, :].set(0.0).at[..., :, :2].set(0.0)
        return x.at[..., 0, 0].set(weights.PLANT_GAIN).at[..., 1, 1].set(
            weights.PLANT_GAIN)
    if path.endswith("dsa/wq") or path.endswith("dsa/wk"):
        src = 1 if path.endswith("wq") else 0
        x = frozen_leaf(key, path, shape, jnp.float32).at[..., :2, :].set(0.0)
        return x.at[..., src, 0].set(1.0)
    return None


def frozen_make(arch, seed, plant):
    dtype = jnp.dtype(arch["param_dtype"])
    sh = frozen_shapes(arch)

    def build(key):
        out = {}
        for i, (p, s) in enumerate(sorted(sh.items())):
            node = out
            *head, last = p.split("/")
            for h in head:
                node = node.setdefault(h, {})
            node[last] = frozen_leaf(jax.random.fold_in(key, i), p, s, dtype,
                                     plant)
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    return jax.block_until_ready(jax.jit(build)(key))


# -- the pins -----------------------------------------------------------------

def test_bench_pins_arch_config():
    got, want = run.arch_config(ARCH), frozen_arch_config(ARCH)
    assert got == want and hash(got) == hash(want)


def test_bench_pins_layout_order():
    """The same paths and shapes, so the same sorted order and the same
    ``fold_in`` index for every leaf."""
    lay = weights.layout(ARCH)
    assert list(lay) == sorted(frozen_shapes(ARCH))
    assert {p: leaf.shape for p, leaf in lay.items()} == frozen_shapes(ARCH)
    assert {leaf.dtype for leaf in lay.values()} == {jnp.dtype("bfloat16")}


def _digests(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("plant", [PLANT, None], ids=["planted", "plain"])
def test_bench_pins_weights_bitwise(plant):
    """Published widths, two layers: every leaf bit for bit, planted
    leaves among them."""
    arch = dict(ARCH, n_layers=2)
    frozen = frozen_make(arch, 3600001501, plant)
    tree, want = jax.tree_util.tree_structure(frozen), _digests(frozen)
    del frozen
    got = weights.make(arch, 3600001501, plant)
    assert jax.tree_util.tree_structure(got) == tree
    assert _digests(got) == want


def _chat_specs(seed):
    return generator.generate(generator.load("chat"), 51, seed,
                              ARCH["vocab"], PLANT, ARCH["dsa"]["block_k"])


def test_bench_pins_schedule_and_prompts():
    h = hashlib.sha256()
    for seed in (3600001501, 2 ** 31 + 77):
        for s in _chat_specs(seed):
            h.update(s.prompt.tobytes())
            h.update(np.int64(s.n_new).tobytes())
            h.update(np.float64(s.arrival_s).tobytes())
    assert h.hexdigest() == ("4c0738377e420c947698ca3f706890a8"
                             "7f1c63ba8dc7762293214ad0529004f8")


def test_bench_pins_work_counts():
    specs = _chat_specs(5)

    def geo_of(plen):
        return reference.geometry(ARCH, MAX_LEN, plen)

    kv = [len(s.prompt) + i for s in specs for i in range(1, s.n_new)]
    plens = [len(s.prompt) for s in specs]
    d = work.decode_steps(ARCH, geo_of(1), kv, 1234)
    p = work.prefill(ARCH, geo_of, plens)
    dk = work.decode_kernel(ARCH, geo_of(1), kv)
    ck = work.chunk_kernel(ARCH, geo_of, plens)
    assert (d.flops, d.bytes) == (25754093568000.0, 7449074370560.0)
    assert (p.flops, p.bytes) == (224213757460480.0, 375726704640.0)
    assert (dk.flops, dk.bytes) == (669261496320.0, 670758666240.0)
    assert (ck.flops, ck.bytes) == (2338517811200.0, 41659924480.0)
    assert work.weight_bytes(ARCH) == 5490283520.0
    assert work.token_flops(ARCH) == 5489950720.0


TINY = dict(name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, vocab=512, rope_theta=1e4, norm_eps=1e-5,
            dtype="float32", param_dtype="float32",
            dsa=dict(sparsity=0.9, sigma=0.25, quant_bits=4, block_q=32,
                     block_k=32, min_blocks=1, local_blocks=1,
                     decode_local=64))


@pytest.mark.parametrize("precision,digest", [
    ("float32",
     "cfeddca765dcd264fdb1d7cf90939b738c57e18c8e3a4d300ec657ee1e0ac777"),
    ("fp8",
     "03f49ccc709daf0be06516c5b34706bdf79ff377853c1f6adf1bd1b548ded291")])
def test_bench_pins_reference_logits(precision, digest):
    w = weights.make(TINY, 5, {"marker_ids": [1, 33],
                               "block_markers": [3, 4, 6, 8, 11, 15, 20,
                                                 27]})
    rng = np.random.default_rng(0)
    prompt = rng.integers(33, 512, size=200).astype(np.int32)
    served = rng.integers(33, 512, size=12).astype(np.int32)
    lg = reference.served_logits(w, TINY, 512, prompt, served, 40,
                                 precision=precision)
    assert hashlib.sha256(lg.tobytes()).hexdigest() == digest
