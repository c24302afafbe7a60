"""A model the program serves on its normal path enters the benchmark by
data files alone.  Into a fresh root this test writes a reduced GQA
mixture-of-experts configuration (a nested ``moe`` block, ``moe_prefill``
``"dense"`` in its cell's serving settings), a reference module of its own
that imports the dense one's helpers, a cell and a traffic file; the
harness, unedited, sets up, serves, checks and reads the metrics of a
run.  The run is correct, and is not once the reference's expert combine
is altered."""
import json
from pathlib import Path

import pytest

from bench import run, work

ROOT = Path(__file__).resolve().parents[2]

ARCH = dict(name="tiny_moe", family="moe", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=512,
            rope_theta=1e4, norm_eps=1e-5, dtype="float32",
            param_dtype="float32",
            moe=dict(num_experts=4, top_k=2, d_ff_expert=64,
                     capacity_factor=8.0),
            dsa=dict(sparsity=0.9, sigma=0.25, quant_bits=4, block_q=32,
                     block_k=32, min_blocks=1, local_blocks=1,
                     decode_local=64))

REFERENCE = '''"""Plain reference of a GQA mixture-of-experts model: the dense
reference's blocks with a routed SwiGLU expert MLP in place of SwiGLU."""
import functools

import jax
import jax.numpy as jnp

from bench import reference as dense

geometry = dense.geometry


def experts(w, h, arch, mm):
    """Router logits over every expert, the top_k kept and softmaxed, the
    kept experts' SwiGLU outputs summed under those weights."""
    moe = arch["moe"]
    vals, ids = jax.lax.top_k(mm(h, w["router"]), moe["top_k"])
    gates = jax.nn.softmax(vals, -1)
    y = jnp.zeros(h.shape[:-1] + (w["w2"].shape[-1],), jnp.float32)
    for e in range(moe["num_experts"]):
        u = jax.nn.silu(mm(h, w["w1"][e])) * mm(h, w["w3"][e])
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
        y = y + g[:, None] * mm(u, w["w2"][e])
    return y


served_logits = functools.partial(dense.served_logits, mlp=experts)
'''

SPEC = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 3,
    "configs": [{"name": "tiny_moe", "source": "https://example.org/tiny",
                 "file": "bench/configs/tiny_moe.json", "reduced": [],
                 "why": "a GQA MoE at test size"}],
    "workloads": [{"name": "tiny_moe.short", "config": "tiny_moe",
                   "traffic": "short", "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": "tpot_p90_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock"},
        {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.25, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": []}

CELL = {"serving": {"max_len": 512, "slots": 2, "moe_prefill": "dense"},
        "plant": {"marker_ids": [1, 33],
                  "block_markers": [3, 4, 6, 8, 11, 15, 20, 27]},
        "check": {"sample": 64,
                  "limits": {"widest_gap": 0.05, "mean_gap": 0.005}}}

TRAFFIC = {"arrival": "poisson", "rate_rps": 2.0,
           "prompt": {"dist": "lognormal", "median": 180, "sigma": 0.2,
                      "min": 129, "max": 256},
           "output": {"dist": "lognormal", "median": 16, "sigma": 0.7,
                      "min": 4, "max": 40}}


class CpuDevice:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


def write_root(root: Path, reference_text: str) -> None:
    files = {
        "BENCHMARK.json": json.dumps(SPEC),
        "bench/configs/tiny_moe.json": json.dumps(
            {"arch": ARCH, "reference": "bench/reference_tiny_moe.py"}),
        "bench/reference_tiny_moe.py": reference_text,
        "bench/cells/tiny_moe.short.json": json.dumps(CELL),
        "bench/traffic/short.json": json.dumps(TRAFFIC)}
    for name, text in files.items():
        (root / name).parent.mkdir(parents=True, exist_ok=True)
        (root / name).write_text(text)


@pytest.mark.parametrize("combine", ["softmax", "altered"])
def test_bench_data_only_moe_cell(combine, tmp_path, monkeypatch):
    monkeypatch.setattr(work, "peak", lambda kind: {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})
    text = REFERENCE
    if combine == "altered":
        # every kept expert at an equal weight, not the router's softmax
        text = REFERENCE.replace("gates = jax.nn.softmax(vals, -1)",
                                 "gates = jnp.full_like(vals, 0.5)")
        assert text != REFERENCE
    write_root(tmp_path, text)
    c = run.load_cell("tiny_moe.short", root=tmp_path)
    assert c.reference.__file__ == str(tmp_path / "bench"
                                       / "reference_tiny_moe.py")
    cfg = run.arch_config(c.arch)
    assert cfg.family == "moe" and cfg.moe.num_experts == 4
    out = run.run_cell(c, 21, 3.0, False, run.CompileLog(), [CpuDevice()])
    assert out["attempted"] == 6 and out["failed"] == 0
    assert set(out["metrics"]) == {"tpot_p90_ms", "output_tok_s", "setup_s"}
    assert out["correct"] is (combine == "softmax"), out["compared"]


def test_bench_data_only_moe_work_counts_active_experts():
    """A routed expert counts at top_k of num_experts in FLOPs and whole in
    weight bytes."""
    d, fe, e, k, n = 64, 64, 4, 2, 2
    dense = dict(ARCH)
    dense.pop("moe")
    dense["family"] = "dense"
    expert = 3 * d * fe                      # w1, w3, w2 of one expert
    router = d * e
    mlp_dense = 3 * d * 128
    assert (work._matmul_params(ARCH) - work._matmul_params(dense)
            == n * (k * expert + router - mlp_dense))
    assert (work.weight_bytes(ARCH) - work.weight_bytes(dense)
            == 4 * n * (e * expert + router - mlp_dense))


@pytest.mark.parametrize("bad,key", [
    ({"n_layer": 2}, "arch.n_layer"),
    ({"moe": {"num_experts": 4, "topk": 2}}, "arch.moe.topk")])
def test_bench_data_only_unknown_key_names_itself(bad, key):
    with pytest.raises(SystemExit, match=key):
        run.arch_config(dict(ARCH, **bad))


def test_bench_data_only_arch_config_fields():
    """Nested blocks become their dataclasses, lists tuples, and the family
    defaults to dense; the config hashes (a static argument of jit)."""
    from repro.configs.base import MoEConfig
    cfg = run.arch_config(dict(ARCH, moe=dict(ARCH["moe"], layer_period=1)))
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe.top_k == 2
    assert cfg.dsa.enabled and cfg.dsa.block_k == 32
    hash(cfg)
    assert run._tuples([1, [2, 3]]) == (1, (2, 3))
    plain = dict(ARCH)
    plain.pop("family")
    assert run.arch_config(plain).family == "dense"
    with pytest.raises(SystemExit, match="decode window"):
        run.arch_config(dict(ARCH, dsa=dict(ARCH["dsa"], decode_local=32)))
