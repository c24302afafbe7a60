"""The comparison that decides ``correct``, at a size a test run holds.

A tiny float32 configuration, planted as the cells are, served through
the real engine (DSA kernel mode, interpreted off the chip) matches the
plain reference token for token, prompt and decode; at the benchmark's own
precision (bf16) the program's served tokens pass and the control (the
reference in fp8, the precision below bf16) fails; and a whole run comes
out ``correct: false`` with the first token altered where the engine
samples it, or with the decode step's block selection inverted."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import check, reference, run, weights, work

ROOT = Path(__file__).resolve().parents[2]
ARCH = dict(name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, vocab=512, rope_theta=1e4, norm_eps=1e-5,
            dtype="float32", param_dtype="float32",
            dsa=dict(sparsity=0.9, sigma=0.25, quant_bits=4, block_q=32,
                     block_k=32, min_blocks=1, local_blocks=1,
                     decode_local=64))
# 4-8 whole blocks of 32 rows a prompt; decode keeps 5 of up to 10 blocks
PLANT = {"marker_ids": [1, 33], "block_markers": [3, 4, 6, 8, 11, 15, 20, 27]}
MIX = {"arrival": "poisson", "rate_rps": 2.0,
       "prompt": {"dist": "lognormal", "median": 180, "sigma": 0.2,
                  "min": 129, "max": 256},
       "output": {"dist": "lognormal", "median": 16, "sigma": 0.7, "min": 4,
                  "max": 40}}
SERVING = {"max_len": 512, "slots": 2}
# float32 against float32: only summation order differs
LIMITS = {"widest_gap": 0.05, "mean_gap": 0.005}
# bf16 program against the float32 reference at this size: sound runs read
# widest gaps of 0-0.0076 and mean gaps of 0-0.00013, the fp8 control
# 0.13-0.31 and 0.0027-0.0086 (seeds 1-6, CPU)
LIMITS_BF16 = {"widest_gap": 0.05, "mean_gap": 0.001}


class CpuDevice:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


def cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return SimpleNamespace(
        name="stablelm_3b.chat", chips=1, arch=ARCH, mix=MIX,
        reference=reference,
        cell={"serving": SERVING, "plant": PLANT,
              "check": {"sample": 64, "limits": LIMITS}},
        end_to_end=[m for m in spec["end_to_end"] if "stablelm_3b.chat"
                    in m.get("workloads", ["stablelm_3b.chat"])],
        per_layer=[])


@pytest.fixture
def tpu_peaks(monkeypatch):
    monkeypatch.setattr(work, "peak", lambda kind: {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})


def serve(seed, arch=ARCH, mix=MIX, slots=2):
    from bench import generator
    w = weights.make(arch, seed, PLANT)
    eng = run.build_engine(run.arch_config(arch), w,
                           dict(SERVING, slots=slots))
    specs = generator.generate(mix, 3, seed, arch["vocab"], PLANT,
                               arch["dsa"]["block_k"])
    return w, specs, eng.serve(run.requests(specs))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_bench_correct_reference_matches_program(seed):
    w, specs, results = serve(seed)
    prompts = {s.rid: s.prompt for s in specs}
    for r in results:
        assert r.status == "ok"
        lg = reference.served_logits(w, ARCH, SERVING["max_len"],
                                     prompts[r.rid], r.tokens, 40)
        np.testing.assert_array_equal(lg.argmax(-1), r.tokens)
    picked = check.sample(results, 64, seed)
    assert len(picked) == len(results)
    assert picked[0].prompt_len == max(r.prompt_len for r in results)
    sound = check.readings(w, ARCH, SERVING["max_len"], 40, prompts, picked)
    assert sound["widest_gap"] == 0.0
    assert sound["tokens"] == sum(r.n_new for r in results)
    assert check.judge(picked, ARCH["vocab"], sound, LIMITS)


@pytest.mark.parametrize("seed", [1, 2])
def test_bench_correct_control_fails(seed):
    arch = dict(ARCH, dtype="bfloat16", param_dtype="bfloat16")
    mix = dict(MIX, rate_rps=8.0, output=dict(MIX["output"], median=4,
                                              min=2, max=8))
    w, specs, results = serve(seed, arch, mix, slots=4)
    prompts = {s.rid: s.prompt for s in specs}
    picked = check.sample(results, 64, seed)
    sound = check.readings(w, arch, SERVING["max_len"], 8, prompts, picked)
    control = check.readings(w, arch, SERVING["max_len"], 8, prompts,
                             picked, precision="fp8")
    assert check.judge(picked, arch["vocab"], sound, LIMITS_BF16)
    assert not check.judge(picked, arch["vocab"], control, LIMITS_BF16)


@pytest.mark.parametrize("fault", [None, "token", "selection"])
def test_bench_correct_run_with_altered_tokens(fault, monkeypatch,
                                               tpu_peaks):
    from repro.core import masks
    build = run.build_engine
    topk = masks.decode_block_topk_indices

    def inverted(block_scores, *a, **k):
        return topk(-block_scores, *a, **k)

    def broken(cfg, w, serving):
        eng = build(cfg, w, serving)
        sample = eng._sample_tok0

        def altered(last_row, req):
            tok0, key = sample(last_row, req)
            return (tok0 + 1) % ARCH["vocab"], key
        eng._sample_tok0 = altered
        return eng

    if fault == "token":
        monkeypatch.setattr(run, "build_engine", broken)
    if fault == "selection":
        monkeypatch.setattr(masks, "decode_block_topk_indices", inverted)
    clog = run.CompileLog()
    out = run.run_cell(cell(), 11, 3.0, False, clog, [CpuDevice()])
    assert out["correct"] is (fault is None)
    assert out["attempted"] == 6 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"ttft_p50_ms", "tpot_p90_ms",
                                   "setup_s"}


@pytest.mark.parametrize("fault", [None, "token"])
def test_bench_correct_batch_run_with_altered_token(fault, monkeypatch,
                                                   tpu_peaks):
    """A batch cell's shape of run (every request at t=0, more requests
    than slots, output_tok_s): correct, and not once the first
    token is altered where the engine samples it."""
    build = run.build_engine

    def broken(cfg, w, serving):
        eng = build(cfg, w, serving)
        sample = eng._sample_tok0

        def altered(last_row, req):
            tok0, key = sample(last_row, req)
            return (tok0 + 1) % ARCH["vocab"], key
        eng._sample_tok0 = altered
        return eng

    if fault == "token":
        monkeypatch.setattr(run, "build_engine", broken)
    c = cell()
    c.mix = dict(MIX, arrival="batch", rate_rps=3.0)
    c.cell = dict(c.cell, serving=dict(SERVING, slots=4))
    c.end_to_end = [{"name": m, "unit": "-"} for m in
                    ("output_tok_s", "tpot_p90_ms", "setup_s")]
    out = run.run_cell(c, 12, 3.0, False, run.CompileLog(), [CpuDevice()])
    assert out["correct"] is (fault is None)
    assert out["attempted"] == 9 and out["failed"] == 0
    assert set(out["metrics"]) == {"output_tok_s", "tpot_p90_ms", "setup_s"}
