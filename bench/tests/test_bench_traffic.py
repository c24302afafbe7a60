"""The traffic generator, the planted prompts and weights, and the latency
arithmetic."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import generator, reference, stats, weights

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def cell(name):
    w = {x["name"]: x for x in SPEC["workloads"]}[name]
    arch = json.loads((ROOT / "bench" / "configs" / f"{w['config']}.json")
                      .read_text())["arch"]
    c = json.loads((ROOT / "bench" / "cells" / f"{name}.json").read_text())
    return arch, generator.load(w["traffic"]), c


def generate(name, seconds, seed):
    arch, mix, c = cell(name)
    return generator.generate(mix, seconds, seed, arch["vocab"],
                              c.get("plant"), arch["dsa"]["block_k"])


@pytest.mark.parametrize("name", CELLS)
def test_bench_traffic_same_seed_same_requests(name):
    a = generate(name, 20, 2 ** 31 + 77)
    b = generate(name, 20, 2 ** 31 + 77)
    c = generate(name, 20, 5)
    for x, y, z in zip(a, b, c):
        assert x.n_new == y.n_new and x.arrival_s == y.arrival_s
        np.testing.assert_array_equal(x.prompt, y.prompt)
        # another seed: the same schedule, other tokens
        assert (x.n_new, len(x.prompt), x.arrival_s) == (
            z.n_new, len(z.prompt), z.arrival_s)
        assert not np.array_equal(x.prompt, z.prompt)
    # the schedule is shuffled, not sorted by length
    assert [len(x.prompt) for x in a] != sorted(len(x.prompt) for x in a)


@pytest.mark.parametrize("name", CELLS)
def test_bench_traffic_lengths_bucket_and_max_len(name):
    arch, mix, c = cell(name)
    max_len = c["serving"]["max_len"]
    reqs = generate(name, 51, 3)
    buckets = {reference.geometry(arch, max_len, len(r.prompt))["bucket"]
               for r in reqs}
    assert len(buckets) == 1
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.n_new <= mix["output"]["max"]
        assert len(r.prompt) + r.n_new <= max_len
        assert r.prompt.min() >= 1 and r.prompt.max() < arch["vocab"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= max_len


PLANTED = [n for n in CELLS if "plant" in cell(n)[2]]


@pytest.mark.parametrize("seed", [0, 17, 2 ** 31 + 3])
@pytest.mark.parametrize("name", PLANTED)
def test_bench_traffic_planted_blocks(name, seed):
    arch, mix, c = cell(name)
    lo, hi = c["plant"]["marker_ids"]
    ladder = c["plant"]["block_markers"]
    bk = arch["dsa"]["block_k"]
    # the counts climb by a factor of 1.3 or more, far beyond bf16 rounding
    assert all(b >= 1.3 * a for a, b in zip(ladder, ladder[1:]))
    assert len(ladder) >= mix["prompt"]["max"] // bk and ladder[-1] <= bk
    for r in generate(name, 51, seed):
        marker = (r.prompt >= lo) & (r.prompt < hi)
        n_full = len(r.prompt) // bk
        counts = [int(marker[b * bk:(b + 1) * bk].sum())
                  for b in range(n_full)]
        assert sorted(counts) == ladder[:n_full]
        assert not marker[n_full * bk:].any()
        assert r.prompt.min() >= 1


def test_bench_traffic_planted_weights():
    """The planted leaves of a tiny tree, as ``bench/weights.py`` states."""
    arch = dict(name="tiny", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=128, vocab=512,
                dtype="float32", param_dtype="float32",
                dsa=dict(sigma=0.25, decode_local=64))
    w = weights.make(arch, 4, {"marker_ids": [1, 33]})
    emb = np.asarray(w["embed"])
    np.testing.assert_array_equal(emb[:, 0], (np.arange(512) >= 1)
                                  & (np.arange(512) < 33))
    assert (emb[:, 1] == 1).all()
    assert (np.asarray(w["lm_head"])[:, 1:33] == 0).all()
    g = w["groups"]["b0"]
    for leaf in (g["attn"]["wo"], g["mlp"]["w2"]):
        assert (np.asarray(leaf)[..., :2] == 0).all()
    p = np.asarray(g["attn"]["dsa"]["p"])
    assert (p[:, 0, 0] == weights.PLANT_GAIN).all()
    assert (p[:, 1, 1] == weights.PLANT_GAIN).all()
    assert (p[:, 0, 1:] == 0).all() and (p[:, 2:, :2] == 0).all()
    for name, src in (("wq", 1), ("wk", 0)):
        x = np.asarray(g["attn"]["dsa"][name])[:, :2]
        want = np.zeros_like(x)
        want[:, src, 0] = 1.0
        np.testing.assert_array_equal(x, want)


def test_bench_traffic_poisson_arrivals():
    mix = dict(generator.load("chat"), rate_rps=2.0)
    reqs = generator.generate(mix, 30, 9, 50304)
    t = np.array([r.arrival_s for r in reqs])
    assert len(reqs) == 60 and t[0] == 0.0 and (np.diff(t) >= 0).all()
    # the gaps are the exponential's 60 stratified quantiles at 2 req/s
    g = np.sort(np.diff(t))
    q = np.sort(generator.gaps(mix, 60))
    assert np.isin(np.round(g, 9), np.round(q, 9)).all()


@pytest.mark.parametrize("rate,seconds,n", [(10.0, 51, 510), (0.1, 40, 4),
                                            (2.5, 3, 8)])
def test_bench_traffic_batch_count(rate, seconds, n):
    """A batch mix sends round(rate_rps * seconds) requests, all at t=0."""
    mix = dict(generator.load("chat"), arrival="batch", rate_rps=rate)
    reqs = generator.generate(mix, seconds, 2 ** 31 + 9, 50304)
    assert len(reqs) == n
    assert all(r.arrival_s == 0.0 for r in reqs)


GRADED = {"marker_ids": [1, 16],
          "graded": {"levels": [round(2.0 * 1.12 ** g, 4) for g in range(15)],
                     "rows": 8}}


@pytest.mark.parametrize("plen", [16000, 1000, 200])
def test_bench_traffic_graded_blocks(plen):
    """Up to one graded block per level, never the first, each with its
    rows of one marker id; no two blocks share a level; the rest, and the
    partial last block, hold no marker."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(16, 50304, size=plen).astype(np.int32)
    generator.plant(tokens, rng, GRADED, 128)
    marker = (tokens >= 1) & (tokens < 16)
    n_full = plen // 128
    ids = []
    for b in range(n_full):
        rows = tokens[b * 128:(b + 1) * 128][marker[b * 128:(b + 1) * 128]]
        if len(rows):
            assert b > 0 and len(rows) == 8 and len(set(rows)) == 1
            ids.append(int(rows[0]))
    assert len(ids) == min(15, n_full - 1) == len(set(ids))
    assert not marker[n_full * 128:].any()


def test_bench_traffic_graded_weights():
    """Marker id lo + g carries channel-0 level levels[g]; every leaf whose
    last logical axis is the residual (here attention ``wo``, the experts'
    ``w2``) leaves channels 0 and 1 alone."""
    arch = dict(name="tiny", family="moe", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=512,
                dtype="float32", param_dtype="float32",
                moe=dict(num_experts=4, top_k=2, d_ff_expert=32),
                dsa=dict(sigma=0.25, decode_local=64))
    w = weights.make(arch, 5, GRADED)
    emb = np.asarray(w["embed"])
    levels = np.zeros(512, np.float32)
    levels[1:16] = GRADED["graded"]["levels"]
    np.testing.assert_array_equal(emb[:, 0], levels)
    assert (emb[:, 1] == 1).all()
    g = w["groups"]["b0"]
    for leaf in (g["attn"]["wo"], g["mlp"]["w2"]):
        assert (np.asarray(leaf)[..., :2] == 0).all()
        assert (np.asarray(leaf)[..., 2:] != 0).any()
    assert (np.asarray(g["mlp"]["router"])[:2] != 0).any()
    with pytest.raises(ValueError, match="levels"):
        weights.make(arch, 5, dict(GRADED, marker_ids=[1, 9]))


def result(status="ok", n_new=5, arrival=0.0, admit=1.0, first=1.5,
           finish=3.5):
    return SimpleNamespace(status=status, n_new=n_new, arrival_s=arrival,
                           admit_s=admit, first_token_s=first,
                           finish_s=finish)


def test_bench_stats_on_hand_made_results():
    rs = [result(),                                        # tpot 0.5
          result(n_new=3, arrival=1.0, admit=1.2, first=2.0, finish=2.4),
          result(n_new=1, arrival=2.0, admit=2.1, first=2.5, finish=2.5),
          result(status="failed", finish=9.0)]
    assert stats.ttft_s(rs) == [1.5, 1.0, 0.5]
    assert stats.tpot_s(rs) == pytest.approx([0.5, 0.2])
    assert stats.percentile([3, 1, 2, 4], 50) == 2.5
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == \
        pytest.approx(9.1)
    assert stats.percentile([], 90) is None
