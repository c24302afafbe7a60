"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric is found by name, and each configuration's weight
layout is the serving program's at full width."""
import json
import re
from pathlib import Path

import jax
import pytest

from bench import run, weights

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_bench_cells_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for entry in SPEC["configs"] + SPEC["workloads"] + METRICS:
        assert NAME.match(entry["name"])
        for k in ("why", "layer", "source"):
            if k in entry:
                assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
    for m in METRICS:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_bench_cells_files_and_metrics(w):
    c = run.load_cell(w["name"])
    assert c.cell["check"]["limits"] and c.cell["serving"]["max_len"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_bench_cells_metric_reader(m):
    assert NAME.match(m["name"])
    assert callable(run.reader(m["name"]))
    for cell in m.get("workloads", []):
        assert cell in {w["name"] for w in SPEC["workloads"]}


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_bench_cells_config_layout(c):
    from repro.models.transformer import init_model
    arch = json.loads((ROOT / c["file"]).read_text())["arch"]
    cfg = run.arch_config(arch)
    program = jax.eval_shape(lambda k: init_model(k, cfg)[0],
                             jax.random.PRNGKey(0))
    flat = {"/".join(str(getattr(p, "key", p)) for p in path):
            (leaf.shape, leaf.dtype)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                program)[0]}
    assert flat == {p: (leaf.shape, leaf.dtype)
                    for p, leaf in weights.layout(arch).items()}
