"""bench/spans.py: idle time credited to the scheduler's host spans and
device time to the model step's named stages, the readers of the
admission stamps, and both on traces recorded on one TPU v5e:
``tpu_spans.xplane.pb`` (``data/record_tpu_spans.py``: a 2-layer
``stablelm_3b`` engine serving three requests, with the stage maps of its
programs in ``tpu_spans.scopes.json``) and ``tpu_small.xplane.pb`` (a
program with no spans or scopes)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run, spans, trace_reduce

DATA = Path(__file__).parent / "data"
SPANS_TRACE = DATA / "tpu_spans.xplane.pb"
SMALL_TRACE = DATA / "tpu_small.xplane.pb"


@pytest.mark.parametrize("op_name,want", [
    ("jit(_segment_fn)/while/body/closed_call/qkv/dot_general", "qkv"),
    ("jit(f)/attend/jit(dsa_decode_paged)/dsa_decode_paged/pallas_call",
     "attend"),
    ("jit(f)/mlp/attend/add", "attend"),          # the innermost stage
    ("jit(_segment_fn)/while/body/dynamic_update_slice", "(no scope)"),
    ("k", "(no scope)")])
def test_bench_spans_stage_of(op_name, want):
    assert spans.stage_of(op_name) == want


def test_bench_spans_scope_map():
    a = "\n".join([
        'ENTRY %main.1 (p: f32[4]) -> f32[4] {',
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(f)/while/body/mlp/mul"}',
        '  ROOT %dsa_decode.1 = f32[4]{0} custom-call(%fusion.3), '
        'metadata={op_name="jit(f)/attend/jit(dsa_decode)/pallas_call"}',
        '  %copy.7 = f32[4]{0} copy(%p)',
        '  %fusion.9 = f32[4]{0} fusion(%p), metadata={op_name="jit(f)/x"}',
        '}'])
    assert spans.scope_map([a]) == {
        "fusion.3": "mlp", "fusion.3 = f32[4]{0}": "mlp",
        "dsa_decode.1": "attend", "dsa_decode.1 = f32[4]{0}": "attend"}
    # another variant (another width) stages fusion.3 otherwise: its
    # name alone no longer tells, its name and shape do
    b = a.replace("f32[4]{0} fusion(%p), kind=kLoop, "
                  'metadata={op_name="jit(f)/while/body/mlp/mul"}',
                  "f32[8]{0} fusion(%p), kind=kLoop, "
                  'metadata={op_name="jit(f)/qkv/mul"}')
    both = spans.scope_map([a, b])
    assert "fusion.3" not in both and both["dsa_decode.1"] == "attend"
    assert spans.stage_at(both, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} "
                          "%p), kind=kLoop") == "qkv"
    assert spans.stage_at(both, "%fusion.3 = f32[4]{0} fusion(f32[4]{0} "
                          "%p), kind=kLoop") == "mlp"
    assert spans.stage_at(both, "%copy.7 = f32[4]{0} copy(f32[4]{0} %p)") \
        == "(no scope)"
    assert spans.instruction("%fusion.3 = f32[4]{0} fusion(%p)") \
        == "fusion.3"


NESTED = [(0, 100, "serve.segment"), (10, 20, "serve.segment.dispatch"),
          (30, 90, "serve.segment.wait"), (150, 200, "serve.admit"),
          (160, 170, "serve.admit.staging")]


def test_bench_spans_innermost():
    assert spans.innermost(NESTED) == [
        (0, 10, "serve.segment"), (10, 20, "serve.segment.dispatch"),
        (20, 30, "serve.segment"), (30, 90, "serve.segment.wait"),
        (90, 100, "serve.segment"), (100, 150, None),
        (150, 160, "serve.admit"), (160, 170, "serve.admit.staging"),
        (170, 200, "serve.admit")]
    # a child that overruns its parent is cut at the parent's end
    assert spans.innermost([(0, 10, "a"), (5, 20, "b")]) == [
        (0, 5, "a"), (5, 10, "b")]


def test_bench_spans_credit():
    gaps = [(5, 15), (95, 155), (165, 300)]
    got = spans._credit(gaps, spans.innermost(NESTED))
    assert dict(got) == {"serve.segment": 10, "serve.segment.dispatch": 5,
                         "(no span)": 50 + 100, "serve.admit": 5 + 30,
                         "serve.admit.staging": 5}
    assert sum(got.values()) == sum(e - s for s, e in gaps)


def test_bench_spans_shares():
    red = {"window_s": 2.0,
           "idle_by_span": {"serve.admit.staging": 0.2, "serve.admit": 0.1,
                            "serve.segment.dispatch": 0.05,
                            "serve.segment.emit": 0.03,
                            "serve.segment.wait": 0.5, "(no span)": 0.02},
           "stage_s": {"_segment_fn": {"dsa_predict": 0.1,
                                       "dsa_select": 0.3, "mlp": 0.4,
                                       "(no scope)": 0.2}}}
    got = spans.shares(red)
    assert got["idle_admit_share"] == pytest.approx(15.0)
    assert got["idle_segment_host_share"] == pytest.approx(4.0)
    assert got["dsa_select_share"] == pytest.approx(40.0)
    # a program without spans or stages reads nothing
    bare = {"window_s": 2.0, "idle_by_span": {"(no span)": 0.6},
            "stage_s": {"_segment_fn": {"(no scope)": 1.0}}}
    assert spans.shares(bare) == {"idle_admit_share": None,
                                  "idle_segment_host_share": None,
                                  "dsa_select_share": None}


def _results(stamps):
    return [SimpleNamespace(status="ok", n_new=4, arrival_s=a, admit_s=b,
                            first_token_s=c, finish_s=c + 1.0)
            for a, b, c in stamps]


@pytest.mark.parametrize("metric,want", [("queue_wait_p50_ms", 200.0),
                                         ("prefill_p50_ms", 300.0)])
def test_bench_spans_admission_readers(metric, want):
    run_ = SimpleNamespace(log=lambda msg: None, results=_results(
        [(0.0, 0.1, 0.3), (1.0, 1.2, 1.5), (2.0, 2.5, 3.0)]))
    assert run.reader(metric)(run_) == pytest.approx(want)
    # admission stamped with the first token (no admission stamp): none
    run_.results = _results([(0.0, 0.4, 0.4), (1.0, 1.5, 1.5)])
    assert run.reader(metric)(run_) is None


@pytest.fixture(scope="module")
def recorded():
    scopes = json.loads((DATA / "tpu_spans.scopes.json").read_text())
    return (spans.reduce(str(SPANS_TRACE), scopes),
            trace_reduce.reduce(str(SPANS_TRACE)))


def test_bench_spans_idle_accounts_for_window(recorded):
    sp, red = recorded
    assert sp["window_s"] == pytest.approx(red["window_s"], rel=1e-12)
    assert sp["busy_s"] == pytest.approx(red["busy_s"], rel=1e-12)
    idle = sum(sp["idle_by_span"].values())
    assert abs(idle - (red["window_s"] - red["busy_s"])) < 1e-6
    # the serving loop's phases hold the idle time
    for name in ("serve.segment.wait", "serve.admit.staging",
                 "serve.chunk_burst"):
        assert name in sp["idle_by_span"], sorted(sp["idle_by_span"])
    assert sp["idle_by_span"].get("(no span)", 0.0) < 0.1 * idle


def test_bench_spans_stages_account_for_programs(recorded):
    sp, red = recorded
    for prog in ("_segment_fn", "_chunk_fn"):
        st = sp["stage_s"][prog]
        assert sum(st.values()) == pytest.approx(red["program_s"][prog],
                                                 rel=0.01)
        assert set(spans.STAGES) <= set(st), (prog, sorted(st))
    got = spans.shares(sp)
    assert all(0.0 < v < 100.0 for v in got.values()), got


def test_bench_spans_program_without_spans():
    """The trace of a program with no spans or scopes: all idle is
    ``(no span)``, all device time ``(no scope)``, and no share reads."""
    sp = spans.reduce(str(SMALL_TRACE))
    red = trace_reduce.reduce(str(SMALL_TRACE))
    assert list(sp["idle_by_span"]) == ["(no span)"]
    assert sp["idle_by_span"]["(no span)"] == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)
    assert list(sp["stage_s"]["step"]) == ["(no scope)"]
    assert set(spans.shares(sp).values()) == {None}
