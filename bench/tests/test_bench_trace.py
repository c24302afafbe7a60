"""trace_reduce on a small trace recorded on one TPU v5e: three runs of a
jitted step holding one Pallas kernel (dsa_decode), counted by hand from
the events of its device plane.  Busy and idle time are measured from the
first device operation to the end of the last."""
from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "tpu_small.xplane.pb"

# profile_stop_time - profile_start_time of the Task Environment plane
PROFILE_NS = 1792243644964972735 - 1792243644674673542
# the first op's start, and the last op's end (63097431 + 2537)
FIRST_NS, LAST_NS = 41012789, 63097431 + 2537
WINDOW_NS = LAST_NS - FIRST_NS
# the three 'jit_step' module events
PROGRAM_NS = 26078 + 26091 + 26086
# the three '%dsa_decode.1' custom calls
KERNEL_NS = 23502 + 23514 + 23510
# union of the op intervals of each run: ops that touch merge
BUSY_NS = (19 + 5 + 23505 + 2539) + (13 + 3 + 1 + 3 + 23514 + 3 + 2537) + (
    13 + 4 + 1 + 2 + 23512 + 2537)
# the idle gaps between the runs: a run's last op end to the next run's
# first op start
GAP_1_2_NS = 52091508 - (41036321 + 2539)
GAP_2_3_NS = 63073891 - (52115054 + 2537)


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(str(TRACE))


def test_bench_trace_window_and_busy(red):
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(WINDOW_NS * 1e-9, rel=1e-12)
    assert red["profile_s"] == pytest.approx(PROFILE_NS * 1e-9, rel=1e-12)
    # the stretch before the first device event is outside the window;
    # the first program run starts with it
    assert red["lead_s"] == pytest.approx(FIRST_NS * 1e-9, rel=1e-12)
    assert red["first_program_s"] == pytest.approx(41012782e-9, rel=1e-12)
    assert red["busy_s"] == pytest.approx(BUSY_NS * 1e-9, rel=1e-12)


def test_bench_trace_programs_and_kernels(red):
    assert red["program_runs"] == {"step": 3}
    assert red["program_s"]["step"] == pytest.approx(PROGRAM_NS * 1e-9,
                                                     rel=1e-12)
    assert red["kernel_s"] == {"step": pytest.approx(KERNEL_NS * 1e-9,
                                                     rel=1e-12)}


def test_bench_trace_breakdown(red):
    ops = dict(red["device_ops"])
    assert next(iter(ops)) == "dsa_decode"
    assert ops["dsa_decode"] == pytest.approx(KERNEL_NS * 1e-9, rel=1e-12)
    assert ops["fusion"] == pytest.approx((2539 + 2537 + 2537) * 1e-9,
                                          rel=1e-12)
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps[0] == pytest.approx(GAP_1_2_NS * 1e-9, rel=1e-12)
    assert gaps[1] == pytest.approx(GAP_2_3_NS * 1e-9, rel=1e-12)
    # no gap reaches outside the window
    assert sum(gaps) <= (WINDOW_NS - BUSY_NS) * 1e-9 * (1 + 1e-12)
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10


@pytest.mark.parametrize("name,want", [
    ("jit__segment_fn(123)", "_segment_fn"), ("jit_step(99)", "step"),
    ("plain", "plain")])
def test_bench_trace_program_name(name, want):
    assert trace_reduce.program_name(name) == want


@pytest.mark.parametrize("name,want", [
    ("%dsa_decode.1 = bf16[4] custom-call(x)", "dsa_decode"),
    ("%fusion.12 = f32[] fusion(y)", "fusion"),
    ("%copy-start = (bf16[2]) copy-start(z)", "copy-start")])
def test_bench_trace_op_name(name, want):
    assert trace_reduce.op_name(name) == want
