"""Record ``tpu_spans.xplane.pb`` and ``tpu_spans.scopes.json`` beside
this file: a profiler trace of ``ContinuousEngine.serve`` on one TPU v5e
and the stage maps (``bench.spans.program_scopes``) of the programs it ran.

    python3 bench/tests/data/record_tpu_spans.py

The engine is the ``stablelm_3b.chat`` cell's (paged bf16 cache, DSA
kernel mode, 4 slots at max_len 2048) with 2 of its 32 layers, so the
trace stays small: two requests at t=0 and one more while they decode,
a few decode segments and two admissions.  Prints what ``trace_reduce``
and ``spans.reduce`` read from it.
"""
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import jax
    import numpy as np

    from bench import run, spans, trace_reduce, weights
    from repro.inference.scheduler import Request
    if jax.devices()[0].platform != "tpu":
        print("record_tpu_spans: needs a TPU", file=sys.stderr)
        return 2
    # compile this program: a persistent cache keys programs without their
    # op metadata, so it could hand back another version's stage names
    jax.config.update("jax_enable_compilation_cache", False)
    arch = json.loads((ROOT / "bench" / "configs" / "stablelm_3b.json")
                      .read_text())["arch"]
    arch = dict(arch, n_layers=2)
    cfg = run.arch_config(arch)
    eng = run.build_engine(cfg, weights.make(arch, 13, None),
                           {"max_len": 2048, "slots": 4})
    eng.warmup([1024])
    run.warm_page_zeroing(eng, 1024 + 64)
    scopes = spans.program_scopes(eng, 1024)
    rng = np.random.default_rng(13)
    reqs = [Request(i, rng.integers(2, arch["vocab"], size=(n,)).astype(
        np.int32), m, arrival_s=t)
        for i, (n, m, t) in enumerate([(520, 20, 0.0), (600, 18, 0.0),
                                       (560, 17, 0.06)])]
    out = ROOT / ".bench_trace" / "record_tpu_spans"   # gitignored
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    results = eng.serve(reqs)
    jax.profiler.stop_trace()
    assert [r.status for r in results] == ["ok"] * len(reqs)
    trace = sorted(out.glob("**/*.xplane.pb"))[-1]
    shutil.copy(trace, HERE / "tpu_spans.xplane.pb")
    (HERE / "tpu_spans.scopes.json").write_text(
        json.dumps(scopes, indent=0, sort_keys=True) + "\n")
    red = trace_reduce.reduce(str(trace))
    sp = spans.reduce(str(trace), scopes)
    print(json.dumps({k: red[k] for k in ("window_s", "busy_s", "program_s",
                                          "program_runs")}))
    print(json.dumps(sp))
    print(json.dumps(spans.shares(sp)))
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
