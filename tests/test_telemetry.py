"""Serving telemetry: the ``Telemetry`` subsystem's four layers.

 - ``telemetry=None`` (the ServingConfig default) is BITWISE-INERT:
   tokens from a fully-instrumented engine equal the untraced engine's.
 - Host spans (``telemetry.span``) land in a ``jax.profiler`` trace as
   ``TraceAnnotation`` events and, with a Telemetry bound, in its ring at
   the same instant: both read ``time.time_ns``, the profiler's clock.
 - Request spans + the engine's ``serve.*`` phase spans export as Chrome
   trace-event JSON with the segment / chunk / admission / retirement
   timeline intact and nested, on the profiler's clock.
 - The metrics registry exports Prometheus text that agrees with
   ``summarize()`` and ``health()`` by construction (same feed paths).
 - The compile watcher turns the documented recompilation contract into
   a live assertion: ONE parametrized test drives the dense / paged /
   quantized / speculative engines through warmup + mixed traffic and
   pins the fixed compile set (replacing the ad-hoc compile-once
   checks).
 - The sampled DSA sparsity probe reports per-slot keep rates in (0, 1]
   without changing tokens.
 - ``ContinuousEngine.reset()`` resets the registry (health/metrics
   zeroed) but KEEPS the compile log.
"""
import json
import time
from collections import Counter as TallyCounter

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.inference.config import ServingConfig
from repro.inference.scheduler import ContinuousEngine, Request, summarize
from repro.inference.telemetry import (MetricsRegistry, Telemetry,
                                       _signature, span)
from repro.models.transformer import init_model

MAX_LEN = 96
SHAPES = [(20, 5), (40, 6), (25, 3), (33, 8), (18, 2)]


@pytest.fixture(scope="module")
def dense(rng):
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    return cfg, params


@pytest.fixture(scope="module")
def dsa(rng):
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    return cfg, params


def _mk_requests(vocab, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(1, vocab - 4, size=(l,)).astype(
        np.int32), n, greedy=True, seed=rid * 7 + 1)
        for rid, (l, n) in enumerate(shapes)]


# -- metrics registry / prometheus -------------------------------------------


def test_registry_prometheus_exposition():
    m = MetricsRegistry()
    m.counter("reqs_total", status="ok").inc(3)
    m.counter("reqs_total", status="failed").inc()
    m.gauge("queue_depth").set(7)
    h = m.histogram("lat_seconds", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    assert m.value("reqs_total", status="ok") == 3.0
    assert m.value("queue_depth") == 7.0
    assert m.value("lat_seconds") == (3, pytest.approx(2.55 / 3))
    assert m.value("never_touched") == 0.0
    text = m.to_prometheus()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{status="ok"} 3.0' in text
    assert "# TYPE lat_seconds histogram" in text
    # cumulative bucket semantics + +Inf == count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    m.reset()
    assert m.to_prometheus().strip() == ""


def test_compile_watch_signature_and_passthrough():
    tel = Telemetry()
    calls = []
    fn = lambda *a, **k: calls.append((a, k)) or 42
    fn._cache_size = lambda: 1
    w = tel.wrap_jit("prog", fn)
    a32 = np.zeros((2, 3), np.int32)
    assert w(a32, flag=True) == 42 and w(a32, flag=True) == 42
    assert tel.compile_count("prog") == 1          # same signature: once
    w(np.zeros((2, 4), np.int32), flag=True)       # new shape
    w(a32.astype(np.float32), flag=True)           # new dtype
    w(a32, flag=False)                             # new static arg
    assert tel.compile_count("prog") == 4 and len(calls) == 5
    assert w._cache_size() == 1                    # attrs pass through
    assert _signature((a32,), {}) == (((2, 3), "int32"),)


# -- host spans on the profiler's clock --------------------------------------


def test_span_lands_on_profiler_clock(tmp_path):
    """A span is one ``TraceAnnotation``: under a ``jax.profiler`` trace it
    appears in the ``/host:CPU`` plane, nested as opened, and the ring
    event a bound Telemetry records for it starts at the same instant on
    the same clock (Unix-epoch ns, ``profile_start_time`` + offset)."""
    import jax
    from jax.profiler import ProfileData
    tel = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    with span(tel, "serve.segment", kind="decode_segment") as sp:
        with span(None, "serve.segment.wait"):
            time.sleep(0.005)
        sp.args["tokens"] = 3
    jax.profiler.stop_trace()
    with pytest.raises(KeyError):         # a span passes exceptions on
        with span(tel, "serve.admit"):
            raise KeyError("x")

    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    start, host = None, {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        elif plane.name == "/host:CPU":
            host.update({e.name: (e.start_ns, e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith("serve.")})
    assert set(host) == {"serve.segment", "serve.segment.wait"}
    (s0, d0), (s1, d1) = host["serve.segment"], host["serve.segment.wait"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0 and d1 >= 5e6
    # the ring holds the bound spans only (the wait span had no Telemetry)
    ring = {e["name"]: e for e in tel.events}
    assert set(ring) == {"serve.segment", "serve.admit"}
    ev = ring["serve.segment"]
    assert ev["args"] == {"kind": "decode_segment", "tokens": 3}
    assert abs(ev["ts"] * 1e3 - (start + s0)) < 1e6      # within 1 ms
    assert abs(ev["dur"] * 1e3 - d0) < 1e6


# -- bitwise inertness + end-to-end spans/trace ------------------------------


def test_telemetry_none_is_default_and_bitwise_inert(dsa):
    """The whole subsystem rides behind ``ServingConfig.telemetry=None``:
    an engine with telemetry fully enabled (probe every segment) must
    produce byte-identical tokens to the default engine."""
    assert ServingConfig().telemetry is None
    cfg, params = dsa
    kw = dict(slots=2, max_len=MAX_LEN, seg_len=4, long_context=True,
              dsa_mode="block")
    plain = ContinuousEngine(cfg, params, **kw)
    tel = Telemetry(sample_every=1)
    traced = ContinuousEngine(cfg, params, telemetry=tel, **kw)
    got_p = plain.run(_mk_requests(cfg.vocab, SHAPES))
    got_t = traced.run(_mk_requests(cfg.vocab, SHAPES))
    for rid in got_p:
        np.testing.assert_array_equal(got_p[rid], got_t[rid],
                                      err_msg=f"rid {rid}")
    assert tel.compile_count() > 0 and len(tel.events) > 0


def test_chrome_trace_structure_and_prometheus_consistency(dense):
    cfg, params = dense
    tel = Telemetry(sample_every=0)
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          telemetry=tel)
    reqs = _mk_requests(cfg.vocab, SHAPES)
    t_lo = time.time_ns() / 1e3
    results = ce.serve(reqs)
    t_hi = time.time_ns() / 1e3
    s = summarize(results, max(r.finish_s for r in results))

    trace = tel.chrome_trace()
    evs = trace["traceEvents"]
    assert json.loads(json.dumps(trace)) == trace       # JSON-serializable
    names = [e["name"] for e in evs]
    # per-request lifecycle: submit / first_token instants + one complete
    # span per retirement, on the request's own track
    by_rid = {r.rid: r for r in results}
    for r in reqs:
        span = [e for e in evs if e["name"] == f"req {r.rid} [ok]"]
        assert len(span) == 1 and span[0]["ph"] == "X"
        assert span[0]["pid"] == "requests"
        assert span[0]["tid"] == f"rid {r.rid}"
        assert span[0]["args"]["tokens"] == len(by_rid[r.rid].tokens)
        assert span[0]["dur"] >= 0
    assert names.count("submit") == len(reqs)
    assert names.count("first_token") == len(reqs)
    # the engine's phase spans, each carrying what its on_* hook adds
    segs = [e for e in evs if e["name"] == "serve.segment"]
    assert segs and all(e["ph"] == "X" for e in segs)
    assert all(e["args"]["kind"] == "decode_segment" for e in segs)
    bursts = [e for e in evs if e["name"] == "serve.chunk_burst"]
    assert bursts and all(e["args"]["chunks"] >= 1 for e in bursts)
    staging = [e for e in evs if e["name"] == "serve.admit.staging"]
    assert staging and all(e["args"]["kind"] == "chunked"
                           for e in staging)
    assert sum(e["args"]["n"] for e in staging) == len(reqs)
    assert any(e["name"] == "serve.admit" for e in evs)
    assert any(n.startswith("compile[") for n in names)
    # children nest inside their parent span
    for child, parent in (("serve.segment.dispatch", segs),
                          ("serve.segment.emit", segs),
                          ("serve.insert", bursts)):
        kids = [e for e in evs if e["name"] == child]
        assert kids, child
        for k in kids:
            assert any(p["ts"] <= k["ts"]
                       and k["ts"] + k["dur"] <= p["ts"] + p["dur"]
                       for p in parent), child
    # metadata rows make the pids/tids human-named in perfetto
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in evs)
    # every non-meta event sits on the profiler's clock: Unix-epoch
    # microseconds from time.time_ns, inside the serve() call
    assert trace["otherData"]["clock"] == "time.time_ns"
    assert all(t_lo <= e["ts"] <= t_hi for e in evs if e["ph"] != "M")

    # prometheus snapshot agrees with summarize() and health() because
    # the registry is fed from the same single retirement path
    text = tel.prometheus_text()
    assert (tel.metrics.value("serving_requests_total", status="ok")
            == s["n_ok"] == len(reqs))
    assert (tel.metrics.value("serving_tokens_delivered_total")
            == s["delivered_tokens"])
    n_ttft, _ = tel.metrics.value("serving_ttft_seconds")
    assert n_ttft == len(reqs)
    n_wait, mean_wait = tel.metrics.value("serving_queue_wait_seconds")
    assert n_wait == len(reqs)
    assert mean_wait == pytest.approx(
        np.mean([r.admit_s - r.arrival_s for r in results]))
    h = ce.health()
    assert f'serving_health_segments {float(h["segments"])}' in text
    assert f'serving_health_failed {float(h["failed"])}' in text
    assert 'serving_requests_total{status="ok"} 5.0' in text


def test_engine_reset_resets_registry_keeps_compile_log(dense):
    """Satellite pin: ``reset()`` must leave ``health()`` fresh AND zero
    the telemetry registry — stale counters after a reset would make the
    prometheus surface disagree with the engine — while the compile log
    survives (the compiled programs do too)."""
    cfg, params = dense
    tel = Telemetry(sample_every=0)
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          telemetry=tel)
    ce.run(_mk_requests(cfg.vocab, SHAPES))
    assert tel.metrics.value("serving_requests_total", status="ok") == 5.0
    n_compiles = tel.compile_count()
    assert n_compiles > 0
    ce.reset()
    h = ce.health()
    assert h["resident"] == 0 and h["segments"] == 0 and h["failed"] == 0
    assert tel.metrics.value("serving_requests_total", status="ok") == 0.0
    assert len(tel.events) == 0
    assert tel.compile_count() == n_compiles       # compile log survives
    # the engine still serves (and the watcher keeps counting) after reset
    ce.run(_mk_requests(cfg.vocab, SHAPES[:2], seed=9))
    assert tel.metrics.value("serving_requests_total", status="ok") == 2.0


# -- the recompilation contract, live ----------------------------------------


@pytest.mark.parametrize("variant", ["dense", "paged", "quant", "spec"])
def test_recompilation_contract(dense, variant):
    """THE fixed-compile-set contract as one assertion per engine family:
    ``warmup`` over two prompt buckets compiles one chunk, one staging
    build and one insert program per (bucket, group-width in {1, slots})
    and ONE decode
    segment (speculative engines compile ONE verify and no segment —
    spec segments always run when the batch is in the envelope); mixed
    traffic afterwards adds ZERO new compiles.  ``zero_pages``/``seed``
    are bounded by pow2 id widths, not fixed, so they are excluded from
    the zero-new-compiles assertion."""
    cfg, params = dense
    kw = {"paged": dict(paged=True), "quant": dict(kv_quant="int8"),
          "spec": dict(spec=3), "dense": {}}[variant]
    tel = Telemetry(sample_every=0)
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          telemetry=tel, **kw)
    ce.warmup([20, 40])                      # two prompt buckets
    tally = TallyCounter(p for p, _, _ in tel.compiles)
    insert = "insert_paged" if variant == "paged" else "insert"
    assert tally["chunk"] == 4               # 2 buckets x widths {1, slots}
    assert tally["staging"] == 4             # their staging-cache builds
    assert tally[insert] == 4
    if variant == "spec":
        assert tally["verify"] == 1 and tally["segment"] == 0
    else:
        assert tally["segment"] == 1 and tally["verify"] == 0
    after_warmup = tel.compile_count()
    ce.run(_mk_requests(cfg.vocab, SHAPES, seed=3))
    fresh = [p for p, _, _ in tel.compiles[after_warmup:]
             if p not in ("zero_pages", "seed")]
    assert fresh == [], f"{variant}: unexpected compiles {fresh}"


# -- dynamic-sparsity observability ------------------------------------------


def test_sparsity_probe_samples_keep_rate(dsa):
    cfg, params = dsa
    tel = Telemetry(sample_every=1)          # probe every decode segment
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          long_context=True, dsa_mode="block",
                          telemetry=tel)
    ce.run(_mk_requests(cfg.vocab, SHAPES))
    n, mean_keep = tel.metrics.value("serving_dsa_keep_rate")
    assert n >= 1 and 0.0 < mean_keep <= 1.0
    samples = [e for e in tel.events if e["name"] == "dsa_sample"]
    assert samples and all(
        0.0 < e["args"]["mean_keep_rate"] <= 1.0 for e in samples)
    # the probe rides its own program and must compile exactly once
    assert tel.compile_count("probe") == 1
    # dense engines / sample_every=0 never probe (gated host-side)
    tel2 = Telemetry(sample_every=0)
    ce2 = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                           seg_len=4, long_context=True, dsa_mode="block",
                           telemetry=tel2)
    ce2.run(_mk_requests(cfg.vocab, SHAPES[:2]))
    assert tel2.compile_count("probe") == 0
    assert tel2.metrics.value("serving_dsa_keep_rate") in (0.0, (0, 0.0))
