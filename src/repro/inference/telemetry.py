"""Serving telemetry: request spans, Chrome-trace timelines, metrics
export, compile events, and dynamic-sparsity observability.

A ``Telemetry`` object hangs off ``ServingConfig.telemetry`` (default
``None``).  ``None`` is BITWISE-INERT: no jit gets wrapped, no hook
runs, and the engines behave byte-identically to a build without this
module.  With telemetry enabled there are four layers:

  request spans      every ``Request`` gets timestamped lifecycle events
                     (submit -> first token -> retire-with-status) and
                     the engine's phase spans (``serve.admit``,
                     ``serve.chunk_burst``, ``serve.segment`` and their
                     children; see ``span``) and fault events land in a
                     bounded ring buffer, exportable as Chrome
                     trace-event JSON (load in Perfetto or
                     chrome://tracing).
  metrics registry   counters / gauges / histograms (per-status request
                     counts, delivered tokens, TTFT, latency, segment
                     and chunk-burst timing, queue depth, slot
                     occupancy, PagePool free pages, faults, watchdog
                     stalls) with Prometheus text-exposition export.
                     The registry is fed from the SAME code paths that
                     feed ``stats``/``summarize()`` (``_emit`` is the
                     single retirement path) and the export refreshes
                     gauges from ``health()`` of the bound engine, so
                     the three surfaces cannot disagree.
  compile events     ``wrap_jit`` wraps a jitted entry point in a
                     host-side watcher that records every distinct
                     (program, shape-signature) dispatch with a
                     timestamp + trace event — the documented
                     recompilation contract becomes a live metric and a
                     CI-assertable invariant (see tests/test_telemetry).
  sparsity sampling  once per ``sample_every`` decode segments the
                     scheduler replays one decode step with
                     ``RunFlags.sel_probe`` set and reads back ONLY the
                     DSA block-selection outputs (XLA dead-code
                     eliminates the attention/MLP compute the probe does
                     not use), recording per-slot keep-rate, selected-
                     block churn between samples, and cross-layer
                     selection overlap — the input-dependent sparsity
                     the paper claims, observable per workload.

Overhead discipline: every hook is host-side and O(events); signature
hashing walks leaf shapes/dtypes only (no device sync); the probe is the
only extra device work and it is sampled.  The traced-vs-untraced
goodput ratio is benchmarked (``table_serve``: ``continuous_traced``)
and regression-gated at >= 0.95 on full runs.

Host spans: the scheduler opens every phase of its loop through
``span(tel, name, **args)``.  A span always enters
``jax.profiler.TraceAnnotation(name)``, so while a ``jax.profiler`` trace
runs the phase lands in its ``/host:CPU`` plane on the device trace's
clock; with no profiler running that costs one flag check.  With a
Telemetry bound (``tel`` not None) the span also records the same
interval as a complete event in the Chrome-trace ring.

Clock: ``Telemetry.now`` and every Chrome-trace timestamp read
``time.time_ns()``, the clock the profiler's ``TraceMe`` events use: a
``ts`` is microseconds since the Unix epoch (``otherData`` in the export
says so), and an event at ``ts`` lies ``ts * 1e3 -
profile_start_time`` ns into a profiler trace whose ``Task Environment``
plane records that ``profile_start_time``.

``reset()`` (called from ``ContinuousEngine.reset()``) clears events,
spans, and metrics but KEEPS the compile log: compiled programs survive
an engine reset, so their record must too.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "Telemetry", "span"]

# default histogram bucket bounds (seconds / ratios)
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
RATE_BUCKETS = tuple(round(0.1 * i, 1) for i in range(1, 11))


# ---------------------------------------------------------------------------
# metrics


class Counter:
    """Monotonic counter."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Fixed-bound histogram (Prometheus cumulative-bucket semantics)."""

    __slots__ = ("bounds", "counts", "count", "sum")

    def __init__(self, bounds: Tuple[float, ...] = LATENCY_BUCKETS):
        self.bounds = tuple(bounds)
        self.counts = [0] * len(self.bounds)   # per-bound, NOT cumulative
        self.count = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.sum += v
        for i, b in enumerate(self.bounds):
            if v <= b:
                self.counts[i] += 1
                break

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0


class MetricsRegistry:
    """Name+labels -> metric store with Prometheus text exposition."""

    def __init__(self):
        self._metrics: Dict[tuple, Any] = {}
        self._kind: Dict[str, str] = {}

    def _get(self, kind, name, labels, factory):
        key = (name, tuple(sorted(labels.items())))
        m = self._metrics.get(key)
        if m is None:
            m = self._metrics[key] = factory()
            self._kind.setdefault(name, kind)
        return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", name, labels, Counter)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", name, labels, Gauge)

    def histogram(self, name: str, bounds: Tuple[float, ...] =
                  LATENCY_BUCKETS, **labels) -> Histogram:
        return self._get("histogram", name, labels,
                         lambda: Histogram(bounds))

    def value(self, name: str, **labels):
        """Current value (Counter/Gauge: float, Histogram: (count, mean));
        0 for a metric that was never touched."""
        m = self._metrics.get((name, tuple(sorted(labels.items()))))
        if m is None:
            return (0, 0.0) if self._kind.get(name) == "histogram" else 0.0
        if isinstance(m, Histogram):
            return (m.count, m.mean)
        return m.value

    def to_prometheus(self) -> str:
        """Prometheus text exposition format (one snapshot)."""
        out: List[str] = []
        seen_type = set()
        for (name, labels), m in sorted(self._metrics.items()):
            if name not in seen_type:
                out.append(f"# TYPE {name} {self._kind[name]}")
                seen_type.add(name)
            lab = ",".join(f'{k}="{v}"' for k, v in labels)
            if isinstance(m, Histogram):
                pre = f"{name}_bucket{{{lab}," if lab else f"{name}_bucket{{"
                cum = 0
                for b, c in zip(m.bounds, m.counts):
                    cum += c
                    out.append(f'{pre}le="{b}"}} {cum}')
                out.append(f'{pre}le="+Inf"}} {m.count}')
                suf = f"{{{lab}}}" if lab else ""
                out.append(f"{name}_sum{suf} {m.sum}")
                out.append(f"{name}_count{suf} {m.count}")
            else:
                suf = f"{{{lab}}}" if lab else ""
                out.append(f"{name}{suf} {m.value}")
        return "\n".join(out) + "\n"

    def reset(self) -> None:
        self._metrics.clear()
        self._kind.clear()


# ---------------------------------------------------------------------------
# compile watching


def _leaf_sig(x) -> Any:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), str(x.dtype))
    return repr(x)


def _signature(args, kwargs) -> tuple:
    """Host-side dispatch signature: leaf shapes/dtypes + static-arg
    reprs.  Never materializes a device value."""
    return tuple(_leaf_sig(x)
                 for x in jax.tree_util.tree_leaves((args, kwargs)))


class _CompileWatch:
    """Forwards calls to a jitted callable unchanged (donation and
    sharding included) while recording every distinct shape signature as
    a compile event on the owning Telemetry."""

    def __init__(self, tel: "Telemetry", program: str, fn):
        self._tel = tel
        self.program = program
        self._fn = fn
        self._seen = set()

    def __call__(self, *args, **kwargs):
        sig = _signature(args, kwargs)
        if sig not in self._seen:
            self._seen.add(sig)
            self._tel._record_compile(self.program, sig)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):        # _cache_size & friends pass through
        return getattr(self._fn, name)


# ---------------------------------------------------------------------------
# host spans


class span:
    """``with span(tel, name, **args) as sp:`` -- one phase of the serving
    loop.  Always a ``jax.profiler.TraceAnnotation(name)`` (a flag check
    when no profiler runs); with a Telemetry ``tel`` also a complete event
    ``name`` in its Chrome-trace ring, carrying ``sp.args`` as they stand
    when the span closes (callers may add to them inside)."""

    __slots__ = ("tel", "name", "args", "t0_ns", "_ann")

    def __init__(self, tel: Optional["Telemetry"], name: str, **args):
        self.tel, self.name, self.args = tel, name, args

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        if self.tel is not None:
            self.t0_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        if self.tel is not None:
            t1 = time.time_ns()
            self.tel.complete(self.name, self.t0_ns * 1e-9,
                              (t1 - self.t0_ns) * 1e-9, tid="serve",
                              args=self.args or None)


# ---------------------------------------------------------------------------
# telemetry


class Telemetry:
    """See module docstring.  ``sample_every=0`` disables the sparsity
    probe; events beyond ``max_events`` evict the oldest (ring)."""

    def __init__(self, *, sample_every: int = 16, max_events: int = 65536):
        self.sample_every = int(sample_every)
        self.metrics = MetricsRegistry()
        self.events: deque = deque(maxlen=int(max_events))
        self.compiles: List[Tuple[str, tuple, float]] = []
        self._spans: Dict[int, float] = {}      # rid -> submit ts (s)
        self._engine: Any = None

    # -- clock / raw events -------------------------------------------------

    @staticmethod
    def now() -> float:
        """Seconds since the Unix epoch on the profiler's clock
        (``time.time_ns``)."""
        return time.time_ns() * 1e-9

    def _ev(self, name, ph, ts, pid, tid, dur=None, args=None):
        e = {"name": name, "ph": ph, "ts": ts * 1e6, "pid": pid,
             "tid": tid}
        if dur is not None:
            e["dur"] = dur * 1e6
        if args:
            e["args"] = args
        self.events.append(e)

    def instant(self, name, *, pid="engine", tid="events", args=None):
        e = {"name": name, "ph": "i", "s": "t", "ts": self.now() * 1e6,
             "pid": pid, "tid": tid}
        if args:
            e["args"] = args
        self.events.append(e)

    def complete(self, name, ts, dur, *, pid="engine", tid="events",
                 args=None):
        self._ev(name, "X", ts, pid, tid, dur=max(dur, 0.0), args=args)

    # -- request lifecycle --------------------------------------------------

    def on_submit(self, rid: int, queued: int) -> None:
        t = self.now()
        self._spans[rid] = t
        self.metrics.counter("serving_submitted_total").inc()
        self.metrics.gauge("serving_queue_depth").set(queued)
        self._ev("submit", "i", t, "requests", f"rid {rid}")
        self.events[-1]["s"] = "t"

    def on_first_token(self, rid: int) -> None:
        t = self.now()
        t0 = self._spans.get(rid)
        if t0 is not None:
            self.metrics.histogram("serving_ttft_seconds").observe(t - t0)
        self._ev("first_token", "i", t, "requests", f"rid {rid}")
        self.events[-1]["s"] = "t"

    def on_retire(self, res) -> None:
        """Called from the engine's single retirement path (``_emit``)
        for EVERY result, so per-status counters match ``summarize()``
        by construction."""
        t = self.now()
        t0 = self._spans.pop(res.rid, t)
        self.metrics.counter("serving_requests_total",
                             status=res.status).inc()
        if res.status == "ok":
            self.metrics.counter("serving_tokens_delivered_total").inc(
                len(res.tokens))
            self.metrics.histogram("serving_request_latency_seconds"
                                   ).observe(res.latency_s)
            self.metrics.histogram("serving_queue_wait_seconds").observe(
                res.admit_s - res.arrival_s)
        self.complete(f"req {res.rid} [{res.status}]", t0, t - t0,
                      pid="requests", tid=f"rid {res.rid}",
                      args={"status": res.status,
                            "prompt_len": int(res.prompt_len),
                            "tokens": len(res.tokens),
                            "ttft_s": res.ttft_s})

    # -- engine timeline ----------------------------------------------------

    def on_admission(self, sp: span, n, bucket, mode, kind,
                     prefix_skip_chunks=0) -> None:
        """An admission group started; ``sp`` is the span that builds it
        (``serve.admit.staging`` or ``serve.admit.blocking``), whose event
        carries the group's shape."""
        self.metrics.counter("serving_admissions_total", kind=kind).inc(n)
        sp.args.update(n=n, bucket=int(bucket), mode=mode, kind=kind)
        if prefix_skip_chunks:
            sp.args["prefix_skip_chunks"] = int(prefix_skip_chunks)

    def on_chunk_burst(self, sp: span, dur_s, chunks, bucket, mode,
                       members) -> None:
        """``dur_s``: the burst's synced wall time (``stats["chunk_s"]``);
        ``sp`` is its ``serve.chunk_burst`` span."""
        self.metrics.counter("serving_chunks_total").inc(chunks)
        self.metrics.histogram("serving_chunk_burst_seconds").observe(dur_s)
        sp.args.update(chunks=chunks, bucket=int(bucket), mode=mode,
                       members=members)

    def on_segment(self, sp: span, kind, dur_s, *, mode, active, tokens,
                   queued, resident, pool_free=None, slow=False,
                   rounds=0) -> None:
        """``dur_s``: the segment's synced wall time
        (``stats["segment_s"]``); ``sp`` is its ``serve.segment`` span."""
        m = self.metrics
        m.counter("serving_segments_total", kind=kind).inc()
        m.counter("serving_segment_tokens_total").inc(tokens)
        m.histogram("serving_segment_seconds").observe(dur_s)
        m.gauge("serving_queue_depth").set(queued)
        m.gauge("serving_resident_slots").set(resident)
        if pool_free is not None:
            m.gauge("serving_pool_free_pages").set(pool_free)
        if slow:
            m.counter("serving_watchdog_slow_total").inc()
        if rounds:
            m.counter("serving_spec_rounds_total").inc(rounds)
        sp.args.update(kind=kind, mode=mode, active=int(active),
                       tokens=int(tokens))
        if rounds:
            sp.args["verify_rounds"] = int(rounds)
        if slow:
            sp.args["watchdog_slow"] = True

    def on_fault(self, point: str, rid=None) -> None:
        self.metrics.counter("serving_faults_total", point=point).inc()
        self.instant(f"fault[{point}]", tid="faults",
                     args={"rid": rid} if rid is not None else None)

    def on_error(self, msg: str) -> None:
        self.metrics.counter("serving_errors_total").inc()
        self.instant("error", tid="faults", args={"error": msg[:200]})

    # -- compile events -----------------------------------------------------

    def wrap_jit(self, program: str, fn):
        """Wrap a jitted callable in a compile watcher (host-side only)."""
        return _CompileWatch(self, program, fn)

    def _record_compile(self, program: str, sig: tuple) -> None:
        t = self.now()
        self.compiles.append((program, sig, t))
        self.metrics.counter("serving_compiles_total", program=program).inc()
        self.instant(f"compile[{program}]", tid="compiles",
                     args={"program": program, "n_leaves": len(sig)})

    def compile_count(self, program: Optional[str] = None) -> int:
        if program is None:
            return len(self.compiles)
        return sum(1 for p, _, _ in self.compiles if p == program)

    def compile_log(self) -> List[Tuple[str, tuple, float]]:
        return list(self.compiles)

    # -- dynamic sparsity ---------------------------------------------------

    def on_sparsity_sample(self, segment: int, samples) -> None:
        """``samples``: (slot, rid, keep_rate, churn|None, overlap|None)
        per active slot, from one sel_probe replay."""
        if not samples:
            return
        m = self.metrics
        m.counter("serving_sparsity_samples_total").inc()
        keeps = []
        for slot, rid, keep, churn, overlap in samples:
            keeps.append(keep)
            m.histogram("serving_dsa_keep_rate", RATE_BUCKETS).observe(keep)
            if churn is not None:
                m.histogram("serving_dsa_block_churn",
                            RATE_BUCKETS).observe(churn)
            if overlap is not None:
                m.histogram("serving_dsa_layer_overlap",
                            RATE_BUCKETS).observe(overlap)
        self.instant("dsa_sample", tid="sparsity",
                     args={"segment": int(segment),
                           "slots": len(samples),
                           "mean_keep_rate": sum(keeps) / len(keeps)})

    # -- export -------------------------------------------------------------

    def bind_engine(self, engine) -> None:
        """Bind the ContinuousEngine whose ``health()`` snapshot is
        mirrored into gauges at export time."""
        self._engine = engine

    def _refresh_health_gauges(self) -> None:
        if self._engine is None:
            return
        for k, v in self._engine.health().items():
            if isinstance(v, bool):
                v = int(v)
            if isinstance(v, (int, float)):
                self.metrics.gauge(f"serving_health_{k}").set(float(v))

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (perfetto-loadable)."""
        meta = []
        pids = {e["pid"] for e in self.events}
        for pid in sorted(pids, key=str):
            meta.append({"name": "process_name", "ph": "M", "pid": pid,
                         "tid": 0, "args": {"name": str(pid)}})
        tids = sorted({(e["pid"], e["tid"]) for e in self.events},
                      key=str)
        for pid, tid in tids:
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": str(tid)}})
        return {"traceEvents": meta + list(self.events),
                "displayTimeUnit": "ms",
                "otherData": {"clock": "time.time_ns",
                              "ts": "microseconds since the Unix epoch"}}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def prometheus_text(self) -> str:
        self._refresh_health_gauges()
        return self.metrics.to_prometheus()

    def write_prometheus(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.prometheus_text())

    # -- lifecycle ----------------------------------------------------------

    def reset(self) -> None:
        """Clear metrics, events, and spans (compile log survives: the
        compiled programs do too)."""
        self.metrics.reset()
        self.events.clear()
        self._spans.clear()
