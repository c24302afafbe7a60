"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (the file its
``configs`` entry gives) and a traffic mix (``bench/traffic/``); its
serving settings and correctness limits are in ``bench/cells/<cell>.json``.
A model the program serves on its normal path is added by data files
alone: its configuration (``arch``, every key an ``ArchConfig`` field,
see ``arch_config``, and ``reference``, the plain reference module, by
default ``bench/reference.py``; a new one is ``bench/reference_<config>.py``
and may import helpers from ``bench/reference.py``), a cell file and a
traffic file.  Weights and work counts follow the program's layout
(``bench/weights.py``, ``bench/work.py``).  One run:

1. set-up: random weights from the seed on the device, planted as the
   cell says (``bench/weights.py``), one ``ContinuousEngine`` in DSA kernel
   mode on a paged bf16 cache, ``warmup`` of the cell's one prompt bucket,
   a check that the decode segment holds Pallas kernels, and the requests
   drawn from the seed (``bench/generator.py``);
2. the window: exactly one ``ContinuousEngine.serve`` call over those
   requests (with ``--trace 1``, a thread profiles a span from the middle);
3. the check: the engine is freed and a sample of the served requests is
   compared with the plain reference (``bench/check.py``);
4. the metrics: with ``--trace 0`` the cell's end-to-end metrics, with
   ``--trace 1`` its per-layer metrics, each read by ``bench/metrics/<name>.py``.

The last lines of standard error are the compared numbers beside their
limits; the last line of standard output is the JSON result.  A run off
the TPU, or on fewer chips than the cell asks for, prints no result and
exits 2.
"""
import time

T_START = time.monotonic()

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import typing
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# the plain reference of a configuration that names none
DEFAULT_REFERENCE = "bench/reference.py"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """Everything a cell is made of, found by name under ``root``: its
    entry in ``BENCHMARK.json``, its configuration's file and the
    reference module that file names, ``bench/cells/<cell>.json`` and
    ``bench/traffic/<traffic>.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = json.loads((root / {c["name"]: c["file"] for c in spec["configs"]}[
        w["config"]]).read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    from bench import generator
    return SimpleNamespace(
        name=name, chips=w["chips"], arch=conf["arch"],
        reference=reference_module(conf.get("reference", DEFAULT_REFERENCE),
                                   root),
        mix=generator.load(w["traffic"], root / "bench"),
        cell=json.loads((root / "bench" / "cells" / f"{name}.json")
                        .read_text()),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]))


def reference_module(path: str, root: Path = ROOT):
    """The plain reference a configuration names (a path under ``root``):
    a module with ``geometry`` and ``served_logits``.  One of this
    package's own files is imported as ``bench.<name>``."""
    file = (root / path).resolve()
    if file.parent == BENCH.resolve():
        return importlib.import_module(f"bench.{file.stem}")
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{file.stem}", file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Compile requests, persistent-cache hits and misses, and compile
    seconds, from ``jax.monitoring``."""

    def __init__(self):
        self.counts, self.secs = {}, {}

    def event(self, name, **_):
        self.counts[name] = self.counts.get(name, 0) + 1

    def duration(self, name, secs, **_):
        self.counts[name] = self.counts.get(name, 0) + 1
        self.secs[name] = self.secs.get(name, 0.0) + secs

    def snapshot(self) -> dict:
        c, s = self.counts, self.secs
        return {
            "requests": c.get(
                "/jax/compilation_cache/compile_requests_use_cache", 0),
            "hits": c.get("/jax/compilation_cache/cache_hits", 0),
            "misses": c.get("/jax/compilation_cache/cache_misses", 0),
            "compile_s": s.get("/jax/core/compile/backend_compile_duration",
                               0.0),
            "backend_compiles": c.get(
                "/jax/core/compile/backend_compile_duration", 0)}


class TraceSpan(threading.Thread):
    """Profile the span [start_s, start_s + span_s) of the window from a
    thread of its own while the main thread serves.  The device's trace
    buffer holds about half a minute of this cell's operations, so the
    whole window would come back cut short; a span from the middle of the
    window is whole.  Exporting the span after it stops takes the
    profiler minutes on a TPU v5e host (most of a run that traced a fifth
    of the window), so the span is a tenth of the window, and a traced
    run ends well inside its time limit."""

    def __init__(self, start_s: float, span_s: float):
        super().__init__(daemon=True)
        self.start_s, self.span_s = start_s, span_s

    def run(self):
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        time.sleep(self.start_s)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        time.sleep(self.span_s)
        jax.profiler.stop_trace()


def arch_config(arch: dict):
    """The program's ``ArchConfig`` for a configuration file's ``arch``.

    Every key is an ``ArchConfig`` field; a nested block (``moe``, ``mla``,
    ``mamba``, ``rwkv``, ``dsa``) becomes the dataclass its field's type
    names, and a list a tuple.  ``family`` defaults to ``"dense"``; DSA is
    on unless the block says otherwise.  A key the program does not know
    stops the run, named.  The configuration's ``dsa.decode_local`` must be
    the program's fixed decode window."""
    from repro.configs.base import ArchConfig
    from repro.models.attention import DECODE_LOCAL
    dsa = dict(arch["dsa"])
    if dsa.pop("decode_local") != DECODE_LOCAL:
        raise SystemExit(f"the program's decode window is {DECODE_LOCAL} "
                         f"rows, the configuration states "
                         f"{arch['dsa']['decode_local']}")
    return _dataclass(ArchConfig, {"family": "dense", **arch,
                                   "dsa": {"enabled": True, **dsa}}, "arch")


def _dataclass(cls, values: dict, where: str):
    hints = typing.get_type_hints(cls)
    kw = {}
    for key, val in values.items():
        if key not in hints:
            raise SystemExit(f"{where}.{key}: the program's "
                             f"{cls.__name__} has no field {key!r}")
        sub = [t for t in (hints[key], *typing.get_args(hints[key]))
               if dataclasses.is_dataclass(t)]
        if isinstance(val, dict) and sub:
            val = _dataclass(sub[0], val, f"{where}.{key}")
        kw[key] = _tuples(val)
    return cls(**kw)


def _tuples(val):
    if isinstance(val, (list, tuple)):
        return tuple(_tuples(v) for v in val)
    return val


def build_engine(cfg, weights, serving: dict):
    import jax.numpy as jnp
    from repro.inference.config import ServingConfig
    from repro.inference.scheduler import ContinuousEngine
    return ContinuousEngine(cfg, weights, config=ServingConfig(
        dsa_mode="kernel", long_context=True, paged=True,
        cache_dtype=jnp.dtype(cfg.dtype), **serving))


PAGE_ZEROING = ("_zero_pages", "_caches", "_page_rows")


def warm_page_zeroing(eng, rows: int) -> None:
    """Compile the engine's page-zeroing program at every width the cell
    can need.  ``warmup`` leaves it out: its width is the power of two (at
    least 4) above the number of freed pages an admission maps, up to a
    request's ``rows`` in pages.  Zeroing the zero page changes nothing.
    This reads engine internals, and goes once ``warmup`` covers it."""
    import jax.numpy as jnp
    missing = [a for a in PAGE_ZEROING if not hasattr(eng, a)]
    if missing:
        raise SystemExit(f"the engine has no {', '.join(missing)}: if its "
                         f"warmup now compiles page zeroing, delete "
                         f"bench.run.warm_page_zeroing")
    n = -(-rows // eng._page_rows)
    width = 4
    while True:
        eng._caches = eng._zero_pages(eng._caches,
                                      jnp.zeros((width,), jnp.int32))
        if width >= n:
            return
        width *= 2


def requests(specs):
    from repro.inference.scheduler import Request
    return [Request(s.rid, s.prompt, s.n_new, greedy=True, seed=s.rid,
                    arrival_s=s.arrival_s) for s in specs]


def run_cell(c: SimpleNamespace, seed: int, seconds: float, trace: bool,
             clog: CompileLog, devices) -> dict:
    """Set-up, window, check and metrics of one run; returns the result."""
    from bench import check, generator, weights, work

    arch, serving, plant = c.arch, c.cell["serving"], c.cell.get("plant")
    reference = c.reference
    peak = work.peak(devices[0].device_kind)
    cfg = arch_config(arch)
    w = weights.make(arch, seed, plant)
    specs = generator.generate(c.mix, seconds, seed, arch["vocab"], plant,
                               arch["dsa"]["block_k"])
    max_len, max_new = serving["max_len"], c.mix["output"]["max"]
    bucket = reference.geometry(arch, max_len, c.mix["prompt"]["max"])[
        "bucket"]
    for s in specs:
        if (reference.geometry(arch, max_len, len(s.prompt))["bucket"]
                != bucket or len(s.prompt) + s.n_new > max_len):
            raise SystemExit(f"request {s.rid} leaves the cell's bucket "
                             f"{bucket} or its max_len {max_len}")
    eng = build_engine(cfg, w, serving)
    eng.warmup([bucket])
    warm_page_zeroing(eng, c.mix["prompt"]["max"] + max_new)
    # on the chip the decode segment must run the Pallas kernels (off the
    # chip, in tests, they are interpreted and leave no custom call)
    n_kernels = eng.segment_hlo().count("tpu_custom_call")
    if n_kernels == 0 and devices[0].platform == "tpu":
        raise SystemExit("the kernel-mode decode segment runs no Pallas "
                         "kernel")
    reqs = requests(specs)
    before = clog.snapshot()
    setup_s = time.monotonic() - T_START
    log(f"set-up {setup_s:.3f} s: {before['requests']} compile requests, "
        f"{before['hits']} persistent-cache hits, {before['misses']} misses, "
        f"{before['compile_s']:.3f} s compiling; {n_kernels} tpu_custom_call "
        f"in the decode segment; {len(reqs)} requests")

    tracer = TraceSpan(0.45 * seconds, 0.1 * seconds) if trace else None
    t0 = time.monotonic()
    if tracer:
        tracer.start()
    results = eng.serve(reqs)
    window_s = time.monotonic() - t0
    if tracer:
        t_join = time.monotonic()
        tracer.join()
        log(f"trace span exported {time.monotonic() - t_join:.1f} s after "
            f"the window")
    after = clog.snapshot()
    in_window = after["requests"] - before["requests"]
    mem_peak = int(devices[0].memory_stats()["peak_bytes_in_use"])
    stats = dict(eng.stats)
    seg_len = eng.seg_len
    log(f"window {window_s:.3f} s; {in_window} compile requests inside it; "
        f"memory peak {mem_peak} bytes")
    del eng
    gc.collect()

    prompts = {s.rid: s.prompt for s in specs}
    picked = check.sample(results, c.cell["check"]["sample"], seed)
    numbers = check.readings(w, arch, max_len, max_new, prompts, picked,
                             ref=reference)
    limits = c.cell["check"]["limits"]
    correct = check.judge(picked, arch["vocab"], numbers, limits)

    red = None
    if trace:
        from bench import trace_reduce
        files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        t_red = time.monotonic()
        red = trace_reduce.reduce(str(files[-1]))
        log(f"trace {files[-1].stat().st_size} bytes, reduced in "
            f"{time.monotonic() - t_red:.1f} s; profile {red['profile_s']!r} "
            f"s, first device op at {red['lead_s']!r} s, first program run "
            f"at {red['first_program_s']!r} s, device-event window "
            f"{red['window_s']!r} s")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    run = SimpleNamespace(
        results=results, window_s=window_s, setup_s=setup_s, stats=stats,
        seg_len=seg_len, arch=arch, max_len=max_len, peak=peak, trace=red,
        geo_of=lambda plen: reference.geometry(arch, max_len, plen),
        log=log)
    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    n_ok = sum(r.status == "ok" for r in results)
    out = {
        "correct": bool(correct), "attempted": len(reqs),
        "failed": len(reqs) - n_ok, "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": mem_peak}}
    if red is not None:
        out["device"]["busy_s"] = red["busy_s"]
        out["device"]["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["compared"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits}
    log(f"compared: {numbers['tokens']} served tokens of "
        f"{numbers['requests']} requests")
    log(f"run {time.monotonic() - T_START:.1f} s")
    for k in limits:
        log(f"  {k} {numbers[k]!r} (limit {limits[k]!r})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    c = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    clog = CompileLog()
    jax.monitoring.register_event_listener(clog.event)
    jax.monitoring.register_event_duration_secs_listener(clog.duration)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < c.chips:
        log(f"bench: the cell needs {c.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    out = run_cell(c, args.seed, args.seconds, bool(args.trace), clog,
                   devices[:c.chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
