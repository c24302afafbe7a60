"""Scheduler spans and model-step stages in a profiler trace.

The program opens every phase of its serving loop as a host span named
``serve.*`` (``repro.inference.telemetry.span``, a
``jax.profiler.TraceAnnotation``) and runs each stage of its model step
under a ``jax.named_scope`` (``STAGES``).  This reads both out of the
trace (``.xplane.pb``) that ``bench/trace_reduce.py`` reads, over the same
device-event window (first device operation to the end of the last):

- ``idle_by_span``: each idle nanosecond of the window credited to the
  innermost ``serve.*`` host span covering it, else ``(no span)``;
- ``stage_s[program][stage]``: device seconds of each program's
  operations per stage.  An operation's stage is the last ``STAGES`` name
  in the ``op_name`` metadata of its instruction in the program's compiled
  HLO text (``scope_map``); operations XLA inserted without one count as
  ``(no scope)``.

``idle_share`` and ``stage_share`` are the shares the per-layer metrics
of these spans and stages would report.  A trace of a program without
the spans or scopes reads all idle as ``(no span)`` and all device time
as ``(no scope)``, and the shares read None.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1`` does and prints its result
line with ``idle_by_span`` and ``stage_s`` added to the breakdown and the
shares added to the metrics.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

# the model step's named scopes (models/attention.py, blocks.py,
# transformer.py, inference/scheduler.py); "weights" is the per-layer
# slicing of the stacked parameters
STAGES = ("weights", "qkv", "kv_write", "dsa_predict", "dsa_select",
          "attend", "mlp", "logits_sample")
NO_SPAN, NO_SCOPE = "(no span)", "(no scope)"
SPAN_PREFIX = "serve."
ADMIT_SPANS = ("serve.admit", "serve.admit.staging", "serve.admit.blocking")
SEGMENT_HOST_SPANS = ("serve.segment.dispatch", "serve.segment.emit")
SELECT_STAGES = ("dsa_predict", "dsa_select")
# the programs whose compiled text names the stages of their operations
SEGMENT, CHUNK = "_segment_fn", "_chunk_fn"

_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_KEY = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) [a-z][\w\-]*\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def stage_of(op_name: str) -> str:
    """The innermost ``STAGES`` scope of an ``op_name``, else
    ``(no scope)``: 'jit(f)/while/body/attend/jit(k)/pallas_call' ->
    'attend'."""
    for part in reversed(op_name.split("/")):
        if part in STAGES:
            return part
    return NO_SCOPE


def scope_map(hlo_texts: Sequence[str]) -> Dict[str, str]:
    """Instruction -> stage, over the compiled HLO texts of one program's
    variants (e.g. each admission width).  Each instruction is keyed by
    its name and result shape (``key``), which tell the variants apart,
    and by its name alone where the variants agree on its stage.
    Instructions without a stage, or given two, are left out."""
    by_key: Dict[str, str] = {}
    by_name: Dict[str, str] = {}
    for text in hlo_texts:
        for line in _scheduled(text):
            k = key(line)
            if k is None:
                continue
            op = _OP_NAME.search(line)
            st = stage_of(op.group(1)) if op else NO_SCOPE
            for d, x in ((by_name, k.split(" = ")[0]), (by_key, k)):
                d[x] = st if d.get(x, st) == st else NO_SCOPE
    return {x: st for d in (by_name, by_key) for x, st in d.items()
            if st != NO_SCOPE}


def _scheduled(text: str):
    """The instruction lines of an HLO module's computations that run as
    operations of their own: all but the bodies of fusions, which the
    trace shows as their fusion instruction."""
    fused = set(re.findall(r"\bcalls=%([\w.\-]+)", text))
    inside = False
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%([\w.\-]+)", line)
            inside = m is not None and m.group(1) not in fused
        elif inside:
            yield line


def key(text: str) -> Optional[str]:
    """'name = shape' of an HLO instruction, from its line in compiled
    text or from the name of its trace event: '%fusion.3 = f32[4]{0}
    fusion(%p), ...' -> 'fusion.3 = f32[4]{0}'."""
    m = _KEY.match(text)
    return f"{m.group(1)} = {m.group(2)}" if m else None


def instruction(event_name: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion.12'."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def stage_at(scopes: Dict[str, str], event_name: str) -> str:
    """The stage of the operation a trace event ran."""
    k = key(event_name)
    if k is not None and k in scopes:
        return scopes[k]
    return scopes.get(instruction(event_name), NO_SCOPE)


def innermost(spans: List[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, Optional[str]]]:
    """Disjoint (start, end, name) pieces of the host timeline, each
    named by the innermost span open over it (None between top-level
    spans).  ``spans`` nest (one serving thread); a span that overruns
    its parent is cut at the parent's end."""
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: List[Tuple[float, str]] = []       # (end, name), innermost last
    cursor = None

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            emit(cursor, end, nm)
            cursor = end
        if cursor is not None:
            emit(cursor, s, stack[-1][1] if stack else None)
        cursor = s
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, nm = stack.pop()
        emit(cursor, end, nm)
        cursor = end
    return out


def _credit(gaps: List[Tuple[float, float]],
            pieces: List[Tuple[float, float, Optional[str]]]
            ) -> Dict[str, float]:
    """ns of each (sorted, disjoint) gap under each named piece."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ps, pe, name = pieces[k]
            ov = min(pe, ge) - max(ps, gs)
            if ov > 0 and name is not None:
                out[name] += ov
                covered += ov
            k += 1
        out[NO_SPAN] += (ge - gs) - covered
    return out


def reduce(path: str, scopes: Optional[Dict[str, Dict[str, str]]] = None,
           top: int = 10) -> Dict:
    """``idle_by_span`` and ``stage_s`` of a trace (module docstring), with
    the window and busy time they are measured against, and the ``top``
    operations that ran without a stage.  ``scopes``: program name ->
    ``scope_map`` of its compiled text."""
    from jax.profiler import ProfileData

    from bench.trace_reduce import CONTROL_FLOW, _union, op_name, \
        program_name
    scopes = scopes or {}
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    per_device = []
    stage_ns: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    unnamed_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       program_name(e.name))
                      for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        ops = []
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else []:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            ops.append((s, e))
            name = instruction(ev.name)
            if re.sub(r"(\.\d+)+$", "", name) in CONTROL_FLOW:
                continue
            prog = _program_at(mods, s)
            if prog is not None:
                st = stage_at(scopes.get(prog, {}), ev.name)
                stage_ns[prog][st] += e - s
                if st == NO_SCOPE:
                    unnamed_ns[(prog, op_name(ev.name))] += e - s
        per_device.append(ops)
    starts = [s for ops in per_device for s, _ in ops]
    if not starts:
        raise ValueError(f"{path}: no device operation in the trace")
    first = min(starts)
    last = max(e for ops in per_device for _, e in ops)
    pieces = innermost(spans)
    idle: Dict[str, float] = defaultdict(float)
    busy_ns = 0.0
    for ops in per_device:
        merged = _union(ops)
        busy_ns += sum(e - s for s, e in merged)
        edges = [first] + [x for iv in merged for x in iv] + [last]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for k, v in _credit(gaps, pieces).items():
            idle[k] += v
    n = len(devices)
    return {
        "window_s": (last - first) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "idle_by_span": {k: v / n * 1e-9 for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "stage_s": {p: {k: v / n * 1e-9 for k, v in sorted(
            st.items(), key=lambda kv: -kv[1])}
            for p, st in stage_ns.items()},
        # what runs without a stage: (program, operation) seconds
        "no_scope_ops": [[p, o, v / n * 1e-9] for (p, o), v in sorted(
            unnamed_ns.items(), key=lambda kv: -kv[1])[:top]],
    }


def _program_at(mods, t: float) -> Optional[str]:
    """The program run (sorted, disjoint (start, end, name)) holding t."""
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else None


def idle_share(red: Dict, names: Sequence[str]) -> Optional[float]:
    """Idle under the spans ``names`` over the window, in %; None when
    the trace holds no ``serve.*`` span."""
    by = red["idle_by_span"]
    if not set(by) - {NO_SPAN}:
        return None
    return 100.0 * sum(by.get(k, 0.0) for k in names) / red["window_s"]


def stage_share(red: Dict, program: str, stages: Sequence[str]
                ) -> Optional[float]:
    """The program's device time in ``stages`` over all of its device
    time, in %; None when none of it carries a stage."""
    st = red["stage_s"].get(program, {})
    if not set(st) - {NO_SCOPE}:
        return None
    return 100.0 * sum(st.get(k, 0.0) for k in stages) / sum(st.values())


def shares(red: Dict) -> Dict[str, Optional[float]]:
    """The three device-trace shares of the spans and stages."""
    return {
        "idle_admit_share": idle_share(red, ADMIT_SPANS),
        "idle_segment_host_share": idle_share(red, SEGMENT_HOST_SPANS),
        "dsa_select_share": stage_share(red, SEGMENT, SELECT_STAGES),
    }


def program_scopes(eng, bucket: int) -> Dict[str, Dict[str, str]]:
    """``scope_map`` of the engine's segment program and of its chunk
    program at both admission widths for prompt bucket ``bucket``.  JAX's
    persistent compilation cache keys a program without its op metadata:
    a cache shared with another version of the program can return that
    version's executable, and with it that version's stage names."""
    chunk = [eng.chunk_hlo(bucket, w) for w in sorted({1, eng.slots})]
    return {SEGMENT: scope_map([eng.segment_hlo()]),
            CHUNK: scope_map(chunk)}


def main(argv=None) -> int:
    """One traced run of a cell through ``bench/run.py``'s own steps; the
    engine's compiled programs are read after its warm-up, and the trace
    is reduced here as well before the harness deletes it."""
    import argparse
    import json
    import sys
    import time
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import run as harness
    from bench import stats, trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    c = harness.load_cell(args.workload)
    got: Dict = {}
    warm, reduce_trace = harness.warm_page_zeroing, trace_reduce.reduce

    def warm_and_read(eng, rows):
        warm(eng, rows)
        bucket = c.reference.geometry(c.arch, c.cell["serving"]["max_len"],
                                      c.mix["prompt"]["max"])["bucket"]
        got["scopes"] = program_scopes(eng, bucket)
        got["stats"] = eng.stats              # the dict serve() fills
        serve = eng.serve

        def timed_serve(reqs):
            t0 = time.monotonic()
            res = serve(reqs)
            got["serve_s"] = time.monotonic() - t0
            got["results"] = res
            return res
        eng.serve = timed_serve

    def reduce_both(path, *a, **k):
        got["spans"] = reduce(path, got.get("scopes"))
        red = reduce_trace(path, *a, **k)
        got["trace_runs"] = red["program_runs"].get(SEGMENT, 0)
        return red

    harness.warm_page_zeroing = warm_and_read
    trace_reduce.reduce = reduce_both
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    clog = harness.CompileLog()
    jax.monitoring.register_event_listener(clog.event)
    jax.monitoring.register_event_duration_secs_listener(clog.duration)
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < c.chips:
        harness.log(f"spans: the cell needs {c.chips} TPU chip(s)")
        return 2
    out = harness.run_cell(c, args.seed, args.seconds, True, clog,
                           devices[:c.chips])
    red = got["spans"]
    out["breakdown"]["idle_by_span"] = red["idle_by_span"]
    out["breakdown"]["stage_s"] = red["stage_s"]
    out["breakdown"]["no_scope_ops"] = red["no_scope_ops"]
    # decode segments per second inside the traced span (from its program
    # runs) and in the rest of the window, untraced
    runs = got["trace_runs"]
    out["breakdown"]["segments_per_s"] = {
        "traced": runs / red["window_s"],
        "untraced": (got["stats"]["segments"] - runs)
        / (got["serve_s"] - red["window_s"])}
    # the traced run's own TTFT median, beside its queue-wait and
    # admission-to-first-token medians (tracing perturbs all three)
    ttft = stats.percentile(stats.ttft_s(got["results"]), 50)
    out["breakdown"]["ttft_p50_ms"] = None if ttft is None else ttft * 1e3
    for k, v in shares(red).items():
        if v is not None:
            out["metrics"][k] = {"value": v, "unit": "%"}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
