"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Device planes are ``/device:TPU:<n>``.  On each, the ``XLA Modules`` line
holds one event per program run (``jit_<function>(<hash>)``) and the ``XLA
Ops`` line one event per operation; a Pallas kernel is an operation whose
text holds ``custom_call_target="tpu_custom_call"``.  Host threads are the
lines of ``/host:CPU``.  Times in the trace are nanoseconds from the start
of the profile.

The window that busy and idle time are measured over runs from the first
device operation to the end of the last, not over the whole profile
(``Task Environment``'s ``profile_start_time`` to ``profile_stop_time``):
the profiler records device events only some time after it starts, so the
stretch before the first one is not known to be idle.  Its length, and
when the first program run began, are returned as ``lead_s`` and
``first_program_s`` so that a run can show it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

KERNEL = 'custom_call_target="tpu_custom_call"'
# ops whose events enclose the ops of their bodies: left out of the ranking
CONTROL_FLOW = {"while", "conditional", "call"}


def program_name(event_name: str) -> str:
    """'jit__segment_fn(1234)' -> '_segment_fn'."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion'; a kernel keeps the
    name its ``pallas_call`` gave it."""
    m = re.match(r"%?([\w\-]+?)(\.\d+)* =", event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(path: str, top: int = 10) -> Dict:
    """Busy and idle time, per-program and per-kernel device time, the
    operations that took most time and the longest idle gaps."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    profile_ns = None
    devices, host = [], []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            profile_ns = float(st["profile_stop_time"]
                               - st["profile_start_time"])
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            host = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for line in plane.lines for e in line.events]
    if profile_ns is None or not devices:
        raise ValueError(f"{path}: no traced window or no TPU device plane")

    per_device = []
    busy_ns, prog_ns, prog_runs, kern_ns = 0.0, defaultdict(float), \
        defaultdict(int), defaultdict(float)
    op_ns: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       program_name(e.name))
                      for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        for s, e, name in mods:
            prog_ns[name] += e - s
            prog_runs[name] += 1
        ops = []
        per_device.append((ops, mods))
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else []:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            ops.append((s, e))
            name = op_name(ev.name)
            if name not in CONTROL_FLOW:
                op_ns[name] += e - s
            if KERNEL in ev.name:
                kern_ns[_containing(mods, s)] += e - s
    starts = [s for ops, _ in per_device for s, _ in ops]
    if not starts:
        raise ValueError(f"{path}: no device operation in the trace")
    first = min(starts)
    last = max(e for ops, _ in per_device for _, e in ops)
    for ops, _ in per_device:
        merged = _union(ops)
        busy_ns += sum(e - s for s, e in merged)
        edges = [first] + [x for iv in merged for x in iv] + [last]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    mod_starts = [s for _, mods in per_device for s, _, _ in mods]
    return {
        "n_devices": n,
        "window_s": (last - first) * 1e-9,
        "profile_s": profile_ns * 1e-9,
        "lead_s": first * 1e-9,
        "first_program_s": min(mod_starts) * 1e-9 if mod_starts else None,
        "busy_s": busy_ns / n * 1e-9,
        "program_s": {k: v / n * 1e-9 for k, v in prog_ns.items()},
        "program_runs": {k: v // n for k, v in prog_runs.items()},
        "kernel_s": {k: v / n * 1e-9 for k, v in kern_ns.items()},
        "device_ops": [[k, v / n * 1e-9] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_name(host, s, e), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }


def _containing(mods, t: float) -> str:
    for s, e, name in mods:
        if s <= t < e:
            return name
    return "(outside any program)"


def _host_name(host, s: float, e: float) -> str:
    """The host event that covers most of [s, e), preferring the shortest
    (most specific) on ties; spans that cover the whole trace, such as
    the profiler's own, name nothing."""
    best, key = "host idle", None
    for hs, he, name in host:
        ov = min(he, e) - max(hs, s)
        if ov <= 0 or (hs <= 0 < he and he - hs > 10 * (e - s) + 1e9):
            continue
        k = (ov, -(he - hs))
        if key is None or k > key:
            best, key = name, k
    return best
