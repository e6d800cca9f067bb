"""From a profiler trace to device busy/idle time, per-op time, the step
program's device time and idle gaps attributed to the host's spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX alone.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds the
operations and their ``XLA Modules`` line the compiled programs.  Host
spans are the benchmark's ``TraceAnnotation`` events on the host plane.
All times are in seconds, on the trace's common clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from jax.profiler import ProfileData

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load(trace_dir: str) -> ProfileData:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def _line_events(plane, line_name: str) -> List[Tuple[str, float, float]]:
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events]
    return out


def leaves(ops: List[Tuple[str, float, float]]) -> list:
    """The ops that hold no other op: a loop or a call op on the line
    spans the ops it runs, and counting both would count twice."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= e[2]]


def short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.3 = f32[..] ...`` ->
    ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_planes(pd: ProfileData) -> list:
    return [p for p in pd.planes if DEVICE_PLANE.match(p.name)]


def host_spans(pd: ProfileData, prefix: str = "bench.") -> list:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events if e.name.startswith(prefix)]
    return sorted(out, key=lambda s: s[1])


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(merged: List[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in merged
            if b > t0 and a < t1]


def gaps(merged: List[Interval], t0: float, t1: float) -> List[Interval]:
    out, t = [], t0
    for a, b in clip(merged, t0, t1):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < t1:
        out.append((t, t1))
    return out


def attribute(gap: Interval, spans: list) -> str:
    """The innermost host span that covers the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best: Optional[tuple] = None
    for name, a, b in spans:
        if a <= mid <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "no span"


def summarize(pd: ProfileData, *, stretch_span: str,
              module_match: str) -> Dict:
    """Busy/idle over the stretch from the first to the last host span
    named ``stretch_span``, averaged over the device planes; the top ops;
    the longest idle gaps by host span; mean device time of the compiled
    programs whose name contains ``module_match``."""
    spans = host_spans(pd)
    marks = [s for s in spans if s[0] == stretch_span]
    planes = device_planes(pd)
    if not marks or not planes:
        return {}
    t0, t1 = marks[0][1], marks[-1][2]
    busy, op_time = [], collections.Counter()
    idle: List[Tuple[str, float]] = []
    mod_durs: List[float] = []
    for plane in planes:
        ops = _line_events(plane, "XLA Ops")
        merged = union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in clip(merged, t0, t1)))
        for name, a, b in leaves(ops):
            if a >= t0 and b <= t1:
                op_time[short(name)] += b - a
        idle += [(attribute(g, spans), g[1] - g[0])
                 for g in gaps(merged, t0, t1)]
        mod_durs += [b - a for name, a, b in _line_events(plane,
                                                          "XLA Modules")
                     if module_match in name and a >= t0 and b <= t1]
    n = len(planes)
    window = t1 - t0
    return {
        "window_s": window,
        "busy_s": sum(busy) / n,
        "idle_share": 1.0 - sum(busy) / n / window,
        "module_s": (sum(mod_durs) / len(mod_durs)) if mod_durs else None,
        "module_count": len(mod_durs) / n,
        "device_ops": [[k, v / n] for k, v in op_time.most_common(10)],
        "idle_gaps": [[k, v] for k, v in
                      sorted(idle, key=lambda g: -g[1])[:10]],
    }
