"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else:

* device planes are those named ``/device:TPU:<n>``; on each, the
  ``XLA Ops`` line gives the intervals in which an operation ran and the
  ``XLA Modules`` line the executions of each jitted program (its name is
  the program's, e.g. ``jit_probe_index``, before the ``(<id>)`` suffix);
  an op is named by its HLO name and result shape, under its program;
* the measured window is the host span ``bench.window`` that the harness
  writes with ``jax.profiler.TraceAnnotation``; every device number is
  clipped to it;
* busy time is the union of the op intervals in the window, averaged over
  the device planes; an idle gap is a stretch of the window with no op
  running, and it is put down to the innermost ``bench.*`` host span that
  covers its midpoint (``host`` where none does).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

__all__ = ["reduce_profile", "reduce_file", "find_trace", "breakdown",
           "program_name"]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

Interval = Tuple[float, float]


def program_name(event_name: str) -> str:
    """``jit_probe_index(1234)`` -> ``jit_probe_index``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An op event's HLO text -> its name and result shape.

    ``%fusion.127 = s32[65536,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.127 s32[65536,128]``; a tuple result reads ``(tuple)``.
    """
    m = re.match(r"%?(\S+) = (\S+)", event_name)
    if not m:
        return event_name
    shape = m.group(2)
    shape = "(tuple)" if shape.startswith("(") else shape.split("{")[0]
    return f"{m.group(1)} {shape}"


def find_trace(trace_dir: str) -> Optional[str]:
    """Newest ``.xplane.pb`` under ``trace_dir``, or None."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _clip(lo: float, hi: float, window: Interval) -> Optional[Interval]:
    lo, hi = max(lo, window[0]), min(hi, window[1])
    return (lo, hi) if hi > lo else None


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce_profile(profile) -> Optional[dict]:
    """Summary of a ``ProfileData``; None when it has no window span.

    Returns, times in seconds::

        window_s, busy_s, devices,
        programs: {name: {"seconds", "count"}}  (device time per program),
        ops: {"program/op": seconds}            (device time per op),
        gaps: {host span: idle seconds}
    """
    spans: List[Tuple[str, float, float]] = []
    device_planes = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            spans.extend(s for s in _events(line)
                         if s[0].startswith(SPAN_PREFIX))
    windows = [(lo, hi) for name, lo, hi in spans if name == WINDOW_SPAN]
    if not windows:
        return None
    window = max(windows, key=lambda w: w[1] - w[0])
    window_ns = window[1] - window[0]

    programs: Dict[str, dict] = defaultdict(lambda: {"seconds": 0.0,
                                                     "count": 0})
    ops: Dict[str, float] = defaultdict(float)
    busy_ns, gaps_ns = 0.0, defaultdict(float)
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[2] - s[1])
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        modules = []
        for name, lo, hi in (_events(lines["XLA Modules"])
                             if "XLA Modules" in lines else ()):
            cut = _clip(lo, hi, window)
            if cut:
                prog = programs[program_name(name)]
                prog["seconds"] += (cut[1] - cut[0]) * 1e-9
                prog["count"] += 1
                modules.append((lo, hi, program_name(name)))
        modules.sort()
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        busy: List[Interval] = []
        for name, lo, hi in (_events(op_line) if op_line else ()):
            cut = _clip(lo, hi, window)
            if not cut:
                continue
            busy.append(cut)
            owner = next((m for a, b, m in modules if a <= lo and hi <= b),
                         None)
            name = op_name(name)
            ops[f"{owner}/{name}" if owner else name] += (
                (cut[1] - cut[0]) * 1e-9)
        merged = _merge(busy)
        busy_ns += sum(hi - lo for lo, hi in merged)
        edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi <= lo:
                continue
            mid = (lo + hi) / 2
            owner = next((n for n, a, b in inner if a <= mid <= b), "host")
            gaps_ns[owner] += hi - lo
    count = max(1, len(device_planes))
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9 / count,
        "devices": len(device_planes),
        "programs": {k: dict(v) for k, v in programs.items()},
        "ops": dict(ops),
        "gaps": {k: v * 1e-9 / count for k, v in gaps_ns.items()},
    }


def reduce_file(path: str) -> Optional[dict]:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def breakdown(summary: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    def largest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]
    return {"device_ops": largest(summary["ops"]),
            "idle_gaps": largest(summary["gaps"])}
