"""The program's own spans in a traced run's profiler trace.

``trace_reduce`` reads the trace by XLA program and by the client's
``bench.*`` spans.  The program writes more into the same trace (DESIGN.md
§12.2): while a profiler session records, each ``repro.obs.trace.span``
becomes a host event named ``repro.<span>``, on the clock of the device's
ops.  This module puts every stretch of the measured window
(``bench.window``) in which no op ran on the device, computed as
``trace_reduce`` computes it (op intervals clipped to the window and
merged with its own functions), down to the innermost ``repro.*`` span
that covers the stretch's midpoint, and where none does, to the innermost
``bench.*`` span (the window itself, at worst).  So the owners' seconds
add up to the window's idle time.

A trace of a program that writes no ``repro.*`` span, as before these
spans existed, gives no owners, and the readers then report nothing.  The
newest trace under ``harness.TRACE_DIR`` is read once and kept, for the
readers of one run share it.
"""
from __future__ import annotations

import bisect
import functools
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import harness, trace_reduce

__all__ = ["newest", "reduce_profile", "idle_ms", "PROGRAM_PREFIX",
           "RUNG_PICK"]

PROGRAM_PREFIX = "repro."
RUNG_PICK = PROGRAM_PREFIX + "rung_pick"     # the span of the rung-pick read

Span = Tuple[str, float, float]


def _innermost(spans: List[Span]):
    """A function of a time: the name of the shortest span covering it."""
    if not spans:
        return lambda t: None
    edges = sorted({t for _, lo, hi in spans for t in (lo, hi)})
    by_length = sorted(spans, key=lambda s: s[2] - s[1])
    names = [next((n for n, lo, hi in by_length if lo <= mid <= hi), None)
             for mid in ((a + b) / 2 for a, b in zip(edges, edges[1:]))]

    def at(t: float) -> Optional[str]:
        i = bisect.bisect_right(edges, t) - 1
        return names[i] if 0 <= i < len(names) else None
    return at


def reduce_profile(profile) -> Optional[dict]:
    """The window's idle time by owner span in a ``ProfileData``; None
    without a window span.

    Returns ``window_s`` and ``idle``, ``{owner span: idle seconds}``
    averaged over the device planes, or None where the trace holds no
    ``repro.*`` span.
    """
    program: List[Span] = []
    client: List[Span] = []
    device_planes = []
    for plane in profile.planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for span in trace_reduce._events(line):
                if span[0].startswith(PROGRAM_PREFIX):
                    program.append(span)
                elif span[0].startswith(trace_reduce.SPAN_PREFIX):
                    client.append(span)
    windows = [(lo, hi) for name, lo, hi in client
               if name == trace_reduce.WINDOW_SPAN]
    if not windows:
        return None
    window = max(windows, key=lambda w: w[1] - w[0])
    in_program, in_client = _innermost(program), _innermost(client)

    idle_ns: Dict[str, float] = defaultdict(float)
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        busy = [cut for cut in (trace_reduce._clip(lo, hi, window)
                                for _, lo, hi in (trace_reduce._events(op_line)
                                                  if op_line else ()))
                if cut]
        merged = trace_reduce._merge(busy)
        edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                mid = (lo + hi) / 2
                owner = in_program(mid) or in_client(mid) or "host"
                idle_ns[owner] += hi - lo
    count = max(1, len(device_planes))
    return {"window_s": (window[1] - window[0]) * 1e-9,
            "idle": {k: v * 1e-9 / count for k, v in idle_ns.items()}
            if program else None}


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float) -> Optional[dict]:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def newest() -> Optional[dict]:
    """``reduce_profile`` of the newest trace under ``harness.TRACE_DIR``,
    read once per file; None where there is none."""
    path = trace_reduce.find_trace(harness.TRACE_DIR)
    return _read(path, os.path.getmtime(path)) if path else None


def idle_ms(run, trace, owns) -> Optional[float]:
    """Device idle per batch, in ms, under the owner spans that ``owns``
    accepts; None without a trace, or where it holds no ``repro.*`` span."""
    if not trace or not run.get("batches"):
        return None
    found = newest()
    if not found or found["idle"] is None:
        return None
    seconds = sum(s for owner, s in found["idle"].items() if owns(owner))
    return seconds / run["batches"] * 1e3
