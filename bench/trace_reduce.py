"""Reduce a JAX profiler trace to busy time, idle gaps and top device ops.

``load_events`` reads the ``.xplane.pb`` a ``jax.profiler.trace`` wrote
into a plain dict (kept as a test fixture):

    {"devices": {plane: [[op, start_ns, dur_ns], ...]},
     "host": [[span, start_ns, dur_ns], ...]}

with the ops of the "XLA Ops" line of each device plane that has one (a
TPU's; a plane without that line, such as the Megascale one, is no chip)
and the host spans the benchmark opened with ``TraceAnnotation`` (names
starting ``bench.``).  ``reduce_events`` takes the ``bench.window`` span
as the window: busy is the union of op intervals inside it, averaged over
the devices; each idle gap of the first device is charged to the
innermost benchmark span that covers its midpoint.  On a v5e the device
plane's clock runs about a millisecond behind the host's (ops start that
long before the host span that dispatched them), which moves a gap's
charge only where the gap is that short.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def op_name(hlo: str) -> str:
    """An op event's name is its HLO instruction; keep the part before
    ``=``, such as ``%fusion.12``."""
    return hlo.split(" = ", 1)[0].strip()


def load_events(logdir: str) -> Dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    out = {"devices": {}, "host": []}
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if plane.name.startswith("/device:") and lines:
                evs = out["devices"].setdefault(plane.name, [])
                for ln in lines:
                    evs.extend([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)] for e in ln.events)
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    out["host"].extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in ln.events
                        if e.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce_events(ev: Dict, top: int = 10) -> Dict:
    """-> {"window_s", "busy_s", "device_ops", "idle_gaps", "n_devices"}."""
    wins = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
    if not wins:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = wins[0]
    devices = sorted(ev["devices"])
    if not devices:
        raise ValueError("trace holds no accelerator plane")
    busy_total = 0
    op_time: Dict[str, float] = defaultdict(float)
    first_busy: List[Tuple[int, int]] = []
    for i, dev in enumerate(devices):
        clipped = []
        for name, s, d in ev["devices"][dev]:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                clipped.append((s0, e0))
                op_time[name] += (e0 - s0) / 1e9
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            first_busy = busy
    if busy_total == 0:
        raise ValueError("no device operation inside the window")
    n_dev = len(devices)
    spans =[(n, s, s + d) for n, s, d in ev["host"] if n != WINDOW]
    gaps: Dict[str, float] = defaultdict(float)
    cursor = w0
    for s, e in first_busy + [(w1, w1)]:
        if s > cursor:
            mid = (cursor + s) / 2
            covering = [(e2 - s2, n) for n, s2, e2 in spans
                        if s2 <= mid <= e2]
            name = min(covering)[1] if covering else WINDOW
            gaps[name] += (s - cursor) / 1e9
        cursor = max(cursor, e)
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": busy_total / n_dev / 1e9,
            "n_devices": n_dev,
            "device_ops": rank({k: v / n_dev for k, v in op_time.items()}),
            "idle_gaps": rank(gaps)}
