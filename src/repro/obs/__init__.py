"""Controller-wide observability: unified metrics registry, flight
recorder and host spans, gated by ``ENEL_OBS`` (default on; ``ENEL_OBS=0``
disables).

``with obs.span(kind, **attrs) as sp:`` times one call into a layer.  The
span always opens a ``jax.profiler.TraceAnnotation`` of the same name (its
attrs, and any counters ``sp.set(...)`` adds before it closes, become the
trace event's arguments), so a profiler trace shows every program span on
its own clock next to the device ops; outside a profiler session the
annotation records nothing.  With observability on, the span also observes
its host wall time into ``enel_span_seconds{span=kind}``, and a span
opened with ``_ring=True`` (one per layer call: ``enel.round``,
``enel.serve``, ``enel.decide``, ``enel.fit``, ``enel.sim_step``) is
recorded in the flight recorder with its start, end and parent; events
emitted while it is open take it as their ``parent``.  Phase and per-request spans stay
out of the ring so they cannot push causal events out of it.

Contract: with observability disabled, decisions are bit-exact vs the
uninstrumented controller and compile counts are unchanged — event and
span recording and histogram observation no-op, and the fused campaign
plan carries ``telemetry=False`` so its jaxpr is identical. No span adds
a device sync or a host transfer. Registry-backed *counters* stay live
either way: they are host-side and feed no decision, and existing
attribute APIs (``service.retries`` etc.) must keep working regardless of
the flag.
"""
from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

from .metrics import (DEFAULT_LATENCY_BUCKETS, CounterSeries, GaugeSeries,
                      HistogramSeries, Metric, MetricsRegistry)
from .recorder import FlightRecorder

_ENABLED = os.environ.get("ENEL_OBS", "1").lower() in ("1", "true", "yes")

REGISTRY = MetricsRegistry()
RECORDER = FlightRecorder(capacity=int(os.environ.get("ENEL_OBS_RING", "4096")),
                          gate=lambda: _ENABLED)


def enabled(override: Optional[bool] = None) -> bool:
    return _ENABLED if override is None else bool(override)


def set_enabled(value: bool) -> bool:
    """Flip the gate; returns the previous value (for try/finally)."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(value)
    return prev


@contextmanager
def obs_enabled(value: bool = True):
    prev = set_enabled(value)
    try:
        yield
    finally:
        set_enabled(prev)


def registry() -> MetricsRegistry:
    return REGISTRY


def recorder() -> FlightRecorder:
    return RECORDER


def emit(_kind: str, _ts: Optional[float] = None, **attrs) -> int:
    """Emit a point event into the global flight recorder, parented to the
    innermost open ring span of this thread (no-op when gated)."""
    return RECORDER.emit(_kind, _ts=_ts, _parent=current_span(), **attrs)


_LOCAL = threading.local()


def _open_spans() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def current_span() -> int:
    """Seq of this thread's innermost open ring span (-1: none)."""
    stack = _open_spans()
    return stack[-1] if stack else -1


class Span:
    """One open span; see :func:`span`."""

    __slots__ = ("kind", "attrs", "ring", "seq", "_ann", "_entry", "_on",
                 "_t0")

    def __init__(self, kind: str, ring: bool, attrs: Dict):
        self.kind = kind
        self.ring = ring
        self.attrs = attrs
        self.seq = -1
        self._entry = None

    def set(self, **counters) -> None:
        """Add counters known only at the end (memo hits, bytes...)."""
        self.attrs.update(counters)
        self._ann.set_metadata(**counters)

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self.kind, **self.attrs)
        self._ann.__enter__()
        self._on = _ENABLED
        if self._on and self.ring:
            stack = _open_spans()
            self._entry = RECORDER.open_span(
                self.kind, time.time(), stack[-1] if stack else -1,
                self.attrs)
            self.seq = self._entry["seq"]
            stack.append(self.seq)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._on:
            if self._entry is not None:
                self._entry["end"] = self._entry["start"] + dt
                _open_spans().pop()
            REGISTRY.histogram(
                "enel_span_seconds", "host wall time of each obs.span"
            ).labels(span=self.kind).observe(dt)
        self._ann.__exit__(*exc)


def span(_kind: str, _ring: bool = False, **attrs) -> Span:
    """Context manager timing one call into a layer (module docstring).
    ``_ring=True`` keeps the span in the flight recorder."""
    return Span(_kind, _ring, attrs)


def observe(name: str, value: float, **labels) -> None:
    """Observe ``value`` into histogram ``name`` (no-op when disabled)."""
    if _ENABLED:
        REGISTRY.histogram(name).labels(**labels).observe(value)


def snapshot() -> Dict:
    """Combined pickle-safe obs state for campaign checkpoints."""
    return {"metrics": REGISTRY.snapshot(), "recorder": RECORDER.state()}


def restore(state: Optional[Dict]) -> None:
    if not state:
        return
    REGISTRY.restore(state.get("metrics", {}))
    if "recorder" in state:
        RECORDER.load(state["recorder"])


def reset() -> None:
    """Clear all global obs state (test isolation)."""
    REGISTRY.reset()
    RECORDER.clear()
