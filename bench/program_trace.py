"""Reduce the program's own spans and scopes in a JAX profiler trace.

The program names its layers in the trace itself: every ``obs.span`` opens
a ``TraceAnnotation`` whose name starts ``enel.`` (its counters are the
event's arguments), and the fused scan puts the four parts of a step under
``jax.named_scope`` (``enel.sim``, ``enel.ring``, ``enel.sweep``,
``enel.fit``), which XLA keeps in each instruction's ``op_name`` metadata.
``collect(path, out)`` reads one ``.xplane.pb`` into the event dict
``trace_reduce.load_events`` returns:

    out["program"]: [[span, start_ns, dur_ns, line, {arg: value}], ...]
    out["scopes"]:  {plane: [[scope, start_ns, dur_ns], ...]}

``scopes`` holds the ops of a device plane's "XLA Ops" line whose
instruction's name stack has an ``enel.`` part (the innermost such part
names the op's scope).  On a v5e an op event carries no name stack (its
only stats are its device offset, duration and time scale): ``collect``
finds the op's module from the "XLA Modules" line it runs in, and the
instruction's ``op_name`` in that module's HLO proto, which the trace keeps
in its ``/host:metadata`` plane (``bench/tests/fixtures/
tpu_trace_spans.json``, recorded on the chip).  ``reduce_program(ev)``
turns them, within the ``bench.window`` span, into

* ``span_s`` / ``self_s``: each span kind's time, and its self time (its
  duration less what its child program spans on the same line cover);
* ``child_self_ms``: ``"<parent>><child>"`` -> per parent span, the summed
  self time of its direct children of that kind (0 where it has none);
* ``span_args``: each kind's numeric arguments, summed;
* ``program_gaps``: the first device's idle time charged to the innermost
  program span covering it (``"-"`` where none does), and ``idle_named``,
  the share of idle time some program span covers;
* ``scope_device_s``: device time per scope, the union of its ops'
  intervals (nested ops count once), averaged over the devices.

``layer_metrics(red, units)`` gives the per-layer numbers these readings
make, under the names the benchmark would report them by.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from trace_reduce import OPS_LINE, WINDOW, _union, op_name

PREFIX = "enel."
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"


def op_scope(name_stack: str) -> Optional[str]:
    """The innermost ``enel.`` part of an op's name stack, or ``None``."""
    for part in reversed(name_stack.split("/")):
        if part.startswith(PREFIX):
            return part
    return None


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message, in wire order;
    length-delimited values stay bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} not read")
        yield key >> 3, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _sub(msg: bytes, field: int) -> List[bytes]:
    return [v for f, v in _fields(msg) if f == field]


def hlo_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{module: {"%instruction": scope}} from the HLO protos of a
    serialized XSpace (XSpace.planes 1; XPlane.name 2, .event_metadata 4;
    XEventMetadata.name 2, .stats 5; XStat.bytes_value 6; HloProto
    .hlo_module 1; HloModuleProto.computations 3; HloComputationProto
    .instructions 2; HloInstructionProto.name 1, .metadata 7;
    OpMetadata.op_name 2)."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(xspace, 1):
        if _sub(plane, 2) != [METADATA_PLANE.encode()]:
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                module = b"".join(_sub(meta, 2)).decode()
                names = out.setdefault(module, {})
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):
                        for mod in _sub(proto, 1):
                            _scope_instructions(mod, names)
    return {m: n for m, n in out.items() if n}


def _scope_instructions(module: bytes, names: Dict[str, str]) -> None:
    for comp in _sub(module, 3):
        for ins in _sub(comp, 2):
            for meta in _sub(ins, 7):
                for stack in _sub(meta, 2):
                    scope = op_scope(stack.decode())
                    if scope is not None:
                        names["%" + b"".join(_sub(ins, 1)).decode()] = scope


def collect(path: str, out: Dict) -> None:
    """Add one ``.xplane.pb``'s program spans and scoped device ops."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        scopes = hlo_scopes(f.read())
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and scopes:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines or MODULES_LINE not in lines:
                continue
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name) for e in lines[MODULES_LINE].events)
            starts = [m[0] for m in mods]
            scoped = []
            for e in lines[OPS_LINE].events:
                k = bisect.bisect_right(starts, int(e.start_ns)) - 1
                if k < 0 or e.start_ns > mods[k][1]:
                    continue
                scope = scopes.get(mods[k][2], {}).get(op_name(e.name))
                if scope is not None:
                    scoped.append([scope, int(e.start_ns),
                                   int(e.duration_ns)])
            if scoped:
                out.setdefault("scopes", {}).setdefault(
                    plane.name, []).extend(scoped)
        elif plane.name.startswith("/host:"):
            prog = out.setdefault("program", [])
            for n, ln in enumerate(plane.lines):
                line = f"{plane.name}/{n}"
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        prog.append([e.name, int(e.start_ns),
                                     int(e.duration_ns), line,
                                     dict(e.stats)])


def _clip(s: int, e: int, w0: int, w1: int) -> int:
    return max(0, min(e, w1) - max(s, w0))


def _nest(program: List) -> List[Optional[int]]:
    """Index of each span's direct parent on its line (``None``: top)."""
    parent: List[Optional[int]] = [None] * len(program)
    by_line: Dict[str, List[int]] = defaultdict(list)
    for i, ev in enumerate(program):
        by_line[ev[3]].append(i)
    for idxs in by_line.values():
        idxs.sort(key=lambda i: (program[i][1], -program[i][2]))
        stack: List[int] = []
        for i in idxs:
            s = program[i][1]
            while stack and program[stack[-1]][1] + program[stack[-1]][2] \
                    <= s:
                stack.pop()
            parent[i] = stack[-1] if stack else None
            stack.append(i)
    return parent


def _innermost(program: List, w0: int, w1: int
               ) -> List[Tuple[int, int, str]]:
    """Piecewise segments (start, end, kind) of the shortest program span
    covering each instant of the window; uncovered instants are left out."""
    points = []
    for i, (name, s, d, _, _) in enumerate(program):
        s0, e0 = max(s, w0), min(s + d, w1)
        if e0 > s0:
            points.append((s0, 1, i))
            points.append((e0, 0, i))
    points.sort()
    active: Dict[int, Tuple[int, str]] = {}
    segs: List[Tuple[int, int, str]] = []
    last = None
    for t, opening, i in points:
        if active and last is not None and t > last:
            kind = min(active.values())[1]
            if segs and segs[-1][2] == kind and segs[-1][1] == last:
                segs[-1] = (segs[-1][0], t, kind)
            else:
                segs.append((last, t, kind))
        if opening:
            active[i] = (program[i][2], program[i][0])
        else:
            active.pop(i, None)
        last = t
    return segs


def reduce_program(ev: Dict) -> Dict:
    """Program spans and scopes of ``ev`` within its window (module doc)."""
    wins = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
    if not wins:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = wins[0]
    program = ev.get("program", [])
    parent = _nest(program)
    child_cover = [0] * len(program)
    for i, p in enumerate(parent):
        if p is not None:
            child_cover[p] += _clip(program[i][1], program[i][1]
                                    + program[i][2], w0, w1)
    span_s: Dict[str, float] = defaultdict(float)
    self_s: Dict[str, float] = defaultdict(float)
    args: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    own = [0] * len(program)
    for i, (name, s, d, _, a) in enumerate(program):
        inside = _clip(s, s + d, w0, w1)
        if inside <= 0:
            continue
        own[i] = inside - child_cover[i]
        span_s[name] += inside / 1e9
        self_s[name] += own[i] / 1e9
        for k, v in a.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                args[name][k] += v
    per_parent: Dict[int, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    child_kinds: Dict[str, set] = defaultdict(set)
    for i, p in enumerate(parent):
        if p is not None and _clip(program[p][1], program[p][1]
                                   + program[p][2], w0, w1) > 0:
            per_parent[p][program[i][0]] += own[i] / 1e6
            child_kinds[program[p][0]].add(program[i][0])
    child_self_ms: Dict[str, List[float]] = {}
    for pkind, kinds in child_kinds.items():
        calls = [i for i, ev_ in enumerate(program) if ev_[0] == pkind
                 and _clip(ev_[1], ev_[1] + ev_[2], w0, w1) > 0]
        for ckind in kinds:
            child_self_ms[f"{pkind}>{ckind}"] = [
                per_parent[i].get(ckind, 0.0) for i in calls]

    devices = sorted(ev["devices"])
    busy = _union([(max(s, w0), min(s + d, w1))
                   for _, s, d in ev["devices"][devices[0]]
                   if min(s + d, w1) > max(s, w0)]) if devices else []
    idle, cursor = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)
    gaps: Dict[str, float] = defaultdict(float)
    segs = _innermost(program, w0, w1)
    j = 0
    for a0, a1 in idle:
        covered = 0
        while j < len(segs) and segs[j][1] <= a0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < a1:
            part = _clip(segs[k][0], segs[k][1], a0, a1)
            gaps[segs[k][2]] += part / 1e9
            covered += part
            k += 1
        if a1 - a0 > covered:
            gaps["-"] += (a1 - a0 - covered) / 1e9
    idle_total = sum(e - s for s, e in idle) / 1e9
    named = sum(v for k, v in gaps.items() if k != "-")

    scope_s: Dict[str, float] = defaultdict(float)
    for dev in devices:
        by_scope: Dict[str, list] = defaultdict(list)
        for scope, s, d in ev.get("scopes", {}).get(dev, []):
            if min(s + d, w1) > max(s, w0):
                by_scope[scope].append((max(s, w0), min(s + d, w1)))
        for scope, iv in by_scope.items():
            scope_s[scope] += sum(e - s for s, e in _union(iv)) / 1e9
    n_dev = max(len(devices), 1)
    return {"span_s": dict(span_s), "self_s": dict(self_s),
            "child_self_ms": child_self_ms,
            "span_args": {k: dict(v) for k, v in args.items()},
            "program_gaps": sorted(([k, v] for k, v in gaps.items()),
                                   key=lambda kv: -kv[1]),
            "idle_named": named / idle_total if idle_total > 0 else None,
            "scope_device_s": {k: v / n_dev for k, v in scope_s.items()}}


def _median(values) -> Optional[float]:
    return float(np.median(values)) if len(values) else None


def layer_metrics(red: Dict, units: int) -> Dict[str, float]:
    """Per-layer numbers of a reduced trace (``None`` where it holds no
    such span or scope): the live path's per-call phase times in ms,
    stack-memo hits and named idle in %, and the fused scan's device
    seconds per scope and campaign (``units`` campaigns in the window)."""
    child = red["child_self_ms"]
    out = {}
    for name, key in (
            ("decide_stack_ms.live", "enel.decide>enel.decide.stack"),
            ("decide_launch_ms.live", "enel.decide>enel.decide.launch"),
            ("decide_fetch_ms.live", "enel.decide>enel.decide.fetch"),
            ("prep_build_ms.live", "enel.prep>enel.prep.build"),
            ("prep_adopt_ms.live", "enel.prep>enel.prep.adopt"),
            ("resume_ms.live", "enel.round>enel.resume")):
        out[name] = _median(child.get(key, []))
    stack = red["span_args"].get("enel.decide.stack", {})
    looked = stack.get("hits", 0) + stack.get("misses", 0)
    out["stack_memo_hit.live"] = (100.0 * stack["hits"] / looked
                                  if looked else None)
    named = red["idle_named"]
    out["idle_named.live"] = None if named is None else 100.0 * named
    scope = red["scope_device_s"]
    for name, key in (("fit_device_s.fused", "enel.fit"),
                      ("sweep_device_s.fused", "enel.sweep"),
                      ("sim_device_s.fused", "enel.sim")):
        out[name] = scope[key] / units if key in scope and units else None
    return out
