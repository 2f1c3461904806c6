"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload fused-256 --seed 7 --seconds 30 --trace 0

Set-up (profiling, plan build, warm-up of every shape the window uses) is
``setup_s``; the window then repeats the cell's unit of work (a whole fused
campaign, or one lockstep run of the live fleet) while ``--seconds`` have
not yet passed, and ends with the last whole unit.  ``--trace 1`` records
the window with the JAX profiler and reports the per-layer metrics instead
of the end-to-end ones.  After the window the cell's own outputs are
compared with the plain reference under ``bench/reference``; the last
lines on standard error and the ``checks`` key of the result give each
number compared beside its limit.

The run needs the accelerator: where JAX finds none, or fewer chips than
the cell asks for, or the program's ``src/`` is not beside this directory,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class _CompileCounter:
    """Counts jit traces and backend compiles JAX reports while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _duration, **_kw):
        if not self.armed:
            return
        if name.endswith("jaxpr_trace_duration"):
            self.traces += 1
        elif name.endswith("backend_compile_duration"):
            self.compiles += 1


def _finite(x):
    return x if x is None or math.isfinite(x) else None


def execute(bench, cell, seed: int, seconds: float, trace: bool,
            t_start: float, devices, overrides=None) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result
    dict.  ``overrides`` (tests only) replaces keys of the config/traffic."""
    import jax
    from repro.core import model as enel_model
    from trace_reduce import load_events, reduce_events

    files = harness.cell_files(bench, cell)
    for part, extra in (overrides or {}).items():
        files[part] = dict(files[part], **extra)
    spans = harness.Spans(trace)
    drv = harness.driver_module(files["traffic"]["driver"]).Driver(
        files["config"], files["traffic"], seed, spans)
    print("flags: ENEL_OBS=%s ENEL_GRAPH_PROP_KERNEL=%s" % (
        os.environ.get("ENEL_OBS", "unset"),
        os.environ.get("ENEL_GRAPH_PROP_KERNEL", "unset")), flush=True)
    counter = _CompileCounter()
    drv.setup()

    run = harness.RunData(cell["name"])
    logdir = None
    traces0 = sum(enel_model.TRACE_COUNTS.values())
    drv.start_window()
    run.setup_s = time.perf_counter() - t_start
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(logdir)
    counter.armed = True
    with spans.span("window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with spans.span("unit"):
                run.decisions += drv.unit()
            run.units += 1
        run.window_s = time.perf_counter() - t0
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
        run.trace = reduce_events(load_events(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
    program_traces = sum(enel_model.TRACE_COUNTS.values()) - traces0
    print(f"window: {run.units} units, {run.decisions} decisions, "
          f"{run.window_s:.3f} s; program jit traces {program_traces}, "
          f"jax traces {counter.traces}, backend compiles "
          f"{counter.compiles}", flush=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]

    run.latencies_s = drv.latencies
    run.spans = dict(spans.durations)
    run.flops_per_unit = drv.flops_per_unit
    kind = devices[0].device_kind
    peak_table = harness.load_json(harness.BENCH_DIR, "peaks.json")
    if devices[0].platform != "cpu":
        if kind not in peak_table:
            raise KeyError(f"no peak figures for device kind {kind!r}")
        run.peak = peak_table[kind]
    print("setup: " + json.dumps(drv.setup_parts), flush=True)

    nums = drv.check()
    print("record: " + json.dumps(drv.record), flush=True)
    nums["window_traces"] = float(program_traces + counter.traces
                                  + counter.compiles)
    checks = {k: (v, files["limits"][k]) for k, v in nums.items()}
    correct = all(v is not None and math.isfinite(v) and v <= lim
                  for v, lim in checks.values())

    kind_key = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in harness.cell_metrics(bench, cell["name"], kind_key):
        value = harness.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(max(peaks))}
    out = {"correct": bool(correct), "attempted": int(drv.attempted),
           "failed": int(drv.failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {k: {"value": _finite(v), "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    devs = harness.start_program()
    if devs is None:
        print("bench: the program's src/ is not beside bench/",
              file=sys.stderr)
        return 2
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} "
              "device(s)", file=sys.stderr)
        return 3
    out = execute(bench, cell, args.seed, args.seconds, bool(args.trace),
                  t_start, devs[:cell["chips"]])
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} <= {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
