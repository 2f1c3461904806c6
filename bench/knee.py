"""Find the knee of an open-arrival cell: the highest steady Poisson rate the
system sustains.

    python bench/knee.py --workload arrivals-1k --seed 7

One set-up of the cell, then steady Poisson schedules (no bursts) of
``TRIAL_S`` seconds each, one per rate: the rate doubles from ``START``
while the system sustains it, then climbs from the last rate sustained in
steps of ``STEP`` until it fails.  A rate is sustained when the backlog
left as arrivals stop drains within ``DRAIN_S`` and at least ``COMPLETE``
of the offered requests are applied within the schedule: the knee's
definition, fixed here.
One JSON line per rate (with the jit traces and compiles it caused, which
should be none) goes to standard output and to ``--out``; the last line
names the knee.  Needs the accelerator, like the benchmark; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

START = 16.0        # decisions/s of the first trial
STEP = 0.10         # the climb's step past the last doubling sustained
TRIAL_S = 10.0      # seconds of arrivals a trial
DRAIN_S = 1.0       # the backlog left when arrivals stop drains within
COMPLETE = 0.99     # share of the offered requests applied within a trial


def trial(drv, rate: float, seconds: float) -> dict:
    """Serve one steady schedule at ``rate``; what became of it."""
    arrivals = drv._unit(seconds, rate, burst=False)
    end = drv.last_start + seconds
    due = np.array([a.due for a in arrivals])
    applied = np.array([a.applied for a in arrivals])
    lat = applied - due
    wait = np.array([a.taken for a in arrivals]) - due
    return {"rate": rate, "offered": len(arrivals),
            "in_window": float(np.mean(applied <= end)),
            "drain_s": float(max(0.0, applied.max() - end)),
            "p50_ms": float(np.quantile(lat, 0.5) * 1e3),
            "p95_ms": float(np.quantile(lat, 0.95) * 1e3),
            "wait_p95_ms": float(np.quantile(wait, 0.95) * 1e3),
            "batch_mean": float(np.mean([a.batch for a in arrivals]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="arrivals-1k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    devs = harness.start_program()
    if devs is None or devs[0].platform != "tpu":
        print("knee: needs the accelerator", file=sys.stderr)
        return 3
    files = harness.cell_files(bench, cell)
    drv = harness.driver_module(files["traffic"]["driver"]).Driver(
        files["config"], files["traffic"], args.seed, harness.Spans(False))
    drv.setup()
    print("setup: " + json.dumps(drv.setup_parts), flush=True)
    from run import _CompileCounter
    counter = _CompileCounter()

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    def sustained(rate):
        counter.armed, counter.traces, counter.compiles = True, 0, 0
        row = trial(drv, rate, TRIAL_S)
        counter.armed = False
        row["compiles"] = counter.traces + counter.compiles
        row["sustained"] = (row["drain_s"] <= DRAIN_S
                            and row["in_window"] >= COMPLETE)
        emit(row)
        return row["sustained"]

    rate, best = START, None
    while sustained(rate):
        best, rate = rate, rate * 2
    if best is not None:
        rate = best * (1 + STEP)
        while sustained(rate):
            best, rate = rate, rate * (1 + STEP)
    emit({"workload": cell["name"], "seed": args.seed, "knee": best,
          "step": STEP, "seconds": TRIAL_S})
    return 0


if __name__ == "__main__":
    sys.exit(main())
