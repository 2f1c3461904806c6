"""Read a cell's compared numbers for the program and for the control.

    python bench/reference/control.py --workload fused-256 --seeds 11,12,13
    python bench/reference/control.py --workload fused-256 --seeds 11,12,13 \
        --fault half_batch

For each seed: the cell's set-up, one unit of its window, then every number
``correct`` compares, once for the program and once with the control put
in the program's place: the plain reference with the operands of its
matrix products rounded to float8, the precision step below the bfloat16
operands of the TPU's default products that the configuration states.
The control has to fail a limit; the program's readings over many seeds
set the lower end of each limit.  One JSON line per seed goes to standard
output and to ``--out``.  With ``--fault`` one of ``checks/faults.py``'s
faults is planted under the program first, so the "program" numbers are
the broken program's.  Needs the accelerator, like the benchmark; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    devs = harness.start_program()
    if devs is None or devs[0].platform != "tpu" or \
            len(devs) < cell["chips"]:
        print("control: needs the accelerator", file=sys.stderr)
        return 3
    files = harness.cell_files(bench, cell)
    limits = files["limits"]
    if args.fault:
        from checks import faults
        faults.plant(files["traffic"]["driver"], args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = harness.driver_module(files["traffic"]["driver"]).Driver(
            files["config"], files["traffic"], seed, harness.Spans(False))
        drv.setup()
        drv.start_window()
        drv.unit()
        prog = drv.check()
        ctrl = {} if args.fault else drv.control()
        fails = sorted(k for k, v in ctrl.items()
                       if not (v is not None and v <= limits[k]))
        line = json.dumps({"workload": cell["name"], "seed": seed,
                           "fault": args.fault or None,
                           "program": prog, "control": ctrl,
                           "control_fails": fails, "record": drv.record,
                           "seconds": time.perf_counter() - t0})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
