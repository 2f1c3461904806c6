"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  The heavyweight Enel-vs-Ellis
campaign (Table III / Fig. 4) runs at reduced scale here by default and is
cached under artifacts/experiments; the full 55-run campaign used for
EXPERIMENTS.md is produced by ``python -m benchmarks.table3_prediction``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402


def _bench(name: str, fn, derived_fn=lambda r: "ok"):
    t0 = time.time()
    try:
        res = fn()
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},{derived_fn(res)}")
        return True
    except Exception as e:  # report and continue
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},ERROR:{type(e).__name__}:{e}")
        return False


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full 55-adaptive-run campaign (slow)")
    args, _ = ap.parse_known_args()
    # prefer already-cached full campaigns (artifacts/experiments)
    from benchmarks.experiment import campaign_path
    cached55 = [j for j in ("lr", "mpc", "kmeans", "gbt")
                if campaign_path(j, "enel", 55).exists()
                and campaign_path(j, "ellis", 55).exists()]
    if args.full or len(cached55) >= 2:
        n_adaptive, camp_jobs = 55, (cached55 or ["kmeans", "gbt"])
    else:
        n_adaptive, camp_jobs = 15, ["kmeans", "gbt"]
    ok = True

    # Table II: jobs + datasets ground truth
    def table2():
        from repro.dataflow.workloads import JOBS, make_multiclass
        x, _ = make_multiclass(512)
        return {j.name: round(j.base_runtime(16), 1) for j in JOBS.values()}
    ok &= _bench("table2_jobs_base_runtime_s", table2, lambda r: str(r))

    # Table III: CVC/CVS windows, Enel vs Ellis (kmeans+gbt in fast mode)
    def table3():
        from benchmarks.table3_prediction import run
        t = run(jobs=camp_jobs, n_adaptive=n_adaptive)
        last = {f"{k[0]}/{k[1]}": round(v[-1]["cvc_mean"], 2)
                for k, v in t.items()}
        return last
    ok &= _bench("table3_cvc_final_window", table3, lambda r: str(r))

    # Fig 4: adaptive behaviour incl. failure phases
    def fig4():
        from benchmarks.fig4_adaptive import summarize
        out = {}
        for j in camp_jobs:
            s = summarize(j, n_adaptive)
            out[j] = round(s["enel"]["viol_second_half"] -
                           s["enel"]["viol_first_half"], 1)
        return out
    ok &= _bench("fig4_violation_improvement_s", fig4, lambda r: str(r))

    # Fig 5: fine-tune / inference timing
    def fig5():
        from benchmarks.fig5_timing import measure
        rows = [measure(j, repeats=5) for j in ("lr", "gbt")]
        return {r["job"]: round(r["fit_s_median"], 2) for r in rows}
    ok &= _bench("fig5_finetune_seconds", fig5, lambda r: str(r))

    # Roofline table + hillclimb-cell selection (reads dry-run artifacts)
    def roofline():
        from benchmarks.roofline import load_all, pick_hillclimb_cells
        rows = [r for r in load_all("pod1") if r.get("status") == "ok"]
        cells = pick_hillclimb_cells()
        return {"cells": len(rows),
                "picked": {k: f"{v['arch']}--{v['shape']}"
                           for k, v in cells.items()}}
    ok &= _bench("roofline_table", roofline, lambda r: str(r))

    # Kernel + smoke-train microbenches
    def micro():
        from benchmarks.microbench import kernel_benches, train_step_benches
        rows = kernel_benches() + train_step_benches()
        for r in rows:
            print(f"{r['name']},{r['us']:.0f},interpret_or_smoke")
        return len(rows)
    ok &= _bench("microbench_suite", micro, lambda r: f"{r}_benches")

    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
