"""Chaos-suite benchmark: drive the fleet control plane through
controller-side fault plans and measure how much target compliance the
robustness machinery gives back.

For every ``chaos_*`` scenario (see ``repro.sim.scenarios``) the suite runs
the same fleet campaign as the scenario suite, but with faults aimed at the
CONTROLLER: poisoned observations, resident-cache bit-rot, NaN model
parameters, dispatch timeouts, and controller crashes recovered from
checkpoints.  A clean ``node_failure`` campaign (same environment, no
control-plane faults) is the reference.

Rows merged into ``BENCH_decision.json`` under ``"chaos"`` carry, per job:
compliance + violation severity (as in the scenario grid), plus the
fault-handling counters (fallback decisions, retries, breaker trips,
quarantined cache rows, poisoned fits, injected timeouts, restores).

Acceptance gates (exit 1 on violation):

* zero non-finite / out-of-range scale-out decisions under every fault plan
  (the guardrail + fallback contract);
* mean compliance under chaos within ``--max-degradation`` (default 0.10)
  of the clean reference;
* a campaign killed at crash rounds and restored from checkpoints
  reproduces the uninterrupted decision trace exactly (with model-poisoning
  chaos active);
* optional ``--budget-s`` wall-clock budget.

``--ci-smoke`` reduces to 2 chaos scenarios x 2 jobs plus the trace check.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, List

import numpy as np

try:
    from benchmarks.fig5_timing import merge_bench_json, merge_latency_rows
except ImportError:                      # run as a script from benchmarks/
    from fig5_timing import merge_bench_json, merge_latency_rows
from repro import obs
from repro.sim.evaluate import (CHAOS_SCENARIOS, chaos_trace_identity,
                                run_chaos_campaign)
from repro.compile_cache import enable_compile_cache

REFERENCE_SCENARIO = "node_failure"      # same environment, no chaos


def _compliance_by_job(rows: List[Dict]) -> Dict[str, float]:
    return {r["job"]: r["compliance"] for r in rows
            if r["job"] != "__fleet__" and "compliance" in r}


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", default=",".join(CHAOS_SCENARIOS))
    ap.add_argument("--jobs", default="lr,mpc,kmeans,gbt")
    ap.add_argument("--engine", default="batched")
    ap.add_argument("--profile-runs", type=int, default=3)
    ap.add_argument("--adaptive-runs", type=int, default=6)
    ap.add_argument("--max-degradation", type=float, default=0.10,
                    help="max allowed drop of mean compliance vs the "
                    "clean reference")
    ap.add_argument("--no-trace-check", dest="trace_check",
                    action="store_false", default=True)
    ap.add_argument("--budget-s", type=float, default=0.0,
                    help="fail (exit 1) if total wall time exceeds this")
    ap.add_argument("--ci-smoke", action="store_true",
                    help="reduced 2-scenario x 2-job suite")
    ap.add_argument("--flight-recorder-out", default="",
                    help="write the controller flight-recorder span ring "
                    "as JSONL to this path after the suite")
    ap.add_argument("--out", default="BENCH_decision.json")
    args = ap.parse_args(argv)
    t_start = time.time()

    if args.ci_smoke:
        scenario_names = ["chaos_model", "chaos_crashes"]
        job_keys = ["kmeans", "gbt"]
        adaptive, profile = 4, 2
    else:
        scenario_names = [s for s in args.scenarios.split(",") if s]
        job_keys = [j for j in args.jobs.split(",") if j]
        adaptive, profile = args.adaptive_runs, args.profile_runs

    failures: List[str] = []
    all_rows: List[Dict] = []

    ref_rows = run_chaos_campaign(REFERENCE_SCENARIO, job_keys,
                                  engine=args.engine, profile_runs=profile,
                                  adaptive_runs=adaptive)
    ref = _compliance_by_job(ref_rows)
    ref_mean = float(np.mean(list(ref.values())))
    all_rows.extend(ref_rows)
    print(f"chaos,reference={REFERENCE_SCENARIO},"
          f"compliance_mean={ref_mean:.2f}")

    for name in scenario_names:
        rows = run_chaos_campaign(name, job_keys, engine=args.engine,
                                  profile_runs=profile,
                                  adaptive_runs=adaptive)
        all_rows.extend(rows)
        comp = _compliance_by_job(rows)
        comp_mean = float(np.mean(list(comp.values())))
        bad = sum(r.get("nonfinite_decisions", 0) for r in rows)
        fleet = next(r for r in rows if r["job"] == "__fleet__")
        degr = ref_mean - comp_mean
        print(f"chaos,{name},compliance_mean={comp_mean:.2f},"
              f"degradation={degr:+.2f},"
              f"fallbacks={fleet['svc_fallback_decisions']},"
              f"retries={fleet['svc_retries']},"
              f"breaker_trips={fleet['svc_breaker_trips']},"
              f"quarantined={fleet['quarantined_rows']},"
              f"restores={fleet['restores']},"
              f"nonfinite={bad}")
        if bad:
            failures.append(f"{name}: {bad} non-finite/out-of-range "
                            "decisions escaped the guardrails")
        if degr > args.max_degradation:
            failures.append(
                f"{name}: mean compliance degraded {degr:.2f} "
                f"> {args.max_degradation:.2f} vs {REFERENCE_SCENARIO}")

    trace_ok = None
    if args.trace_check:
        trace_ok = chaos_trace_identity(
            job_keys=tuple(job_keys[:2]), adaptive_runs=min(adaptive, 4))
        print(f"chaos,trace_identity,ok={trace_ok}")
        if not trace_ok:
            failures.append("crash/restore campaign diverged from the "
                            "uninterrupted trace")

    # controller latency distributions (decision dispatch + fit) from the
    # metrics registry: fixed-bucket histograms -> p50/p95/p99/max rows
    lat_rows: List[Dict] = []
    if obs.enabled():
        lat_rows = [dict(r, source="chaos_suite")
                    for r in obs.registry().rows()
                    if r["kind"] == "histogram"]
        for r in lat_rows:
            if not r.get("count"):
                continue
            print(f"latency,{r['metric']},{r['labels']},"
                  f"n={r['count']},p50={r['p50'] * 1e3:.3f}ms,"
                  f"p95={r['p95'] * 1e3:.3f}ms,p99={r['p99'] * 1e3:.3f}ms,"
                  f"max={r['max'] * 1e3:.3f}ms")
    if args.flight_recorder_out:
        obs.recorder().to_jsonl(args.flight_recorder_out)
        print(f"flight recorder: {len(obs.recorder())} spans -> "
              f"{os.path.abspath(args.flight_recorder_out)}")

    wall = time.time() - t_start
    summary = {"job": "__suite__", "reference": REFERENCE_SCENARIO,
               "reference_compliance_mean": ref_mean,
               "scenarios": scenario_names, "jobs": job_keys,
               "adaptive_runs": adaptive, "trace_identity": trace_ok,
               "wall_s": wall, "failures": failures}
    merge_bench_json(args.out, {"chaos": all_rows + [summary]})
    if lat_rows:
        merge_latency_rows(args.out, lat_rows, "chaos_suite")
    print(f"wrote {os.path.abspath(args.out)} (total {wall:.0f}s)")
    if args.budget_s and wall > args.budget_s:
        failures.append(f"chaos suite took {wall:.0f}s "
                        f"> budget {args.budget_s:.0f}s")
    for f in failures:
        print(f"FAIL: {f}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
