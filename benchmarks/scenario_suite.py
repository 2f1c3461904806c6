"""Scenario-suite benchmark: disturbance grid, cross-context transfer cells,
and the sim-engine throughput race.

Three measurements, all merged into ``BENCH_decision.json``:

* ``scenarios`` — every scenario x job cell through a FleetCampaign
  (vectorized engine, cross-batched decisions): per-scenario
  target-compliance, violation severity, rescale counts, fleet
  decisions/sec.  The ``multi_tenant`` scenario runs the Poisson-arrival
  capacity campaign (capacity-capped picks against a bounded pool).
* ``scenario_transfer`` — train the model under context A (scenario,
  dataset size), deploy under context B without a scratch retrain; per-cell
  compliance + prediction error of the reused model (paper §I/§VI reuse
  claim).
* ``sim_engine`` — fleet-of-N end-to-end simulation campaign wall time:
  the numpy per-job event loop vs the vectorized engine (per-component
  lockstep steps AND whole-run single dispatches), median-of-k with IQR.
* ``fused_race`` — the fleet-32 acceptance race for the whole-campaign
  kernel (``core/campaign_kernel.py``): sim step + decision sweep +
  resident fit fused into ONE scanned jit vs the stepped python loop over
  the same jitted body.  Bit-exact traces (tests/test_fused_campaign.py),
  so the race is pure host-dispatch overhead; plan build (host-side, once
  per campaign) is timed separately.

``--ci-smoke`` runs a reduced 2-scenario x 2-job suite plus a small engine
race under a wall-clock budget (exit 1 on overrun) so CI guards both the
subsystem's health and its cost.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, List

import numpy as np

try:
    from benchmarks.fig5_timing import med_iqr, merge_bench_json
except ImportError:                      # run as a script from benchmarks/
    from fig5_timing import med_iqr, merge_bench_json
from repro.dataflow.workloads import JOBS
from repro.sim.engine import (BatchedClusterSim, NumpySimBackend,
                              SimStepRequest)
from repro.sim.evaluate import (DEFAULT_JOBS, DEFAULT_SCENARIOS,
                                DEFAULT_TRANSFER_CELLS,
                                run_scenario_campaign, run_transfer_cells)
from repro.sim.scenarios import make_scenario
from repro.compile_cache import enable_compile_cache

JOB_CYCLE = ("lr", "mpc", "kmeans", "gbt")


# ------------------------------------------------------------ engine race
def measure_engine(fleet_size: int = 32, runs: int = 2, repeats: int = 5,
                   scenario_name: str = "node_failure", seed: int = 0
                   ) -> Dict:
    """End-to-end wall time of a fleet simulation campaign (records
    materialized, failure injection on) under three engines:

    * ``numpy``: the per-job event loop (reference),
    * ``batched_step``: vectorized engine, one dispatch per fleet
      component-step (the adaptive-campaign access pattern),
    * ``batched_full``: vectorized engine, one dispatch per full fleet run
      (the profiling / scenario-replay access pattern).

    All three replay the same seeded rescale schedules; the batched paths
    are bit-identical to the numpy loop (asserted in tests), so this is a
    pure wall-clock race.
    """
    sc = make_scenario(scenario_name, seed=seed)
    jobs = [JOBS[JOB_CYCLE[i % len(JOB_CYCLE)]] for i in range(fleet_size)]
    c_max = max(j.n_components for j in jobs)
    rng = np.random.RandomState(seed)
    scheds = [(rng.choice([8, 16, 24, 32], (fleet_size, c_max)).astype(int),
               rng.choice([8, 16, 24, 32], (fleet_size, c_max)).astype(int))
              for _ in range(runs)]

    npb = NumpySimBackend()
    stepped = BatchedClusterSim()
    full = BatchedClusterSim()
    for i, job in enumerate(jobs):
        npb.register(job, seed=seed + i, scenario=sc)
        stepped.register(job, seed=seed + i, scenario=sc)
        full.register(job, seed=seed + i, scenario=sc)

    def campaign_numpy():
        for a, z in scheds:
            for j, job in enumerate(jobs):
                npb.begin_run(j)
                clock = 0.0
                for k in range(job.n_components):
                    r = npb.step([SimStepRequest(j, k, int(a[j, k]),
                                                 int(z[j, k]), clock,
                                                 True)])[0]
                    clock = r.clock_end

    def campaign_stepped():
        for a, z in scheds:
            clocks = [0.0] * fleet_size
            for j in range(fleet_size):
                stepped.begin_run(j)
            for k in range(c_max):
                reqs = [SimStepRequest(j, k, int(a[j, k]), int(z[j, k]),
                                       clocks[j], True)
                        for j, job in enumerate(jobs)
                        if k < job.n_components]
                for req, res in zip(reqs, stepped.step(reqs)):
                    clocks[req.slot] = res.clock_end

    def campaign_full():
        for a, z in scheds:
            full.run_full(a, z, inject_failures=True)

    times = {"numpy": [], "batched_step": [], "batched_full": []}
    fns = {"numpy": campaign_numpy, "batched_step": campaign_stepped,
           "batched_full": campaign_full}
    for name, fn in fns.items():
        fn()                                  # warmup (jit compile)
        for _ in range(repeats):
            t0 = time.time()
            fn()
            times[name].append(time.time() - t0)
    row = {"fleet_size": fleet_size, "runs_per_campaign": runs,
           "scenario": scenario_name, "repeats": repeats}
    for name in fns:
        m = med_iqr(times[name])
        row[f"{name}_s_median"] = m["median"]
        row[f"{name}_s_iqr"] = m["iqr"]
    row["speedup_step"] = row["numpy_s_median"] / row["batched_step_s_median"]
    row["speedup_full"] = row["numpy_s_median"] / row["batched_full_s_median"]
    return row


# ------------------------------------------------------------- fused race
def measure_fused_race(fleet_size: int = 32, runs: int = 2,
                       repeats: int = 5, scenario_name: str = "node_failure",
                       seed: int = 40, profile_runs: int = 3) -> Dict:
    """Fused whole-campaign scan vs the STEPPED PATH under the disturbance
    scenario the acceptance gate names.

    ``speedup_fused`` (the gated number) races against the live stepped
    driver — ``adaptive_campaign`` on a fresh twin fleet per repeat: host
    python graph building, per-bucket service dispatch and sequential
    per-job resident fits, i.e. exactly the host round-trips fusion
    removes.  ``speedup_vs_twin`` is the secondary dispatch-overhead-only
    number against the python loop over the fused plan's own jitted step
    body (bit-exact twin).  One seed per job class so the plan dedups to 4
    structural classes; plan build is reported separately (host-side, once
    per campaign, amortized over every run it drives)."""
    import jax

    from repro.core import campaign_kernel as ck
    from repro.core.service import DecisionService
    from repro.dataflow import FleetCampaign, JobExperiment

    def fresh_fleet() -> FleetCampaign:
        exps = [JobExperiment(JOB_CYCLE[i % len(JOB_CYCLE)],
                              seed=seed + i % len(JOB_CYCLE),
                              scenario=make_scenario(scenario_name,
                                                     seed=seed))
                for i in range(fleet_size)]
        camp = FleetCampaign(exps, DecisionService(), engine="batched")
        camp.profile(profile_runs)
        return camp

    camp = fresh_fleet()
    t0 = time.time()
    plan = ck.build_plan(camp.experiments, runs)
    plan_build_s = time.time() - t0
    c_f, ys_f = ck.run_fused(plan)            # warmup: compiles the scan
    jax.block_until_ready(ys_f)
    _, ys_s = ck.run_stepped(plan)            # warmup: compiles the step
    jax.block_until_ready(ys_s)
    fused_t, twin_t = [], []
    for _ in range(repeats):
        t0 = time.time()
        jax.block_until_ready(ck.run_fused(plan)[1])
        fused_t.append(time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(ck.run_stepped(plan)[1])
        twin_t.append(time.time() - t0)
    live_t = []
    for _ in range(min(repeats, 3)):      # fresh fleet per repeat: the
        twin = fresh_fleet()              # scratch/tune fit cadence then
        t0 = time.time()                  # matches the fused plan's
        twin.adaptive_campaign(runs)
        live_t.append(time.time() - t0)
    fm, tm, lm = med_iqr(fused_t), med_iqr(twin_t), med_iqr(live_t)
    return {"fleet_size": fleet_size, "runs_per_campaign": runs,
            "scenario": scenario_name, "repeats": repeats,
            "steps": plan.n_steps, "plan_build_s": plan_build_s,
            "fused_s_median": fm["median"], "fused_s_iqr": fm["iqr"],
            "stepped_s_median": lm["median"], "stepped_s_iqr": lm["iqr"],
            "twin_s_median": tm["median"], "twin_s_iqr": tm["iqr"],
            "speedup_fused": lm["median"] / fm["median"],
            "speedup_vs_twin": tm["median"] / fm["median"],
            "nonfinite_decisions": int(np.asarray(c_f["nonfinite"]).sum())}


# ----------------------------------------------------------------- driver
def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", default=",".join(DEFAULT_SCENARIOS) +
                    ",multi_tenant")
    ap.add_argument("--jobs", default=",".join(DEFAULT_JOBS))
    ap.add_argument("--engine", default="batched")
    ap.add_argument("--profile-runs", type=int, default=3)
    ap.add_argument("--adaptive-runs", type=int, default=3)
    ap.add_argument("--transfer", action="store_true", default=True)
    ap.add_argument("--no-transfer", dest="transfer", action="store_false")
    ap.add_argument("--fleet", type=int, default=32)
    ap.add_argument("--engine-runs", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--fused-runs", type=int, default=2)
    ap.add_argument("--fused-min-speedup", type=float, default=0.0,
                    help="fail (exit 1) if the fused race speedup over the "
                         "stepped loop drops below this (acceptance: 3.0 "
                         "on an idle machine; leave 0 in CI — timings "
                         "there are noise)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    default=True)
    ap.add_argument("--budget-s", type=float, default=0.0,
                    help="fail (exit 1) if total wall time exceeds this")
    ap.add_argument("--ci-smoke", action="store_true",
                    help="reduced 2x2 suite + small engine race")
    ap.add_argument("--out", default="BENCH_decision.json")
    args = ap.parse_args(argv)
    t_start = time.time()

    if args.ci_smoke:
        scenario_names = ["node_failure", "multi_tenant"]
        job_keys = ["kmeans", "gbt"]
        transfer_cells = DEFAULT_TRANSFER_CELLS[:1]
        fleet, adaptive, profile = 8, 1, 2
    else:
        scenario_names = [s for s in args.scenarios.split(",") if s]
        job_keys = [j for j in args.jobs.split(",") if j]
        transfer_cells = DEFAULT_TRANSFER_CELLS if args.transfer else ()
        fleet, adaptive, profile = (args.fleet, args.adaptive_runs,
                                    args.profile_runs)

    scenario_rows: List[Dict] = []
    for name in scenario_names:
        rows = run_scenario_campaign(name, job_keys, engine=args.engine,
                                     profile_runs=profile,
                                     adaptive_runs=adaptive)
        scenario_rows.extend(rows)
        for r in rows:
            if r["job"] == "__fleet__":
                print(f"scenario,{name},fleet={r['fleet_size']},"
                      f"decisions={r.get('decisions', 0)},"
                      f"dec_per_s={r.get('decisions_per_s', 0):.1f}"
                      + (f",capped={r['capped_decisions']}"
                         if "capped_decisions" in r else ""))
            else:
                print(f"scenario,{name},{r['job']},"
                      f"compliance={r.get('compliance', float('nan')):.2f},"
                      f"cvs={r.get('cvs_mean_min', float('nan')):.2f}min,"
                      f"rescales={r.get('rescales_mean', float('nan')):.1f}")

    transfer_rows: List[Dict] = []
    if transfer_cells:
        transfer_rows = run_transfer_cells(transfer_cells,
                                           engine=args.engine,
                                           adaptive_runs=adaptive + 1)
        for r in transfer_rows:
            print(f"transfer,{r['train_scenario']}@{r['train_size']}->"
                  f"{r['deploy_scenario']}@{r['deploy_size']},{r['job']},"
                  f"compliance={r.get('compliance', float('nan')):.2f},"
                  f"pred_err={r.get('pred_rel_err_mean', float('nan')):.2f}")

    engine_row = measure_engine(fleet_size=fleet, runs=args.engine_runs,
                                repeats=max(args.repeats, 5))
    print(f"sim_engine,fleet={engine_row['fleet_size']},"
          f"numpy={engine_row['numpy_s_median']*1e3:.0f}ms,"
          f"step={engine_row['batched_step_s_median']*1e3:.0f}ms,"
          f"full={engine_row['batched_full_s_median']*1e3:.0f}ms,"
          f"speedup_step={engine_row['speedup_step']:.1f}x,"
          f"speedup_full={engine_row['speedup_full']:.1f}x")

    ok = True
    updates = {"scenarios": scenario_rows,
               "scenario_transfer": transfer_rows,
               "sim_engine": [engine_row]}
    if args.fused:
        fused_row = measure_fused_race(fleet_size=fleet,
                                       runs=args.fused_runs,
                                       repeats=max(args.repeats, 5))
        print(f"fused_race,fleet={fused_row['fleet_size']},"
              f"fused={fused_row['fused_s_median']*1e3:.0f}ms,"
              f"stepped={fused_row['stepped_s_median']*1e3:.0f}ms,"
              f"twin={fused_row['twin_s_median']*1e3:.0f}ms,"
              f"plan_build={fused_row['plan_build_s']:.1f}s,"
              f"speedup_fused={fused_row['speedup_fused']:.1f}x,"
              f"vs_twin={fused_row['speedup_vs_twin']:.2f}x")
        updates["fused_race"] = [fused_row]
        if fused_row["nonfinite_decisions"]:
            print(f"FAIL: fused race produced "
                  f"{fused_row['nonfinite_decisions']} non-finite decisions")
            ok = False
        if (args.fused_min_speedup and
                fused_row["speedup_fused"] < args.fused_min_speedup):
            print(f"FAIL: fused speedup {fused_row['speedup_fused']:.1f}x "
                  f"< required {args.fused_min_speedup:.1f}x")
            ok = False

    merge_bench_json(args.out, updates)
    wall = time.time() - t_start
    print(f"wrote {os.path.abspath(args.out)} (total {wall:.0f}s)")
    if args.budget_s and wall > args.budget_s:
        print(f"FAIL: scenario suite took {wall:.0f}s "
              f"> budget {args.budget_s:.0f}s")
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
