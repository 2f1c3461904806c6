"""Fleet-scale decision benchmark: cross-job batched dispatch vs sequential
per-job ``recommend``, plus the campaign compile-count budget.

Three measurements:

* **Throughput** — a fleet of concurrent jobs (all four job classes x seeds,
  cycling) each needs a mid-run rescaling decision.  ``sequential`` answers
  them one ``EnelScaler.recommend`` at a time (a one-request decide on
  each job's own service); ``batched`` prepares shape-bucketed requests and
  answers all of them in one ``DecisionService.decide`` call (one jit
  dispatch per bucket, one transfer per group).  Reported at fleet sizes
  1/8/32.

* **Compile budget** — a full 4-job mini-campaign (profiling + adaptive runs
  covering every remaining-component count) must compile the fleet sweep at
  most once per visited shape-bucket key: the bucket ladders exist precisely
  so this stays a small constant (~12) instead of O(runs x components).
  The script FAILS (exit 1) if the trace count exceeds the visited-bucket
  bound, or if the ladder lets the campaign visit more than MAX_BUCKETS
  distinct keys.

* **Fused campaign** — the whole-campaign-on-device race
  (``core/campaign_kernel.py``) against two stepped baselines: the python
  loop over the *same* jitted step body (bit-exact twin, isolating
  per-step dispatch overhead) and the LIVE production path
  (``adaptive_campaign``: host graph building, service dispatch,
  sequential per-job fits — the work fusion actually eliminates).  A numpy
  event-loop replay of the fused schedule (sim only — no decisions/fit,
  so fused speedups over it are lower bounds) anchors the absolute scale.
  The live and numpy baselines cap at ``--numpy-max`` slots and larger
  fleets extrapolate linearly (both paths are sequential per job), marked
  ``*_estimated``.  Default sizes 32/128/1024 measure the ROADMAP
  "fleet sizes in the thousands" claim instead of asserting it.

Rows are merged into ``BENCH_decision.json`` (``fleet`` + ``fleet_budget``
+ ``fused``) next to the fig5/fit/decision rows; CI uploads the JSON as an
artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

import numpy as np

try:
    from benchmarks.fig5_timing import (med_iqr, merge_bench_json,
                                        merge_latency_rows)
except ImportError:                      # run as a script from benchmarks/
    from fig5_timing import med_iqr, merge_bench_json, merge_latency_rows
from repro import obs
from repro.core import model as enel_model
from repro.core.graph import summary_node
from repro.core.service import DecisionService
from repro.dataflow import FleetCampaign, JobExperiment
from repro.dataflow.runner import _component_nodes, _future_nodes, _to_graph
from repro.sim.engine import SimStepRequest
from repro.compile_cache import enable_compile_cache

JOB_CYCLE = ("lr", "mpc", "kmeans", "gbt")
MAX_BUCKETS = 12          # bucket-ladder bound for the 4-job mini-campaign


def _decision_context(exp: JobExperiment) -> Dict:
    """The runner's mid-run decision kwargs (component 0 finished — the
    largest sweep of the job), mirroring fig5's measure_decision."""
    job = exp.job
    builder = lambda ci, a, z, pr: _to_graph(
        _future_nodes(exp.encoder, job, ci, a, z), pr, ci)
    comp = exp.sim.run_component(job, 0, clock=0.0, start_scaleout=8,
                                 end_scaleout=8, inject_failures=False,
                                 failures_log=[])
    summ = summary_node(_component_nodes(exp.encoder, job, comp), name="P0")
    return dict(graph_builder=builder, next_comp=1,
                n_components=job.n_components, elapsed=comp.runtime,
                current_scaleout=8, target_runtime=exp.target,
                current_summary=summ)


def build_base_experiments(profile_runs: int = 3) -> List[JobExperiment]:
    exps = []
    for i, key in enumerate(JOB_CYCLE):
        exp = JobExperiment(key, seed=i)
        exp.profile(profile_runs)
        exps.append(exp)
    return exps


def measure_fleet(base_exps: List[JobExperiment], sizes=(1, 8, 32),
                  repeats: int = 7) -> List[Dict]:
    """decisions/sec: sequential per-job recommend vs batched service."""
    service = DecisionService()
    contexts = [(exp, _decision_context(exp)) for exp in base_exps]
    rows = []
    for size in sizes:
        fleet = [contexts[i % len(contexts)] for i in range(size)]
        for _ in range(2):           # untimed rounds: jit warmup + settling
            for exp, kw in fleet[:len(contexts)]:
                exp.enel.recommend(**kw)
            service.decide(
                [exp.enel.prepare_request(**kw) for exp, kw in fleet])
        seq_t, bat_t = [], []
        for _ in range(repeats):
            t0 = time.time()
            for exp, kw in fleet:
                exp.enel.recommend(**kw)
            seq_t.append(time.time() - t0)
            t0 = time.time()
            service.decide(
                [exp.enel.prepare_request(**kw) for exp, kw in fleet])
            bat_t.append(time.time() - t0)
        seq_m, bat_m = med_iqr(seq_t), med_iqr(bat_t)
        seq, bat = seq_m["median"], bat_m["median"]
        rows.append({
            "fleet_size": size,
            "sequential_dec_per_s": size / seq,
            "batched_dec_per_s": size / bat,
            "speedup": seq / bat,
            "sequential_ms_per_decision": seq / size * 1e3,
            "sequential_ms_iqr": seq_m["iqr"] / size * 1e3,
            "batched_ms_per_decision": bat / size * 1e3,
            "batched_ms_iqr": bat_m["iqr"] / size * 1e3,
        })
    return rows


def _fused_fleet(size: int, profile_runs: int,
                 seed0: int = 20) -> FleetCampaign:
    """A fleet of `size` slots cycling the four job classes with ONE seed
    per class, so the fused plan dedups to 4 structural/history classes no
    matter the fleet size (plan build stays O(classes), not O(fleet))."""
    exps = [JobExperiment(JOB_CYCLE[i % len(JOB_CYCLE)],
                          seed=seed0 + i % len(JOB_CYCLE))
            for i in range(size)]
    camp = FleetCampaign(exps, DecisionService(), engine="batched")
    camp.profile(profile_runs)
    return camp


def _numpy_replay_times(exps, ys, n_runs: int, c_max: int,
                        repeats: int) -> List[float]:
    """Wall time of the numpy per-job event loop replaying the fused
    z-schedule (sim only — the numpy path has no batched decision or
    resident-fit equivalent, so this is the sim floor, not the campaign)."""
    from repro.sim.engine import NumpySimBackend
    from repro.sim.scenarios import make_scenario
    a = np.asarray(ys["a"]).astype(int)
    z = np.asarray(ys["z"]).astype(int)
    npb = NumpySimBackend()
    for j, e in enumerate(exps):
        npb.register(e.job, seed=e.seed, scenario=make_scenario("baseline"))
    times = []
    for _ in range(repeats):
        t0 = time.time()
        for r in range(n_runs):
            base = r * c_max
            for j, e in enumerate(exps):
                npb.begin_run(j)
                clock = 0.0
                for k in range(e.job.n_components):
                    res = npb.step([SimStepRequest(
                        j, k, int(a[base + k, j]), int(z[base + k, j]),
                        clock, True)])[0]
                    clock = res.clock_end
        times.append(time.time() - t0)
    return times


def measure_fused(sizes=(32, 128, 1024), n_runs: int = 2, repeats: int = 5,
                  profile_runs: int = 3, numpy_max: int = 32,
                  live_max: int = 32, big_repeats: int = 2) -> List[Dict]:
    """Whole-campaign wall time at each fleet size, median-of-k + IQR,
    across three drivers of the SAME protocol work (n_runs adaptive runs,
    identical decision cadence, one scratch + one tune fit window):

    * ``fused`` — ONE scanned jit (core/campaign_kernel.py);
    * ``stepped`` — python loop over the same jitted step body (bit-exact
      twin; isolates per-step dispatch overhead);
    * ``live`` — the production stepped path, ``adaptive_campaign`` on a
      fresh twin fleet per repeat: host python graph building, service
      dispatch, per-job sequential ``fit_resident`` (what fused replaces).

    ``live`` is sequential per job (linear in fleet size), so sizes above
    ``live_max`` extrapolate linearly from the last measured size and are
    marked ``live_estimated`` — same convention as the numpy sim floor.
    Sizes above ``live_max`` also drop to ``big_repeats`` timed repeats
    (single-core CPU: a 1024-slot campaign is minutes per repeat).

    Also verifies, per size, that the timed repeats add ZERO new traces
    (the compile count is bounded by the bucket ladder, not by repeats)
    and that every decision left the scan finite."""
    import jax

    from repro.core import campaign_kernel as ck

    rows: List[Dict] = []
    numpy_per_step = None      # s per (component-step x job), from replay
    live_per_step = None
    for size in sizes:
        reps = repeats if size <= live_max else max(big_repeats, 2)
        camp = _fused_fleet(size, profile_runs)
        t0 = time.time()
        plan = ck.build_plan(camp.experiments, n_runs)
        plan_build_s = time.time() - t0
        trace0 = enel_model.trace_count("fused_campaign")
        c_f, ys_f = ck.run_fused(plan)         # warmup: compiles the scan
        jax.block_until_ready(ys_f)
        _, ys_s = ck.run_stepped(plan)         # warmup: compiles the step
        jax.block_until_ready(ys_s)
        warm = enel_model.trace_count("fused_campaign") - trace0

        fused_t, stepped_t = [], []
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(ck.run_fused(plan)[1])
            fused_t.append(time.time() - t0)
            t0 = time.time()
            jax.block_until_ready(ck.run_stepped(plan)[1])
            stepped_t.append(time.time() - t0)
        new_traces = (enel_model.trace_count("fused_campaign")
                      - trace0 - warm)

        fleet_steps = int(np.asarray(plan.host["n_comp"]).sum()) * n_runs
        decisions = int(np.asarray(ys_f["decided"]).sum())
        nonfinite = int(np.asarray(c_f["nonfinite"]).sum())
        if size <= numpy_max:
            m = med_iqr(_numpy_replay_times(camp.experiments, ys_f, n_runs,
                                            plan.static.c_max, reps))
            numpy_s, numpy_iqr, n_est = m["median"], m["iqr"], False
            numpy_per_step = numpy_s / fleet_steps
        else:                  # linear in fleet-steps from the last replay
            numpy_s = (numpy_per_step or 0.0) * fleet_steps
            numpy_iqr, n_est = 0.0, True

        if size <= live_max:
            _fused_fleet(size, profile_runs).adaptive_campaign(n_runs)
            live_t = []                       # ^ untimed live-bucket warmup
            for _ in range(min(reps, 3)):     # fresh twin per repeat so the
                twin = _fused_fleet(size, profile_runs)   # scratch cadence
                t0 = time.time()              # matches the fused plan
                twin.adaptive_campaign(n_runs)
                live_t.append(time.time() - t0)
            m = med_iqr(live_t)
            live_s, live_iqr, l_est = m["median"], m["iqr"], False
            live_per_step = live_s / fleet_steps
        else:                  # the live path is sequential per job
            live_s = (live_per_step or 0.0) * fleet_steps
            live_iqr, l_est = 0.0, True

        fm, sm = med_iqr(fused_t), med_iqr(stepped_t)
        rows.append({
            "fleet_size": size, "runs_per_campaign": n_runs,
            "repeats": reps, "steps": plan.n_steps,
            "fleet_steps": fleet_steps, "decisions": decisions,
            "plan_build_s": plan_build_s,
            "fused_s_median": fm["median"], "fused_s_iqr": fm["iqr"],
            "stepped_s_median": sm["median"], "stepped_s_iqr": sm["iqr"],
            "live_s_median": live_s, "live_s_iqr": live_iqr,
            "live_estimated": l_est,
            "fused_steps_per_s": fleet_steps / fm["median"],
            "fused_dec_per_s": decisions / fm["median"],
            "stepped_steps_per_s": fleet_steps / sm["median"],
            "stepped_dec_per_s": decisions / sm["median"],
            "live_dec_per_s": (decisions / live_s) if live_s else 0.0,
            "speedup_vs_stepped": sm["median"] / fm["median"],
            "speedup_vs_live": (live_s / fm["median"]) if live_s else 0.0,
            "numpy_s_median": numpy_s, "numpy_s_iqr": numpy_iqr,
            "numpy_estimated": n_est, "numpy_sim_only": True,
            "numpy_steps_per_s": (fleet_steps / numpy_s) if numpy_s else 0.0,
            "speedup_vs_numpy": (numpy_s / fm["median"]) if numpy_s else 0.0,
            "new_traces_during_timing": new_traces,
            "nonfinite_decisions": nonfinite,
        })
    return rows


def measure_budget(adaptive_runs: int = 2,
                   profile_runs: int = 3) -> Dict:
    """Compile-count budget: a fresh 4-job mini-campaign through the fleet
    service must compile at most once per visited shape-bucket key."""
    enel_model.reset_trace_counts()
    exps = [JobExperiment(key, seed=10 + i)
            for i, key in enumerate(JOB_CYCLE)]
    campaign = FleetCampaign(exps)
    campaign.profile(profile_runs)
    visited = set()
    for exp in exps:                      # individually: J=1 dispatches
        for _ in range(adaptive_runs):
            gen = exp.adaptive_run_gen("enel", False)
            try:
                req = next(gen)
                while True:
                    if isinstance(req, SimStepRequest):
                        req = gen.send(exp.backend.step([req])[0])
                    else:
                        visited.add(req.bucket_key)
                        req = gen.send(exp.service.decide([req])[0])
            except StopIteration:
                pass
    compiles = enel_model.trace_count("fleet_sweep")
    # fault-envelope health straight from the registry-backed service
    # stats: a clean campaign must answer every decision from the model
    # (every robustness counter stays 0)
    health = {k: v for k, v in campaign.service.stats().items()
              if k not in ("decisions", "dispatches", "batched_away",
                           "breaker_state")}
    return {"adaptive_runs_per_job": adaptive_runs,
            "visited_buckets": len(visited),
            "fleet_sweep_compiles": compiles,
            "bucket_bound": MAX_BUCKETS,
            "decisions": sum(st.decide_calls for e in exps
                             for st in e.stats if st.kind == "enel"),
            **health}


def measure_obs_overhead(size: int = 8, n_runs: int = 2, repeats: int = 5,
                         profile_runs: int = 3) -> Dict:
    """In-scan telemetry cost: the SAME fused campaign compiled with the
    telemetry carry block on vs off (``build_plan(..., telemetry=)``),
    per-decision wall-time delta.  This is the zero-cost-when-disabled
    contract made measurable: ``ENEL_OBS=0`` compiles the ``off`` jaxpr."""
    import jax

    from repro.core import campaign_kernel as ck

    camp = _fused_fleet(size, profile_runs, seed0=40)
    out: Dict = {"fleet_size": size, "runs_per_campaign": n_runs,
                 "repeats": repeats}
    for tel in (False, True):
        plan = ck.build_plan(camp.experiments, n_runs, telemetry=tel)
        _, ys = ck.run_fused(plan)          # warmup: compiles this variant
        jax.block_until_ready(ys)
        decisions = int(np.asarray(ys["decided"]).sum())
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(ck.run_fused(plan)[1])
            ts.append(time.time() - t0)
        m = med_iqr(ts)
        key = "on" if tel else "off"
        out[f"{key}_s_median"] = m["median"]
        out[f"{key}_s_iqr"] = m["iqr"]
        out[f"{key}_ms_per_decision"] = \
            m["median"] / max(decisions, 1) * 1e3
    out["decisions"] = decisions
    out["overhead"] = out["on_s_median"] / out["off_s_median"] - 1.0
    return out


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1,8,32")
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--profile-runs", type=int, default=3)
    ap.add_argument("--adaptive-runs", type=int, default=2)
    ap.add_argument("--fused-sizes", default="32,128,1024",
                    help="fleet sizes for the fused-campaign race "
                         "(empty string skips it)")
    ap.add_argument("--fused-runs", type=int, default=2)
    ap.add_argument("--fused-repeats", type=int, default=5)
    ap.add_argument("--numpy-max", type=int, default=32,
                    help="largest fleet the numpy replay runs for real; "
                         "bigger sizes extrapolate (numpy_estimated)")
    ap.add_argument("--no-fused", dest="fused", action="store_false")
    ap.add_argument("--obs-overhead-max", type=float, default=0.0,
                    help="measure telemetry-on vs telemetry-off fused "
                    "campaign time and fail (exit 1) if the relative "
                    "overhead exceeds this (0 skips the check)")
    ap.add_argument("--budget-s", type=float, default=0.0,
                    help="fail (exit 1) if total wall time exceeds this")
    ap.add_argument("--out", default="BENCH_decision.json")
    args = ap.parse_args(argv)
    sizes = tuple(int(s) for s in args.sizes.split(","))
    t_start = time.time()

    # budget FIRST: it must observe a cold jit cache — running the fleet
    # throughput sweep beforehand would prewarm bucket compiles and hide
    # regressions from the trace counter
    budget = measure_budget(args.adaptive_runs, args.profile_runs)
    print(f"budget,buckets={budget['visited_buckets']},"
          f"compiles={budget['fleet_sweep_compiles']},"
          f"decisions={budget['decisions']},bound={budget['bucket_bound']}")

    base = build_base_experiments(args.profile_runs)
    fleet_rows = measure_fleet(base, sizes, args.repeats)
    for r in fleet_rows:
        print(f"fleet,size={r['fleet_size']},"
              f"seq={r['sequential_dec_per_s']:.1f}/s,"
              f"batched={r['batched_dec_per_s']:.1f}/s,"
              f"speedup={r['speedup']:.2f}x")

    fused_rows: List[Dict] = []
    if args.fused and args.fused_sizes:
        fsizes = tuple(int(s) for s in args.fused_sizes.split(","))
        fused_rows = measure_fused(fsizes, args.fused_runs,
                                   args.fused_repeats, args.profile_runs,
                                   args.numpy_max)
        for r in fused_rows:
            print(f"fused,size={r['fleet_size']},"
                  f"fused={r['fused_s_median']*1e3:.0f}ms,"
                  f"stepped={r['stepped_s_median']*1e3:.0f}ms,"
                  f"live={r['live_s_median']*1e3:.0f}ms,"
                  f"dec_per_s={r['fused_dec_per_s']:.1f},"
                  f"steps_per_s={r['fused_steps_per_s']:.1f},"
                  f"vs_stepped={r['speedup_vs_stepped']:.2f}x,"
                  f"vs_live={r['speedup_vs_live']:.1f}x"
                  + (",live_est" if r["live_estimated"] else ""))

    obs_row: Dict = {}
    if args.obs_overhead_max > 0:
        osize = int(args.fused_sizes.split(",")[0]) if args.fused_sizes \
            else 8
        obs_row = measure_obs_overhead(osize, args.fused_runs,
                                       max(args.fused_repeats, 5),
                                       args.profile_runs)
        print(f"obs_overhead,size={obs_row['fleet_size']},"
              f"off={obs_row['off_s_median'] * 1e3:.0f}ms,"
              f"on={obs_row['on_s_median'] * 1e3:.0f}ms,"
              f"overhead={obs_row['overhead'] * 100:+.1f}%")

    # controller latency distributions (decision dispatch + fit) observed
    # during this bench, from the registry's fixed-bucket histograms
    lat_rows: List[Dict] = []
    if obs.enabled():
        lat_rows = [dict(r, source="fleet_bench")
                    for r in obs.registry().rows()
                    if r["kind"] == "histogram" and r.get("count")]
        for r in lat_rows:
            print(f"latency,{r['metric']},{r['labels']},n={r['count']},"
                  f"p50={r['p50'] * 1e3:.3f}ms,p95={r['p95'] * 1e3:.3f}ms,"
                  f"p99={r['p99'] * 1e3:.3f}ms,max={r['max'] * 1e3:.3f}ms")

    updates = {"fleet": fleet_rows, "fleet_budget": budget}
    if obs_row:
        updates["obs_overhead"] = obs_row
    if fused_rows:
        # merge-by-size so partial reruns (one big fleet at a time) refresh
        # their row without clobbering the others
        prev: Dict = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                prev = {r.get("fleet_size"): r
                        for r in json.load(f).get("fused", [])}
        for r in fused_rows:
            prev[r["fleet_size"]] = r
        updates["fused"] = [prev[k] for k in sorted(prev)]
    merge_bench_json(args.out, updates)
    if lat_rows:
        merge_latency_rows(args.out, lat_rows, "fleet_bench")
    print(f"wrote {os.path.abspath(args.out)}")

    ok = True
    if budget["fleet_sweep_compiles"] > budget["visited_buckets"]:
        print(f"FAIL: {budget['fleet_sweep_compiles']} compiles > "
              f"{budget['visited_buckets']} visited buckets "
              "(recompilation within a bucket)")
        ok = False
    if budget["visited_buckets"] > MAX_BUCKETS:
        print(f"FAIL: campaign visited {budget['visited_buckets']} buckets "
              f"> ladder bound {MAX_BUCKETS}")
        ok = False
    for r in fused_rows:
        if r["new_traces_during_timing"]:
            print(f"FAIL: fused fleet {r['fleet_size']} added "
                  f"{r['new_traces_during_timing']} traces during timed "
                  "repeats (compile count must be bounded by the ladder)")
            ok = False
        if r["nonfinite_decisions"]:
            print(f"FAIL: fused fleet {r['fleet_size']} produced "
                  f"{r['nonfinite_decisions']} non-finite decisions")
            ok = False
    if obs_row and obs_row["overhead"] > args.obs_overhead_max:
        print(f"FAIL: in-scan telemetry overhead "
              f"{obs_row['overhead'] * 100:.1f}% > "
              f"{args.obs_overhead_max * 100:.1f}% "
              f"(fused size {obs_row['fleet_size']})")
        ok = False
    wall = time.time() - t_start
    if args.budget_s and wall > args.budget_s:
        print(f"FAIL: fleet bench took {wall:.0f}s "
              f"> budget {args.budget_s:.0f}s")
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
