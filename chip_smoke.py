"""On-chip smoke run of the Enel fleet controller: one process, one TPU chip.

Drives the main control path once through its normal entry points at
deployment size -- the published model widths (HIDDEN=32, EDGE_DIM=16,
X_DIM), all four Table-II job classes (LR, MPC, K-Means, GBT) and the full
candidate grid SCALEOUT_RANGE=(4, 36) at stride 1 -- and checks each phase
against its plain reference:

1. fused fleet: ``FleetCampaign.fused_campaign`` over 1024 tenants cycling
   the four job classes (2 adaptive runs in one scanned jit), against
   ``run_stepped`` on the same plan and against a replay of its scale-out
   schedule through the numpy simulator (``NumpySimBackend``);
2. live stepped fleet: ``FleetCampaign.adaptive_campaign`` over 32 tenants
   through a double-buffered ``DecisionService`` for 5 runs (fine-tunes and
   one scratch retrain through the donated fit jit), with zero guardrail
   trips, fallbacks, retries or dispatch failures, then decisions of the
   trained models against the per-graph reference
   ``EnelScaler.recommend_pergraph``, both batched in one service call and
   one job at a time through ``EnelScaler.recommend``.

It prints each phase's wall time (first calls include compilation; no
timing here is a benchmark number), decision counts, the max deviations and
the device's peak memory, writes them to ``chiprun_out/chip_smoke.json``,
and ends with one JSON line ``{"ok": true, "device": {...}}``.  It exits
non-zero without that line on any failed check, when JAX finds no TPU, or
when the repo's ``src/`` is not beside it.

    python chip_smoke.py

The 1024 fused tenants are four classes of 256 identical tenants (same job,
seed and scenario), so profiling runs once per class and the other tenants
restore that class's snapshot: the state profiling would have produced, at
1/256 of the set-up time.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")

JOB_CYCLE = ("lr", "mpc", "kmeans", "gbt")
PROFILE_RUNS = 3
FUSED_TENANTS = 1024         # a multiple of the 4 job classes
FUSED_RUNS = 2
LIVE_TENANTS = 32
RETRAIN_EVERY = 5            # the runner's scratch-retrain cadence = live runs

# stated tolerances
FUSED_STEPPED_TOL = 1e-5     # fused vs stepped float leaves (ints exact)
SIM_RTOL = 1e-4              # chip stage runtimes vs numpy ClusterSim
DECISION_TOL = 1e-2          # predicted totals vs per-graph, x job target


class Check:
    """Collects named checks; a failed one fails the run at the end."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _max_rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-12))


# ------------------------------------------------------------- fused fleet
def fused_fleet(size: int, seed0: int = 20):
    """``size`` tenants cycling the four job classes, one seed per class,
    on one shared batched simulator (fleet_bench's ``_fused_fleet``)."""
    from repro.core.service import DecisionService
    from repro.dataflow import FleetCampaign, JobExperiment
    exps = [JobExperiment(JOB_CYCLE[i % 4], seed=seed0 + i % 4,
                          candidate_stride=1) for i in range(size)]
    camp = FleetCampaign(exps, DecisionService(), engine="batched")
    leaders = exps[:len(JOB_CYCLE)]
    for exp in leaders:
        exp.profile(PROFILE_RUNS)
    snaps = [exp.snapshot_state() for exp in leaders]
    for i, exp in enumerate(exps[len(leaders):], start=len(leaders)):
        exp.restore_state(snaps[i % len(leaders)])
    return camp


def _numpy_replay(exp, slot_state, ys, j, n_runs, c_max):
    """Replay tenant j's fused a/z schedule through the numpy reference
    simulator from the slot's pre-campaign state; returns the max relative
    stage-runtime deviation from the chip's scan."""
    from repro.dataflow.simulator import ClusterSim
    from repro.sim.engine import NumpySimBackend, SimStepRequest
    sim = ClusterSim(seed=exp.seed, scenario=exp.scenario)
    sim.load_state_dict(slot_state)
    npb = NumpySimBackend()
    slot = npb.adopt(sim, exp.job)
    a = np.asarray(ys["a"]).astype(int)
    z = np.asarray(ys["z"]).astype(int)
    rt = np.asarray(ys["rt"])
    worst = 0.0
    for r in range(n_runs):
        npb.begin_run(slot)
        clock = 0.0
        for k in range(exp.job.n_components):
            t = r * c_max + k
            res = npb.step([SimStepRequest(slot, k, int(a[t, j]),
                                           int(z[t, j]), clock, False)])[0]
            clock = res.clock_end
            for i, st in enumerate(res.component.stages):
                ref = float(np.float32(st.runtime))
                worst = max(worst, abs(float(rt[t, i, j]) - ref) / ref)
    return worst


def phase_fused(size: int, n_runs: int, check: Check) -> dict:
    import jax
    from repro.core import campaign_kernel as ck

    t0 = time.time()
    camp = fused_fleet(size)
    setup_s = time.time() - t0
    exps = camp.experiments
    backend = exps[0].backend
    ref_slots = sorted({0, 1, 2, 3, size - 4, size - 3, size - 2, size - 1})
    ref_state = {j: backend.slot_state(j) for j in ref_slots}

    t0 = time.time()
    plan = ck.build_plan(exps, n_runs)
    plan_s = time.time() - t0
    t0 = time.time()
    _, report = camp.fused_campaign(n_runs, plan=plan)
    jax.block_until_ready(report.carry)
    fused_first_s = time.time() - t0
    t0 = time.time()
    c_f, ys_f = ck.run_fused(plan)
    jax.block_until_ready(ys_f)
    fused_rerun_s = time.time() - t0
    t0 = time.time()
    c_s, ys_s = ck.run_stepped(plan)
    jax.block_until_ready(ys_s)
    stepped_first_s = time.time() - t0

    ys = report.ys
    decisions = int(np.asarray(ys["decided"]).sum())
    check(decisions > 0, f"fused: {decisions} decisions made")
    check(int(report.nonfinite.sum()) == 0
          and bool(np.isfinite(np.asarray(ys["s_next"])).all()),
          f"fused: every decision finite (nonfinite="
          f"{int(report.nonfinite.sum())})")
    check(int(report.fallbacks.sum()) == 0,
          f"fused: no in-scan fallback (fallbacks="
          f"{int(report.fallbacks.sum())})")

    # run_fused vs run_stepped: same plan, same step body
    bit_exact, worst = True, 0.0
    pairs = list(zip(jax.tree_util.tree_leaves((c_f, ys_f)),
                     jax.tree_util.tree_leaves((c_s, ys_s))))
    exact_mismatch = 0
    for a, b in pairs:
        a, b = np.asarray(a), np.asarray(b)
        if not np.array_equal(a, b):
            bit_exact = False
            if np.issubdtype(a.dtype, np.floating):
                worst = max(worst, _max_rel(a, b))
            else:
                exact_mismatch += 1
    check(exact_mismatch == 0 and worst <= FUSED_STEPPED_TOL,
          f"fused == stepped (bit_exact={bit_exact}, max rel dev {worst:.3g}"
          f" <= {FUSED_STEPPED_TOL}, integer/bool mismatches "
          f"{exact_mismatch})")

    sim_dev = max(_numpy_replay(exps[j], ref_state[j], ys, j, n_runs,
                                plan.static.c_max) for j in ref_slots)
    check(sim_dev <= SIM_RTOL,
          f"fused sim vs numpy ClusterSim on slots {ref_slots}: max rel "
          f"stage-runtime dev {sim_dev:.3g} <= {SIM_RTOL}")
    return {"tenants": size, "runs": n_runs, "steps": plan.n_steps,
            "decisions": decisions, "setup_s": setup_s,
            "plan_build_s": plan_s, "fused_first_call_s": fused_first_s,
            "fused_rerun_s": fused_rerun_s,
            "stepped_first_call_s": stepped_first_s,
            "fused_stepped_bit_exact": bit_exact,
            "fused_stepped_max_rel_dev": worst,
            "numpy_sim_max_rel_dev": sim_dev}


# ------------------------------------------------------- live stepped fleet
def _frozen_builder(exp, s_now: int):
    """The runner's future-component graph builder with every node context
    taken at the current scale-out and no software-version dropout: the
    candidate-invariant contexts the batched sweep assumes, so the per-graph
    reference and the sweep engine see the same graphs."""
    from repro.core.graph import NodeAttrs
    from repro.dataflow.runner import _to_graph
    job, enc = exp.job, exp.encoder

    def build(ci, a, z, preds):
        nodes = [NodeAttrs(
            name=spec.name,
            context=enc.node_context(job, spec.name, int(s_now * 4),
                                     drop_versions=False),
            metrics=None, start_scaleout=a if i == 0 else z,
            end_scaleout=z, time_fraction=1.0 if a == z else 0.8)
            for i, spec in enumerate(job.stages(ci))]
        return _to_graph(nodes, preds, ci)
    return build


def _decision_point(exp, s_now: int) -> dict:
    """Decision kwargs after component 0 ran at ``s_now``."""
    from repro.core.graph import summary_node
    from repro.dataflow.runner import _component_nodes
    comp = exp.sim.run_component(exp.job, 0, clock=0.0,
                                 start_scaleout=s_now, end_scaleout=s_now,
                                 inject_failures=False, failures_log=[])
    summ = summary_node(_component_nodes(exp.encoder, exp.job, comp),
                        name="P0")
    return dict(graph_builder=_frozen_builder(exp, s_now), next_comp=1,
                n_components=exp.job.n_components, elapsed=comp.runtime,
                current_scaleout=s_now, target_runtime=exp.target,
                current_summary=summ)


def pick_consistent(s: int, totals: dict, target: float, tol: float) -> bool:
    """True iff the compliant pick could return ``s`` for SOME totals within
    ``tol`` of the reference ``totals`` (smallest candidate at or under the
    target, else the least-violating one)."""
    cands = sorted(totals)
    lo = {c: totals[c] - tol for c in cands}
    hi = {c: totals[c] + tol for c in cands}
    if lo[s] <= target and all(hi[c] > target for c in cands if c < s):
        return True
    return all(hi[c] > target for c in cands) and \
        lo[s] <= min(hi.values())


def phase_live(size: int, n_runs: int, check: Check) -> dict:
    from repro.core.service import DecisionService
    from repro.dataflow import FleetCampaign, JobExperiment

    t0 = time.time()
    exps = [JobExperiment(JOB_CYCLE[i % 4], seed=100 + i,
                          candidate_stride=1) for i in range(size)]
    svc = DecisionService()
    camp = FleetCampaign(exps, svc, engine="batched")
    camp.profile(PROFILE_RUNS)
    setup_s = time.time() - t0
    t0 = time.time()
    stats, _ = camp.adaptive_campaign(n_runs)
    campaign_s = time.time() - t0

    health = svc.stats()
    decisions = int(health["decisions"])
    check(decisions > 0, f"live: {decisions} service decisions, "
          f"{health['dispatches']} dispatches")
    bad = {k: health[k] for k in ("guardrail_trips", "fallback_decisions",
                                  "retries", "dispatch_failures",
                                  "shed_requests", "breaker_trips")
           if health[k]}
    check(not bad, f"live: zero guardrail trips/fallbacks/retries/"
          f"dispatch failures ({bad or 'all 0'})")
    check(all(np.isfinite(st.runtime) for row in stats for st in row),
          "live: every run finished with a finite runtime")
    scratch = sum(e.trainer.runs_seen // RETRAIN_EVERY for e in exps)
    check(scratch >= size, f"live: {scratch} scratch retrains")
    check(all(e.trainer.params_finite() for e in exps),
          "live: every model's parameters finite after training")

    # trained models vs the plain per-graph reference, batched in one
    # service call and one job at a time through recommend()
    t0 = time.time()
    points = [(exp, _decision_point(exp, s)) for exp in exps[:len(JOB_CYCLE)]
              for s in (8, 24)]
    results = svc.decide([exp.enel.prepare_request(**kw)
                          for exp, kw in points])
    dev_svc = dev_one = 0.0
    agree = 0
    for (exp, kw), res in zip(points, results):
        s_ref, _, tot_ref = exp.enel.recommend_pergraph(**kw)
        s_one, _, tot_one = exp.enel.recommend(**kw)
        tol = DECISION_TOL * exp.target
        dev_svc = max(dev_svc, max(abs(res.totals[c] - tot_ref[c])
                                   for c in tot_ref) / exp.target)
        dev_one = max(dev_one, max(abs(tot_one[c] - tot_ref[c])
                                   for c in tot_ref) / exp.target)
        agree += int(res.scaleout == s_ref)
        check(not res.fallback and
              pick_consistent(res.scaleout, tot_ref, exp.target, tol) and
              pick_consistent(s_one, tot_ref, exp.target, tol),
              f"live {exp.job.name} s={kw['current_scaleout']}: service "
              f"pick {res.scaleout}, recommend {s_one}, per-graph {s_ref}")
    check(dev_svc <= DECISION_TOL and dev_one <= DECISION_TOL,
          f"live: totals vs per-graph, max dev / target: service "
          f"{dev_svc:.3g}, recommend {dev_one:.3g} <= {DECISION_TOL}")
    return {"tenants": size, "runs": n_runs, "decisions": decisions,
            "dispatches": int(health["dispatches"]),
            "setup_s": setup_s, "campaign_s": campaign_s,
            "reference_s": time.time() - t0,
            "reference_points": len(points), "same_pick_as_pergraph": agree,
            "service_max_dev_over_target": float(dev_svc),
            "recommend_max_dev_over_target": float(dev_one)}


# --------------------------------------------------------------------- main
def main() -> int:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}; compile cache: {cache_dir}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 2

    check = Check()
    report = {"device": device}
    for name, run in (
            ("fused", lambda: phase_fused(FUSED_TENANTS, FUSED_RUNS, check)),
            ("live", lambda: phase_live(LIVE_TENANTS, RETRAIN_EVERY, check))):
        print(f"phase {name}", flush=True)
        t0 = time.time()
        report[name] = run()
        report[name]["wall_s"] = time.time() - t0
    stats = devs[0].memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    report["failed"] = check.failed
    for name in ("fused", "live"):
        print(f"{name}: {json.dumps(report[name], sort_keys=True)}")
    print(f"peak_bytes_in_use: {report['peak_bytes_in_use']}")
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
