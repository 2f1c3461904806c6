"""Generator for fused-campaign traffic: a whole fleet's adaptive runs in
one scanned dispatch per campaign (``FleetCampaign.fused_campaign``).

The deployment file gives the job classes, the fleet size and the seeds
(one per class, shared by that class's tenants); the mix gives the runs
per campaign and what the check samples.  Set-up profiles one leader per
class, attaches the class's other tenants to the leader's trained models
(``share_models_from``) with the leader's profiled target, run counter and
simulator state, loads the weights the benchmark makes (the plain
reference's scratch fit over the leader's profiled ring) in place of the
profile's fit, builds the campaign plan and runs one warm-up campaign.
Every unit of the window is the same ``fused_campaign`` call on that plan:
the scan, its transfer to the host and the materialised run statistics.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

import harness
from checks import fits
from checks import fused as fused_checks
from reference.enel_ref import CONTROL


def build_fleet(cfg: Dict, class_seeds: List[int]):
    """Tenant ``i`` runs job class ``i % classes`` with that class's seed,
    on one shared batched simulator; returns (campaign, leaders)."""
    from repro.core.service import DecisionService
    from repro.dataflow import FleetCampaign, JobExperiment
    jobs = cfg["jobs"]
    stride = cfg["candidate_stride"]
    leaders = [JobExperiment(job, seed=s, candidate_stride=stride)
               for job, s in zip(jobs, class_seeds)]
    exps = list(leaders)
    for i in range(len(jobs), cfg["tenants"]):
        c = i % len(jobs)
        exps.append(JobExperiment(jobs[c], seed=class_seeds[c],
                                  candidate_stride=stride,
                                  share_models_from=leaders[c]))
    return FleetCampaign(exps, DecisionService(), engine="batched"), leaders


def profile_fleet(camp, leaders, profiling_runs: int) -> None:
    """Profile each leader; each follower takes its leader's target, run
    counter and simulator slot state (its models are the leader's)."""
    for exp in leaders:
        exp.profile(profiling_runs)
    n = len(leaders)
    backend = leaders[0].backend
    for i, exp in enumerate(camp.experiments[n:], start=n):
        lead = leaders[i % n]
        exp.target = lead.target
        exp._run_idx = lead._run_idx
        backend.restore_slot(exp.sim_slot, backend.slot_state(lead.sim_slot))


class Driver:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, spans):
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        self.latencies: List[float] = []
        self.reports = []
        self.setup_parts: Dict[str, float] = {}
        self.record: Dict[str, Dict] = {}
        self.attempted = self.failed = 0

    def setup(self) -> None:
        from repro.core import campaign_kernel as ck
        fused_checks.check_config(self.cfg)
        t = time.perf_counter()
        class_seeds = harness.seeds(self.seed, len(self.cfg["jobs"]))
        self.camp, leaders = build_fleet(self.cfg, class_seeds)
        self.setup_parts["fleet_s"] = time.perf_counter() - t
        t = time.perf_counter()
        profile_fleet(self.camp, leaders, self.cfg["profiling_runs"])
        self.setup_parts["profile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.made = {}
        for c, lead in enumerate(leaders):
            case = fits.made_weights(self.cfg, lead.trainer)
            case.update(program_scratch=True, got=jax.tree_util.tree_map(
                np.asarray, lead.trainer.params))
            fits.load_weights(lead.trainer, case["ref"])
            self.made[c] = case
        self.setup_parts["weights_s"] = time.perf_counter() - t
        exps = self.camp.experiments
        self.sample = fused_checks.sample_tenants(
            len(exps), len(leaders), self.mix["check"], self.seed)
        backend = exps[0].backend
        self.slot_state0 = {j: backend.slot_state(j) for j in self.sample}
        t = time.perf_counter()
        self.plan = ck.build_plan(
            exps, self.mix["runs_per_campaign"],
            inject_failures=self.mix["inject_failures"],
            retrain_every=self.cfg["retrain_every"])
        self.setup_parts["plan_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.first = self._campaign()
        self.setup_parts["warmup_s"] = time.perf_counter() - t
        from flops import fused_campaign_flops
        self.flops = fused_campaign_flops(self.plan)
        self.flops_per_unit = float(self.flops["total"])
        if self.spans.trace:
            self._instrument()

    def _instrument(self) -> None:
        """Trace runs only: spans around the host-side parts of a campaign
        call, so the trace reducer can charge idle gaps to them."""
        from repro.core import campaign_kernel as ck
        from repro.dataflow import fleet
        ck.init_carry = self.spans.wrap("carry_to_device", ck.init_carry)
        ck.carry_to_host = self.spans.wrap("carry_to_host", ck.carry_to_host)
        fleet.materialize_fused = self.spans.wrap("materialize",
                                                  fleet.materialize_fused)

    def _campaign(self):
        _, report = self.camp.fused_campaign(
            self.mix["runs_per_campaign"], plan=self.plan, write_back=False)
        return report

    def start_window(self) -> None:
        self.reports = []

    def unit(self) -> int:
        report = self._campaign()
        self.reports.append(report)
        n = int(np.asarray(report.ys["decided"]).sum())
        self.attempted += n
        self.failed += int(report.fallbacks.sum() + report.nonfinite.sum())
        return n

    def check(self) -> Dict:
        """Name -> number for every comparison of this run."""
        from repro.core import campaign_kernel as ck
        last = self.reports[-1]
        nums = {
            "repeat_mismatch": float(sum(
                fused_checks.mismatched_leaves(self.first, r)
                for r in self.reports)),
            "copy_mismatch": float(fused_checks.copy_mismatch(
                last, len(self.cfg["jobs"]))),
            "fallbacks": float(last.fallbacks.sum()),
            "nonfinite": float(last.nonfinite.sum()),
        }
        c_max = self.plan.static.c_max
        t_fit = fused_checks.tune_run(self.plan, self.seed) * c_max \
            + c_max - 1
        stepped, before, after = fused_checks.stepped_run(
            self.plan, t_fit, self.sample)
        nums["stepped_mismatch"] = float(fused_checks.mismatched_leaves(
            last, stepped))
        exps = self.camp.experiments
        nums["sim_rel_dev"], self.observed = fused_checks.sim_replay(
            exps, self.slot_state0, last.ys, self.plan)
        self.cases = fused_checks.fit_cases(
            self.cfg, self.plan, exps, self.sample, self.made, t_fit,
            before, after, last)
        fits.reference_fits(self.cfg, self.cases)
        numbers, self.record["worst_leaf"] = fits.fit_numbers(self.cases)
        nums.update(numbers)
        self.points = fused_checks.sample_decisions(
            self.plan, self.sample, self.mix["check"]["decisions_per_tenant"],
            self.seed)
        numbers, self.record["decisions"] = fused_checks.decision_numbers(
            self.plan, self.made, last.ys, self.observed, self.points)
        nums.update(numbers)
        return nums

    def control(self) -> Dict:
        """The control (``enel_ref.CONTROL``) put in the program's place,
        read by the same comparisons (call after :meth:`check`)."""
        fits.reference_fits(self.cfg, self.cases, CONTROL)
        out, self.record["control_worst_leaf"] = fits.fit_numbers(
            self.cases, control=True)
        numbers, self.record["control_decisions"] = \
            fused_checks.decision_numbers(self.plan, self.made,
                                          self.reports[-1].ys, self.observed,
                                          self.points, CONTROL)
        out.update(numbers)
        return out
