"""Generator for live lockstep traffic: every tenant runs its adaptive runs
through the host path, in lockstep rounds (``FleetCampaign.adaptive_campaign``)
over one shared batched simulator and one double-buffered
``DecisionService``.

The deployment file gives the job classes and the team's size; each
tenant has a seed of its own, drawn from the run's seed.  The mix gives the
runs per unit and the warm-up runs.  Set-up builds and profiles every
tenant, runs the warm-up, and loads the weights the benchmark makes into
the tenants the check samples.  A unit of the window is one more adaptive
run of every tenant.

A decision's latency runs from the moment the shared simulator's ``step``
returned the job's component result to the end of the generator step that
applies the decision; both ends are taken by wrappers on the instances
this driver builds.  Trace runs also open spans around request preparation,
service dispatch, fits and simulator steps.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import harness
from checks import fits
from checks import live as live_checks
from checks.fused import check_config
from reference.enel_ref import CONTROL


class _StepClock:
    """Wraps the shared simulator's ``step``; remembers when it returned."""

    def __init__(self, step, spans):
        self._step = step
        self._spans = spans
        self.returned = 0.0

    def __call__(self, requests):
        if self._spans.trace:
            with self._spans.span("sim"):
                out = self._step(requests)
        else:
            out = self._step(requests)
        self.returned = time.perf_counter()
        return out


class Driver:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, spans):
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        self.latencies: List[float] = []
        self.setup_parts: Dict[str, float] = {}
        self.record: Dict[str, Dict] = {}
        self.attempted = self.failed = 0
        self.flops_per_unit = None
        self.recording = False

    def setup(self) -> None:
        from repro.core.service import DecisionService
        from repro.dataflow import FleetCampaign, JobExperiment
        check_config(self.cfg)
        t = time.perf_counter()
        jobs, n = self.cfg["jobs"], self.cfg["tenants"]
        tenant_seeds = harness.seeds(self.seed, n)
        self.exps = [JobExperiment(jobs[i % len(jobs)], seed=tenant_seeds[i],
                                   candidate_stride=self.cfg[
                                       "candidate_stride"])
                     for i in range(n)]
        self.svc = DecisionService()
        self.camp = FleetCampaign(self.exps, self.svc, engine="batched")
        self.setup_parts["fleet_s"] = time.perf_counter() - t
        self.capture = live_checks.Capture(
            self.exps, self.mix["check"], self.cfg, self.seed)
        self._instrument()
        t = time.perf_counter()
        self.camp.profile(self.cfg["profiling_runs"])
        self.setup_parts["profile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self._runs(self.mix["warmup_runs"])
        self.capture.warm()
        self.setup_parts["warmup_s"] = time.perf_counter() - t
        t = time.perf_counter()
        for i in sorted(self.capture.tenants):
            trainer = self.exps[i].trainer
            made = fits.made_weights(self.cfg, trainer)["ref"]
            fits.load_weights(trainer, made)
            self.capture.made[i] = made
        self.setup_parts["weights_s"] = time.perf_counter() - t

    def _instrument(self) -> None:
        backend = self.exps[0].backend
        self.clock = _StepClock(backend.step, self.spans)
        backend.step = self.clock
        for i, exp in enumerate(self.exps):
            exp.adaptive_run_gen = self._timed_gen(i, exp.adaptive_run_gen)
            exp.trainer.fit_resident = self.capture.fit_wrapper(
                i, exp.trainer, self.spans.wrap("fit",
                                                exp.trainer.fit_resident)
                if self.spans.trace else exp.trainer.fit_resident)
            if self.spans.trace:
                exp.enel.prepare_request = self.spans.wrap(
                    "prep", exp.enel.prepare_request)
        if self.spans.trace:
            self.svc.decide = self.spans.wrap("dispatch", self.svc.decide)

    def _timed_gen(self, i: int, make):
        """Wraps tenant ``i``'s run generator: stamps each decision from the
        simulator return that preceded it to the end of the send that
        delivered it, and offers requests and results to the capture."""
        from repro.core.service import DecisionRequest

        def gen(*args, **kwargs):
            self.capture.start_run(i)
            inner = make(*args, **kwargs)
            req = next(inner)
            t_obs = 0.0
            while True:
                res = yield req
                decision = isinstance(req, DecisionRequest)
                if decision:
                    self.capture.offer(i, req, res)
                else:
                    t_obs = self.clock.returned
                try:
                    req = inner.send(res)
                except StopIteration as stop:
                    if decision and self.recording:
                        self.latencies.append(time.perf_counter() - t_obs)
                    return stop.value
                if decision and self.recording:
                    self.latencies.append(time.perf_counter() - t_obs)
        return gen

    def _runs(self, n: int):
        stats, _ = self.camp.adaptive_campaign(
            n, inject_failures=self.mix["inject_failures"])
        self.run_stats = stats
        return stats

    def start_window(self) -> None:
        self.latencies = []
        self.health0 = self.svc.stats()
        self.recording = True
        self.capture.arm()

    def unit(self) -> int:
        before = len(self.latencies)
        stats = self._runs(self.mix["runs_per_unit"])
        self.all_stats = getattr(self, "all_stats", []) + stats
        n = len(self.latencies) - before
        self.attempted += n
        self.failed += sum(st.fallback_decisions + st.shed_requests
                           for row in stats for st in row)
        return n

    def check(self) -> Dict:
        """Name -> number for every comparison of this run."""
        self.recording = False
        health = self.svc.stats()
        bad = sum(int(health[k]) - int(self.health0[k])
                  for k in ("guardrail_trips", "fallback_decisions",
                            "retries", "dispatch_failures",
                            "shed_requests", "breaker_trips"))
        nums = {"service_faults": float(bad)}
        nums["nonfinite_params"] = float(sum(
            not e.trainer.params_finite() for e in self.exps))
        runs = getattr(self, "all_stats", [])
        nums["unfinished_runs"] = float(
            sum(st is None or not np.isfinite(st.runtime)
                for row in runs for st in row)
            + (len(self.exps) * len(runs) - sum(len(r) for r in runs)))
        self.capture.armed = False
        self.cases = self.capture.cases()
        fits.reference_fits(self.cfg, self.cases)
        numbers, self.record["decisions"] = self.capture.decision_numbers()
        nums.update(numbers)
        numbers, self.record["worst_leaf"] = fits.fit_numbers(self.cases)
        nums.update(numbers)
        return nums

    def control(self) -> Dict:
        """The control (``enel_ref.CONTROL``) put in the program's place,
        read by the same comparisons (call after :meth:`check`)."""
        fits.reference_fits(self.cfg, self.cases, CONTROL)
        out, self.record["control_decisions"] = \
            self.capture.decision_numbers(CONTROL)
        numbers, self.record["control_worst_leaf"] = fits.fit_numbers(
            self.cases, control=True)
        out.update(numbers)
        return out
