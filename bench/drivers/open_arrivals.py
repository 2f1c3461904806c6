"""Generator for open-loop arrival traffic: a fleet of tenants reaching their
decision points independently, served by ``FleetCampaign.serve_arrivals``
through one ``DecisionService`` and one shared batched simulator.

The deployment file gives the job classes and the fleet's size; each
tenant has a seed of its own, drawn from the run's seed.  Set-up profiles
one leader per class, loads the weights the benchmark makes into it, and
gives every other tenant of the class its own copy of the leader's profiled
state (``adopt_profile``: model, optimizer state and history ring as
separate device arrays; the context encoder shared) with a retrain phase of
its own.  The leaders then run one whole run, which visits every shape a
decision can take; every (bucket, job rung) pair of the service compiles;
every tenant advances, untimed, to a decision point drawn uniformly from
its run; and a short warm-up schedule is served.  Retrain phases and
starting points are drawn evenly within each class (``balanced``: each
tenant's uniform, the class's together as even as its size allows), so a
run's mix of positions is the steady state's and not a draw's.

A unit of the window is ``unit_seconds`` of arrivals followed by draining
the queue (so a unit never ends before its schedule does): exactly
``round(unit_seconds * mean_rate)`` arrivals, placed by a Poisson process
whose rate is ``burst_factor`` times the calm rate in one ``burst_seconds``
burst at an offset drawn from the seed, the calm rate set so that the mean
is ``mean_rate``, conditioned on that count.  Each arrival's class is
drawn in proportion to its decisions per second (11 decisions a run over
the class's mean profiled run time), its tenant uniformly among the
class's tenants not yet due in the unit.

A decision's latency runs from its scheduled arrival to the end of the
generator send that applies it; its queue wait from the scheduled arrival
to the start of the ``decide()`` that took it.  Trace runs also open spans
around request preparation, service dispatch, the shared simulator's step
and each fit.  The benchmark's spans and counts start afresh with the
window.
"""
from __future__ import annotations

import time
from collections import Counter
from typing import Dict, List

import jax
import numpy as np

import harness
from checks import arrivals as arrival_checks
from checks import fits
from checks.fused import check_config
from reference.enel_ref import CONTROL

DECISIONS_PER_RUN = 11      # every Table II job decides 11 times a run


def burst_schedule(rng, n: int, seconds: float, burst_s: float,
                   factor: float):
    """``n`` arrival times in [0, seconds): a Poisson process whose rate is
    ``factor`` times the calm rate inside one ``burst_s`` burst, conditioned
    on its count: each arrival falls in the burst with the burst's share of
    the expected count.  Returns (sorted times, burst start)."""
    start = rng.uniform(0.0, seconds - burst_s)
    calm_s = seconds - burst_s
    n_burst = rng.binomial(n, factor * burst_s / (factor * burst_s + calm_s))
    calm = rng.uniform(0.0, calm_s, n - n_burst)
    calm = np.where(calm >= start, calm + burst_s, calm)
    t = np.concatenate([start + rng.uniform(0.0, burst_s, n_burst), calm])
    return np.sort(t), start


def balanced(rng, n: int, values: int) -> np.ndarray:
    """``n`` draws of ``range(values)``, each uniform, together as even as
    ``n`` allows: every value ``n // values`` times and a random few once
    more, in a random order."""
    rest = rng.choice(values, n % values, replace=False)
    return rng.permutation(np.concatenate(
        [np.tile(np.arange(values), n // values), rest]))


class Driver:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, spans):
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        self.latencies: List[float] = []
        self.setup_parts: Dict[str, float] = {}
        self.record: Dict[str, Dict] = {}
        self.attempted = self.failed = 0
        self.flops_per_unit = None
        self.recording = False
        self.rng = np.random.default_rng(harness.seeds(seed, 3)[2])
        self.batches: Counter = Counter()
        self.due: Dict[int, List[float]] = {}   # tenant -> this unit's dues
        self.rid_due: Dict[int, float] = {}     # request id -> its due

    # ---------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core.service import DecisionService
        from repro.dataflow import FleetCampaign, JobExperiment
        if not hasattr(FleetCampaign, "serve_arrivals"):
            raise RuntimeError("the program has no open-loop entry point "
                               "(FleetCampaign.serve_arrivals)")
        check_config(self.cfg)
        t = time.perf_counter()
        jobs, n = self.cfg["jobs"], self.cfg["tenants"]
        classes = len(jobs)
        tenant_seeds = harness.seeds(self.seed, n)
        stride = self.cfg["candidate_stride"]
        leaders = [JobExperiment(jobs[c], seed=tenant_seeds[c],
                                 candidate_stride=stride)
                   for c in range(classes)]
        # followers start attached to their class leader (no encoder of
        # their own to train, no model to initialise) until adopt_profile
        # gives each its own copy of the leader's profiled state
        self.exps = leaders + [
            JobExperiment(jobs[i % classes], seed=tenant_seeds[i],
                          candidate_stride=stride,
                          share_models_from=leaders[i % classes])
            for i in range(classes, n)]
        self.svc = DecisionService()
        self.camp = FleetCampaign(self.exps, self.svc, engine="batched")
        self.setup_parts["fleet_s"] = time.perf_counter() - t
        t = time.perf_counter()
        for lead in leaders:
            lead.profile(self.cfg["profiling_runs"])
        self.setup_parts["profile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        for lead in leaders:
            fits.load_weights(lead.trainer,
                              fits.made_weights(self.cfg,
                                                lead.trainer)["ref"])
        phase = {c: iter(balanced(self.rng, len(range(c, n, classes)) - 1,
                                  self.cfg["retrain_every"]))
                 for c in range(classes)}
        for i, exp in enumerate(self.exps[classes:], start=classes):
            exp.adopt_profile(leaders[i % classes])
            exp.trainer.runs_seen = int(next(phase[i % classes]))
        self.setup_parts["adopt_s"] = time.perf_counter() - t
        # classes decide in proportion to 11 decisions a run over the mean
        # profiled run time
        rate = np.array([DECISIONS_PER_RUN / np.mean(
            [st.runtime for st in lead.stats]) for lead in leaders])
        self.class_p = rate / rate.sum()
        self.members = [np.arange(c, n, classes) for c in range(classes)]
        sim_tenants = [int(self.rng.choice(m)) for m in self.members]
        self.accounting = arrival_checks.Accounting()
        self.capture = arrival_checks.Capture(
            self.exps, sim_tenants, classes, self.cfg["retrain_every"])
        self._instrument()
        # the leaders run one whole run first: it visits every shape a
        # decision can take, and ends in a fine-tune
        t = time.perf_counter()
        self.shapes: Dict[tuple, object] = {}
        self.camp.serve_arrivals([(0.0, c) for c in range(classes)
                                  for _ in range(DECISIONS_PER_RUN)])
        self.setup_parts["leader_run_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self._compile_rungs()
        self.setup_parts["compile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        start = np.zeros(n, int)
        for m in self.members:
            start[m] = balanced(self.rng, len(m), DECISIONS_PER_RUN)
        self.camp.serve_arrivals([(0.0, i) for i in range(n)
                                  for _ in range(int(start[i]))])
        self.setup_parts["position_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self._unit(self.mix["warmup_seconds"])
        self.setup_parts["warmup_s"] = time.perf_counter() - t

    def _compile_rungs(self) -> None:
        """One decide() of every job rung for every bucket a decision can
        take, on requests kept from the leaders' run with their current
        parameters (the run's fit donated those they held).  The buckets
        compile side by side, each on a service of its own, into the jit
        caches every service shares."""
        import dataclasses
        from concurrent.futures import ThreadPoolExecutor
        from repro.core.service import JOB_LADDER, DecisionService

        def compile_bucket(item):
            i, req = item
            req = dataclasses.replace(req,
                                      params=self.exps[i].trainer.params)
            svc = DecisionService()
            for j in JOB_LADDER:
                svc.decide([dataclasses.replace(req) for _ in range(j)])
        with ThreadPoolExecutor(len(self.shapes)) as pool:
            list(pool.map(compile_bucket, self.shapes.values()))

    def _instrument(self) -> None:
        trace = self.spans.trace
        if trace:
            backend = self.exps[0].backend
            backend.step = self.spans.wrap("sim", backend.step)
        for i, exp in enumerate(self.exps):
            exp.adaptive_run_gen = self._tenant_gen(i, exp.adaptive_run_gen)
            fit = exp.trainer.fit_resident
            exp.trainer.fit_resident = self.capture.fit_wrapper(
                i, exp.trainer, self.spans.wrap("fit", fit) if trace else fit)
            if trace:
                exp.enel.prepare_request = self.spans.wrap(
                    "prep", exp.enel.prepare_request)
        decide = self.svc.decide

        def timed_decide(reqs):
            t0 = time.perf_counter()
            if self.recording:
                self.spans.durations["queue_wait"] += [
                    t0 - self.rid_due[r.rid] for r in reqs
                    if r.rid in self.rid_due]
                self.batches[len(reqs)] += 1
            return decide(reqs)
        self.svc.decide = self.spans.wrap("dispatch", timed_decide) \
            if self.spans.trace else timed_decide

    def _tenant_gen(self, i: int, make):
        """Wraps tenant ``i``'s run generator: stamps each decision's end,
        accounts each result against the request it answers, and offers
        requests and sim steps to the capture."""
        from repro.core.service import DecisionRequest

        def gen(*args, **kwargs):
            self.capture.new_run(i)
            inner = make(*args, **kwargs)
            req = next(inner)
            while True:
                if isinstance(req, DecisionRequest):
                    self.shapes.setdefault(req.bucket_key, (i, req))
                    if self.recording and self.due.get(i):
                        # a tenant's arrivals are delivered in due order
                        self.rid_due[req.rid] = self.due[i].pop(0)
                res = yield req
                try:
                    nxt = inner.send(res)
                except StopIteration as stop:
                    nxt, value = None, stop.value
                if isinstance(req, DecisionRequest):
                    if self.recording:
                        if req.rid in self.rid_due:
                            self.latencies.append(time.perf_counter()
                                                  - self.rid_due[req.rid])
                        self.accounting.applied_result(i, req, res)
                        self.capture.offer_decision(i, req, res)
                else:
                    self.capture.offer_step(i, req, res)
                if nxt is None:
                    return value
                req = nxt
        return gen

    # ---------------------------------------------------------------- window
    def schedule(self, seconds: float, rate: float, burst: bool = True):
        """One unit's (time, tenant) arrivals at mean ``rate``."""
        n = int(round(seconds * rate))
        if burst:
            t, _ = burst_schedule(self.rng, n, seconds,
                                  self.mix["burst_seconds"],
                                  self.mix["burst_factor"])
        else:
            t = np.sort(self.rng.uniform(0.0, seconds, n))
        cls = self.rng.choice(len(self.members), size=n, p=self.class_p)
        free = [list(self.rng.permutation(m)) for m in self.members]
        out = []
        for ti, c in zip(t, cls):
            if not free[c]:
                free[c] = list(self.rng.permutation(self.members[c]))
            out.append((float(ti), int(free[c].pop())))
        return out

    def _unit(self, seconds: float, rate: float = None, burst: bool = True):
        sched = self.schedule(seconds, rate or self.mix["mean_rate"], burst)
        start = self.last_start = time.perf_counter()
        self.due = {}
        for t, i in sched:
            self.due.setdefault(i, []).append(start + t)
        if self.recording:
            self.accounting.start_unit({i: len(d)
                                        for i, d in self.due.items()})
        arrivals = self.camp.serve_arrivals(sched, start=start)
        if self.recording:
            self.accounting.end_unit()
        # the unit spans its whole schedule, however early the queue drains
        time.sleep(max(0.0, start + seconds - time.perf_counter()))
        return arrivals

    def start_window(self) -> None:
        # set-up's spans (positioning alone prepares ~5,000 requests) are
        # not the window's
        self.spans.durations.clear()
        self.latencies = []
        self.accounting = arrival_checks.Accounting()
        self.capture.clear()
        self.batches.clear()
        self.health0 = self.svc.stats()
        self.run_ends0 = sum(len(v) for v in self.camp.open_stats.values())
        self.recording = True
        self.capture.armed = True

    def unit(self) -> int:
        before = self.svc.stats()
        arrivals = self._unit(self.mix["unit_seconds"])
        after = self.svc.stats()
        self.attempted += len(arrivals)
        self.failed += sum(after[k] - before[k]
                           for k in ("fallback_decisions", "shed_requests"))
        return len(arrivals)

    # ----------------------------------------------------------------- check
    def check(self) -> Dict:
        """Name -> number for every comparison of this run."""
        self.recording = False
        self.capture.armed = False
        health = self.svc.stats()
        bad = sum(int(health[k]) - int(self.health0[k])
                  for k in ("guardrail_trips", "fallback_decisions",
                            "retries", "dispatch_failures",
                            "shed_requests", "breaker_trips"))
        acc = self.accounting
        nums = {"service_faults": float(bad),
                "unanswered": float(acc.unanswered),
                "misrouted_or_duplicate": float(acc.misrouted_or_duplicate)}
        params = jax.device_get([e.trainer.params for e in self.exps])
        nums["nonfinite_params"] = float(sum(
            not all(np.isfinite(x).all() for x in jax.tree_util.tree_leaves(p))
            for p in params))
        nums["sim_rel_dev"] = self.capture.sim_replay()
        numbers, self.record["decisions"] = \
            self.capture.decision_numbers()
        nums.update(numbers)
        self.cases = self.capture.fit_cases(self.cfg)
        fits.reference_fits(self.cfg, self.cases)
        numbers, self.record["worst_leaf"] = \
            arrival_checks.window_fit_numbers(self.cases)
        nums.update(numbers)
        # the control's readings of the same decisions, for the record: on
        # every run they show the limits apart from the float8 control
        self._control = self.capture.decision_numbers(CONTROL)
        self.record["control"] = self._control[0]
        lookups = health["memo_lookups"] - self.health0["memo_lookups"]
        self.record["serve"] = {
            "decide_batches": dict(sorted(self.batches.items())),
            "memo_lookups": lookups,
            "memo_hit_share": (health["memo_hits"]
                               - self.health0["memo_hits"])
            / max(lookups, 1),
            "dispatches": health["dispatches"] - self.health0["dispatches"],
            "run_ends": sum(len(v) for v in self.camp.open_stats.values())
            - self.run_ends0}
        return nums

    def control(self) -> Dict:
        """The control (``enel_ref.CONTROL``) put in the program's place,
        read by the same comparisons (call after :meth:`check`)."""
        out, self.record["control_decisions"] = self._control
        out = dict(out)
        fits.reference_fits(self.cfg, self.cases, CONTROL)
        numbers, self.record["control_worst_leaf"] = \
            arrival_checks.window_fit_numbers(self.cases, control=True)
        out.update(numbers)
        return out
