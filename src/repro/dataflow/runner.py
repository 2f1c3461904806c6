"""The paper's experiment protocol (§V-B): profiling runs, adaptive runs with
dynamic scaling (Enel vs Ellis), failure phases, CVC/CVS metrics.

Per job: 10 profiling runs (no scaling) -> initial model fit -> adaptive runs
where the scaler is consulted at every component boundary.  Enel retrains
from scratch every 5th run and fine-tunes otherwise; Ellis refits its
per-component model ensemble after every run.

The execution loop is a generator that YIELDS two kinds of requests and
receives their results:

* :class:`~repro.sim.engine.SimStepRequest` — the next component's
  simulated execution, answered by a sim backend: the per-job numpy event
  loop (:class:`~repro.sim.engine.NumpySimBackend`, ``engine="numpy"``) or
  the vectorized fleet engine
  (:class:`~repro.sim.engine.BatchedClusterSim`, ``engine="batched"``,
  bit-identical at batch=1), which a fleet campaign steps for ALL
  concurrent jobs in one device dispatch;
* :class:`~repro.core.service.DecisionRequest` — the pending rescaling
  decision, answered by a :class:`~repro.core.service.DecisionService`
  (shape-bucketed; cross-job batched under a campaign).

Disturbance scenarios (``repro.sim.scenarios``) and dataset-size scaling
(``size_scale``) parameterize the execution context; ``share_models_from``
transplants a trained model into a new context for the paper's
cross-context reuse claim (see ``repro.sim.evaluate``).
"""
from __future__ import annotations

import copy
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.graph import (ComponentGraph, NodeAttrs, build_graph,
                              historical_summary, summary_node)
from repro.core.scaling import EnelScaler
from repro.core.ellis import EllisScaler
from repro.core.service import DecisionRequest, DecisionService
from repro.core.training import EnelTrainer
from repro.dataflow.context import ContextEncoder
from repro.dataflow.simulator import (ClusterSim, ComponentRecord, RunRecord,
                                      rescale_overhead)
from repro.dataflow.workloads import JOBS, SCALEOUT_RANGE, JobSpec, scale_job
from repro.sim.engine import (BatchedClusterSim, NumpySimBackend,
                              SimStepRequest)
from repro.sim.scenarios import BASELINE, Scenario

PROFILING_SCALEOUTS = [4, 8, 11, 14, 18, 21, 25, 28, 32, 36]
HISTORY_WINDOW = 96           # newest graphs kept for scratch retraining


@dataclass
class RunStats:
    run_idx: int
    kind: str                 # profiling | enel | ellis
    runtime: float
    target: float
    violation: float
    predicted: Optional[float] = None
    scaleouts: List[int] = field(default_factory=list)
    n_failures: int = 0
    n_rescales: int = 0
    fit_seconds: float = 0.0
    decide_seconds: float = 0.0
    decide_calls: int = 0
    # fault-tolerance counters: decisions answered by the model-free
    # fallback / shed under overload during this run, plus this run's share
    # of service-wide dispatch retries and breaker trips (deltas over the
    # run — service-wide under a fleet campaign, see adaptive_run_gen)
    fallback_decisions: int = 0
    shed_requests: int = 0
    retries: int = 0
    breaker_trips: int = 0

    @property
    def cvc(self) -> int:
        return int(self.violation > 0)

    @property
    def decide_seconds_per_call(self) -> float:
        return self.decide_seconds / self.decide_calls if self.decide_calls \
            else 0.0


def _component_nodes(encoder: ContextEncoder, job: JobSpec,
                     comp: ComponentRecord) -> List[NodeAttrs]:
    nodes = []
    for st in comp.stages:
        ctx = encoder.node_context(job, st.name, int(st.end_scaleout * 4),
                                   attempt=st.failures)
        nodes.append(NodeAttrs(
            name=st.name, context=ctx, metrics=st.metrics,
            start_scaleout=st.start_scaleout, end_scaleout=st.end_scaleout,
            time_fraction=st.time_fraction, runtime=st.runtime,
            overhead=st.overhead if st.overhead > 0 else None))
    return nodes


def _future_nodes(encoder: ContextEncoder, job: JobSpec, comp_idx: int,
                  a: float, z: float) -> List[NodeAttrs]:
    nodes = []
    for i, spec in enumerate(job.stages(comp_idx)):
        ctx = encoder.node_context(job, spec.name, int(z * 4))
        nodes.append(NodeAttrs(
            name=spec.name, context=ctx, metrics=None,
            start_scaleout=a if i == 0 else z, end_scaleout=z,
            time_fraction=1.0 if a == z else 0.8))
    return nodes


def frozen_context_tables(encoder: ContextEncoder, job: JobSpec
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic node-context tables for the fused campaign planner.

    Returns ``(ctx (C, S_max, NS, CTX_DIM) f32, n_stages (C,) int32)`` with
    NS spanning the whole scale-out grid (``SCALEOUT_RANGE[0]..[1]``): entry
    ``[c, i, s - lo]`` is component c / stage i's context at scale-out s.
    Built with ``drop_versions=False`` so NO encoder RNG is consumed — the
    fused campaign freezes contexts at plan time (documented deviation from
    the live path's per-observation software-version dropout; ``attempt`` is
    likewise frozen at 0).  The embed cache makes repeat lookups cheap.
    """
    lo, hi = SCALEOUT_RANGE
    grid = np.arange(lo, hi + 1)
    n_comp = job.n_components
    s_max = max(len(job.stages(c)) for c in range(n_comp))
    ctx = np.zeros((n_comp, s_max, len(grid), 24), np.float32)
    n_stages = np.zeros(n_comp, np.int32)
    for c in range(n_comp):
        specs = job.stages(c)
        n_stages[c] = len(specs)
        for i, spec in enumerate(specs):
            for si, s in enumerate(grid):
                ctx[c, i, si] = encoder.node_context(
                    job, spec.name, int(s * 4), drop_versions=False)
    return ctx, n_stages


def _to_graph(nodes: List[NodeAttrs], preds: List[NodeAttrs],
              comp_idx: int) -> ComponentGraph:
    n = len(nodes)
    all_nodes = nodes + preds
    edges = [(i, i + 1) for i in range(n - 1)]
    edges += [(n + j, 0) for j in range(len(preds))]
    return build_graph(all_nodes, edges, component_id=comp_idx)


def drive(gen, service: Optional[DecisionService], backend=None):
    """Run an execution generator to completion, answering each yielded
    :class:`SimStepRequest` with the backend's component record and each
    :class:`DecisionRequest` with the service's decision."""
    try:
        req = next(gen)
        while True:
            if isinstance(req, SimStepRequest):
                req = gen.send(backend.step([req])[0])
            else:
                req = gen.send(service.decide([req])[0])
    except StopIteration as stop:
        return stop.value


class JobExperiment:
    """Shared environment for one job: simulator, encoder, both scalers.

    ``engine`` selects the sim backend ("numpy": per-job reference event
    loop; "batched": vectorized engine — bit-identical, and batched across
    jobs when a shared ``backend`` is passed, e.g. by a fleet campaign).
    ``scenario`` injects seeded disturbances; ``size_scale`` scales the
    dataset (cross-context axis); ``share_models_from`` reuses another
    experiment's trained model/encoder/scalers instead of fresh ones
    (transfer deployment — the source experiment should be done running).
    """

    def __init__(self, job_key: str, seed: int = 0,
                 candidate_stride: int = 2,
                 service: Optional[DecisionService] = None,
                 engine: str = "numpy",
                 scenario: Optional[Scenario] = None,
                 backend=None, size_scale: float = 1.0,
                 share_models_from: Optional["JobExperiment"] = None):
        job = JOBS[job_key]
        if size_scale != 1.0:
            job = scale_job(job, size_scale)
        self.job = job
        self.job_key = job_key
        self.seed = seed
        self.scenario = scenario or BASELINE
        self.engine = engine
        self.sim = ClusterSim(seed=seed, scenario=self.scenario)
        if backend is not None:
            self.backend = backend
        elif engine == "batched":
            self.backend = BatchedClusterSim()
        elif engine == "numpy":
            self.backend = NumpySimBackend()
        else:
            raise ValueError(f"unknown engine {engine!r}")
        if isinstance(self.backend, NumpySimBackend):
            self.sim_slot = self.backend.adopt(self.sim, self.job)
        else:
            self.sim_slot = self.backend.register(self.job, seed,
                                                  self.scenario)
        if share_models_from is not None:
            src = share_models_from
            self.encoder = src.encoder
            self.trainer = src.trainer
            self.enel = src.enel
            self.ellis = src.ellis
        else:
            self.encoder = ContextEncoder([self.job], seed=seed)
            self.trainer = EnelTrainer(seed=seed,
                                       cache_capacity=HISTORY_WINDOW)
            self.enel = EnelScaler(self.trainer, SCALEOUT_RANGE,
                                   candidate_stride=candidate_stride)
            self.ellis = EllisScaler(SCALEOUT_RANGE,
                                     rescale_overhead=rescale_overhead(4, 8),
                                     candidate_stride=candidate_stride)
        self.service = service or DecisionService()
        # decision cadence: every component for short jobs, every 2nd for
        # the 22-component LR/MPC (keeps the campaign tractable on 1 core)
        self.decision_interval = 2 if self.job.n_components > 15 else 1
        self.scale_cap: Optional[int] = None   # multi-tenant capacity cap
        self.best_effort = False     # shed first under service overload
        self.chaos = None            # optional per-experiment fault injector
        self.graph_history: List[ComponentGraph] = []
        self.target: Optional[float] = None
        self.stats: List[RunStats] = []
        self._run_idx = 0

    def decides_after(self, comp_idx: int) -> bool:
        """Whether an adaptive run asks for a decision once component
        ``comp_idx`` has run."""
        return comp_idx < self.job.n_components - 1 and \
            comp_idx % self.decision_interval == 0

    def adopt_profile(self, src: "JobExperiment") -> None:
        """Make ``src``'s profiled state this tenant's own: its target,
        history and learned models, copied into this tenant's own trainer
        (new device buffers, no buffer aliased; this tenant's seed keys its
        dropout and scratch retrains), scaler and Ellis model.  The context
        encoder, which describes the job and its dataset, is shared.  The
        sim slot stays this tenant's own.  Profiling one tenant per class
        and adopting it costs a fraction of profiling every tenant."""
        self.encoder = src.encoder
        self.trainer = src.trainer.copy(self.seed)
        self.enel = src.enel.copy(self.trainer)
        self.ellis = copy.deepcopy(src.ellis)
        self.target = src.target
        self._run_idx = src._run_idx
        self.stats = copy.deepcopy(src.stats)
        self.graph_history = list(src.graph_history)

    # ----------------------------------------------------------- checkpoint
    def snapshot_state(self) -> Dict:
        """Everything a trace-identical resume needs: learned state (model
        params, optimizer moments, cache rings, observation histories), the
        sim slot's RNG/clock state and the bookkeeping counters.  Perf-only
        caches (edge lists, candidate arrays, memoized stacks) are skipped —
        they repopulate deterministically.  Graph/summary lists hold
        append-only immutable records, so shallow list copies suffice."""
        return {
            "run_idx": int(self._run_idx),
            "target": self.target,
            "scale_cap": self.scale_cap,
            "best_effort": bool(self.best_effort),
            "stats": copy.deepcopy(self.stats),
            "graph_history": list(self.graph_history),
            # node_context consumes the encoder's rng per call (the random
            # version-dropout of the v-group), so replay must re-draw the
            # same stream
            "encoder_rng": self.encoder.rng.get_state(),
            "trainer": self.trainer.snapshot_state(),
            "enel": {
                "hist_summaries": {k: list(v) for k, v in
                                   self.enel.hist_summaries.items()},
                "first_component_history":
                    list(self.enel.first_component_history),
                # NOT perf-only: a probe-cache MISS makes build_sweep call
                # the graph builder twice more, consuming encoder rng draws
                # — the hit/miss pattern must replay exactly (entries are
                # immutable tuples, a shallow dict copy suffices)
                "probe_cache": dict(self.enel._probe_cache),
            },
            "ellis_history": {k: list(v) for k, v in
                              self.ellis.history.items()},
            # fitted models are snapshotted, NOT refit on restore: under
            # method="enel" they are deliberately stale relative to the
            # growing history (last fit at profile time), and a refit would
            # diverge the s0 recommendation from the uninterrupted trace
            "ellis_models": copy.deepcopy(self.ellis.models),
            "backend": self.backend.slot_state(self.sim_slot),
        }

    def restore_state(self, state: Dict) -> None:
        """Inverse of :meth:`snapshot_state`; the snapshot itself is left
        pristine (fresh copies are handed out), so one checkpoint can be
        restored any number of times."""
        self._run_idx = int(state["run_idx"])
        self.target = state["target"]
        self.scale_cap = state["scale_cap"]
        self.best_effort = bool(state["best_effort"])
        self.stats = copy.deepcopy(state["stats"])
        self.graph_history = list(state["graph_history"])
        self.encoder.rng.set_state(state["encoder_rng"])
        self.trainer.restore_state(state["trainer"])
        self.enel.hist_summaries = defaultdict(
            list, {k: list(v) for k, v in
                   state["enel"]["hist_summaries"].items()})
        self.enel.first_component_history = \
            list(state["enel"]["first_component_history"])
        self.enel._probe_cache = dict(state["enel"]["probe_cache"])
        self.ellis.history = defaultdict(
            list, {k: list(v) for k, v in state["ellis_history"].items()})
        self.ellis.models = copy.deepcopy(state["ellis_models"])
        self.backend.restore_slot(self.sim_slot, state["backend"])

    # ------------------------------------------------------------ execution
    def _execute_gen(self, *, scaler: Optional[str], inject_failures: bool,
                     initial_s: int):
        """Generator form of one run: yields Enel decision requests, resumes
        with the service's :class:`DecisionResult`, returns the run tuple."""
        job = self.job
        run = RunRecord(job.name, self.target or 0.0)
        self.backend.begin_run(self.sim_slot)
        clock = 0.0
        s_prev = s = initial_s
        scaleouts = [s]
        run_graphs: List[ComponentGraph] = []
        prev_summary: Optional[NodeAttrs] = None
        decide_s = 0.0
        decide_n = 0
        fallback_n = 0
        shed_n = 0
        for k in range(job.n_components):
            step = yield SimStepRequest(
                slot=self.sim_slot, comp_idx=k, start_scaleout=s_prev,
                end_scaleout=s, clock=clock,
                inject_failures=inject_failures)
            comp = step.component
            run.components.append(comp)
            run.failures.extend(step.failures)
            clock = step.clock_end
            nodes = _component_nodes(self.encoder, job, comp)
            preds = [p for p in (prev_summary,) if p is not None]
            if k > 0:
                h = historical_summary(
                    self.enel.hist_summaries.get(k - 1, []), float(s))
                if h is not None:
                    preds.append(h)
            run_graphs.append(_to_graph(nodes, preds, k))
            # record AFTER building this graph (history = previous runs only)
            self.enel.record_component(k, nodes, comp.runtime)
            self.ellis.observe_component(k, comp.scaleout, comp.runtime)
            prev_summary = summary_node(nodes, name=f"P{k}")
            s_prev = s
            # --- dynamic scaling decision at the component boundary
            if scaler and self.decides_after(k):
                # decision latency = this job's local work + its amortized
                # share of the service dispatch (result.service_seconds);
                # the suspended yield interval is NOT billed — under fleet
                # interleaving it contains every other job's round
                t0 = time.time()
                if scaler == "enel":
                    # batched candidate sweep: template + deltas, one
                    # service dispatch (shape-bucketed; batched across jobs
                    # when a fleet campaign drives the generator).  NOTE:
                    # under this engine node contexts are built once at the
                    # CURRENT scale-out (the z -> n_tasks context dependence
                    # below is frozen across candidates); only a/z/r and
                    # H-summary attrs vary per candidate.
                    builder = lambda ci, a, z, pr: _to_graph(
                        _future_nodes(self.encoder, job, ci, a, z), pr, ci)
                    req = self.enel.prepare_request(
                        graph_builder=builder, next_comp=k + 1,
                        n_components=job.n_components, elapsed=clock,
                        current_scaleout=s, target_runtime=self.target,
                        current_summary=prev_summary,
                        best_effort=self.best_effort)
                    decide_s += time.time() - t0
                    result = yield req
                    t0 = time.time()
                    fallback_n += int(result.fallback)
                    shed_n += int(result.shed)
                    s_new, _, _ = self.enel.apply_decision(req, result)
                    decide_s += result.service_seconds
                else:
                    s_new, _ = self.ellis.recommend(
                        next_comp=k + 1, n_components=job.n_components,
                        elapsed=clock, current_scaleout=s,
                        target_runtime=self.target)
                decide_s += time.time() - t0
                decide_n += 1
                if s_new != s:
                    run.rescales.append((k + 1, s, s_new))
                    s = s_new
                    scaleouts.append(s)
        return run, run_graphs, scaleouts, decide_s, decide_n, fallback_n, \
            shed_n

    def _execute(self, *, scaler: Optional[str], inject_failures: bool,
                 initial_s: int) -> Tuple[RunRecord, List[ComponentGraph],
                                          List[int], float, int, int, int]:
        return drive(self._execute_gen(scaler=scaler,
                                       inject_failures=inject_failures,
                                       initial_s=initial_s), self.service,
                     self.backend)

    # ------------------------------------------------------------ profiling
    def calibrate_target(self, n_runs: int = 10) -> None:
        """Profiling runs WITHOUT a model fit: sets the runtime target and
        fits Ellis, feeding the observation history.  Used standalone by
        cross-context transfer deployments (the transplanted model must not
        be scratch-retrained just to learn the new context's target)."""
        for i in range(n_runs):
            s = PROFILING_SCALEOUTS[i % len(PROFILING_SCALEOUTS)]
            run, graphs, scaleouts, _, _, _, _ = self._execute(
                scaler=None, inject_failures=False, initial_s=s)
            self.graph_history.extend(graphs)
            self.trainer.extend_history(graphs)
            self._run_idx += 1
            self.stats.append(RunStats(self._run_idx, "profiling",
                                       run.runtime, 0.0, 0.0,
                                       scaleouts=scaleouts))
        runtimes = [st.runtime for st in self.stats if st.kind == "profiling"]
        # target: slightly under the median profiled runtime, so meeting it
        # requires actively choosing good scale-outs (cf. §V-B.3)
        self.target = float(np.median(runtimes) * 0.95)
        for st in self.stats:
            st.target = self.target
            st.violation = max(0.0, st.runtime - self.target)
        self.ellis.refit()

    def profile(self, n_runs: int = 10) -> None:
        self.calibrate_target(n_runs)
        # initial model: scratch-train on the resident ring (profiling graphs
        # were appended run-by-run above — no restack)
        self.trainer.fit_resident(steps=160, from_scratch=True)

    # -------------------------------------------------------------- adaptive
    def adaptive_run(self, method: str, inject_failures: bool) -> RunStats:
        return drive(self.adaptive_run_gen(method, inject_failures),
                     self.service, self.backend)

    def adaptive_run_gen(self, method: str, inject_failures: bool):
        """Generator form of :meth:`adaptive_run` for fleet interleaving."""
        assert self.target is not None, "profile() first"
        job = self.job
        # retry/breaker deltas are service-wide (one envelope serves the
        # whole fleet); per-run rows report the delta observed over the run
        svc0 = (self.service.retries, self.service.breaker_trips)
        # fair initial allocation for both methods (paper §V-B.3): Ellis'
        # per-component models pick the cheapest compliant scale-out
        s0, predicted = self.ellis.recommend(
            next_comp=0, n_components=job.n_components, elapsed=0.0,
            current_scaleout=SCALEOUT_RANGE[0], target_runtime=self.target)
        if self.scale_cap is not None:      # multi-tenant admission headroom
            s0 = max(SCALEOUT_RANGE[0], min(s0, int(self.scale_cap)))
        run, graphs, scaleouts, decide_s, decide_n, fallback_n, shed_n = \
            yield from self._execute_gen(
                scaler=method, inject_failures=inject_failures, initial_s=s0)
        if self.chaos is not None:
            # controller-side fault injection: poisoned observations enter
            # the pipeline HERE, upstream of the cache quarantine guardrail
            graphs = self.chaos.poison_graphs(graphs, self._run_idx)
        self.graph_history.extend(graphs)
        # keep the resident ring in sync for BOTH methods so a later Enel
        # scratch retrain sees the full history window
        self.trainer.extend_history(graphs)
        self._run_idx += 1
        fit_s = 0.0
        if method == "enel":
            t0 = time.time()
            # online fast path: graphs are already device-resident, so the
            # cadence fit reuses the ring buffers (no restack per run)
            self.trainer.observe_run_resident(
                retrain_every=5, steps=160, fine_tune_steps=60)
            fit_s = time.time() - t0
            if self.chaos is not None:
                self.chaos.after_fit(self.trainer, self._run_idx)
        else:
            self.ellis.refit()
        st = RunStats(self._run_idx, method, run.runtime, self.target,
                      run.violation, predicted=predicted,
                      scaleouts=scaleouts, n_failures=len(run.failures),
                      n_rescales=len(run.rescales),
                      fit_seconds=fit_s, decide_seconds=decide_s,
                      decide_calls=decide_n,
                      fallback_decisions=fallback_n, shed_requests=shed_n,
                      retries=self.service.retries - svc0[0],
                      breaker_trips=self.service.breaker_trips - svc0[1])
        self.stats.append(st)
        if obs.enabled():
            reg = obs.registry()
            labels = {"job": job.name, "kind": method}
            reg.counter("enel_runs_total",
                        "adaptive runs completed").labels(**labels).inc()
            if run.violation > 0:
                reg.counter("enel_run_violations_total",
                            "runs exceeding target").labels(**labels).inc()
            obs.emit("run.end", driver="stepped", job=job.name,
                     run=st.run_idx, kind=method,
                     runtime=round(st.runtime, 6),
                     target=round(st.target, 6),
                     violation=round(st.violation, 6),
                     rescales=st.n_rescales, failures=st.n_failures,
                     fallbacks=st.fallback_decisions,
                     shed=st.shed_requests, retries=st.retries,
                     breaker_trips=st.breaker_trips,
                     fit_seconds=round(st.fit_seconds, 6),
                     decide_seconds=round(st.decide_seconds, 6),
                     decide_calls=st.decide_calls)
        return st


def window_stats(stats: List[RunStats], lo: int, hi: int) -> Dict[str, float]:
    """CVC/CVS aggregates over adaptive runs lo..hi (1-based, inclusive)."""
    sel = [s for s in stats if s.kind != "profiling" and lo <= s.run_idx <= hi]
    if not sel:
        return {"cvc_mean": float("nan"), "cvc_median": float("nan"),
                "cvs_mean": float("nan"), "cvs_median": float("nan")}
    cvc = np.array([s.cvc for s in sel], float)
    cvs = np.array([s.violation / 60.0 for s in sel], float)   # minutes
    return {"cvc_mean": float(cvc.mean()), "cvc_median": float(np.median(cvc)),
            "cvs_mean": float(cvs.mean()), "cvs_median": float(np.median(cvs)),
            "n": len(sel)}
