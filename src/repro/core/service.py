"""Fleet-scale decision service: shape-bucketed, cross-job batched sweeps.

One rescaling decision is a (template, deltas) candidate sweep (see
``core/scaling.py``).  This module turns decisions into a batched,
recompilation-free service:

* every request arrives padded to the fixed shape ladders of
  :func:`repro.core.graph.bucket_sweep`, so the whole fleet shares a handful
  of jit shapes instead of one per exact sweep;
* requests with the same bucket key are stacked along a new job axis J
  (per-request model parameters included — each tenant keeps its own model)
  and evaluated in ONE jit dispatch, vmapped over the existing sweep
  assembly + the sparse-edge engine (:func:`~repro.core.model.sweep_sparse_totals`);
* the compliant-scale-out pick runs on device
  (:func:`~repro.core.model.pick_candidate`); the host fetches the picked
  indices and per-candidate totals in a single transfer, and the (J, C, K)
  per-component diagnostics stay on device until someone asks.

Fault tolerance (the control plane assumes the model CAN fail):

* a per-row on-device ``isfinite`` reduce
  (:func:`~repro.core.model.sweep_totals_ok`) rides the existing pick
  transfer; rows whose valid totals are non-finite are answered by the
  bounded model-free :class:`~repro.core.fallback.FallbackPolicy` instead
  of a poisoned pick;
* dispatch is wrapped in a retry envelope — capped exponential backoff with
  seeded jitter under a per-call deadline — and a :class:`CircuitBreaker`
  that trips the whole service into fallback mode after K consecutive
  failed dispatches, then half-opens on a probe cadence;
* overload shedding (the first piece of ROADMAP item 2's admission
  control): above ``shed_capacity`` pending requests per call, excess
  requests — best-effort ones first — are rejected to the fallback policy
  without touching the dispatch path.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import OrderedDict, defaultdict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.fallback import FallbackPolicy
from repro.core.graph import ladder_bucket
from repro.core.model import (assemble_sweep_batch, pick_candidate,
                              record_trace, sweep_sparse_totals,
                              sweep_totals_ok)

JOB_LADDER = (1, 2, 4, 8, 16, 32)       # job axis J (pad by repeating a row)

# service robustness counters: attribute name -> (metric family, help).
# Registered in the unified obs registry, exposed behind the original
# attribute API via properties (see _install_counter_properties below).
_SERVICE_COUNTERS = {
    "decisions": ("enel_service_decisions_total", "requests served"),
    "dispatches": ("enel_service_dispatches_total", "jit dispatches issued"),
    "batched_away": ("enel_service_batched_away_total",
                     "dispatches saved vs one-per-request"),
    "fallback_decisions": ("enel_service_fallback_decisions_total",
                           "requests answered by the fallback policy"),
    "guardrail_trips": ("enel_service_guardrail_trips_total",
                        "non-finite sweep rows caught by the guardrail"),
    "retries": ("enel_service_retries_total",
                "dispatch attempts beyond the first"),
    "dispatch_failures": ("enel_service_dispatch_failures_total",
                          "failed dispatch attempts (incl. retried)"),
    "shed_requests": ("enel_service_shed_requests_total",
                      "requests rejected under overload"),
    "memo_lookups": ("enel_stack_memo_lookups_total",
                     "stack-memo lookups, one per memoised field a group"),
    "memo_hits": ("enel_stack_memo_hits_total",
                  "stack-memo lookups that reused a stacked field"),
}

_BREAKER_STATE_CODE = {"closed": 0, "half_open": 1, "open": 2}


class DispatchFault(RuntimeError):
    """A decision dispatch failed (retryable)."""


class DispatchTimeout(DispatchFault):
    """A decision dispatch exceeded its deadline (chaos injection raises
    this; a real deployment would raise it from an RPC timer)."""


def _job_bucket(j: int) -> int:
    return ladder_bucket(j, JOB_LADDER)


def _group_stack_impl(rows):
    """Stack each device leaf along a new leading job axis: ``rows`` is the
    group's padded list of per-row leaf lists, the result one list of
    (J, ...) arrays.  One compiled call per memo field, not one eager
    ``jnp.stack`` (J + 1 dispatches) per leaf; the compile key is the
    field's leaf shapes and the job rung J, never the group's real size."""
    record_trace("group_stack")
    return [jnp.stack(col) for col in zip(*rows)]


_group_stack = jax.jit(_group_stack_impl)


def _stack_rows(treedef, leaf_rows, tally):
    """Stack per-row leaf lists (``leaf_rows``, one per padded row, all of
    ``treedef``) into one tree of (J, ...) arrays.

    Host leaves get one np.stack + one upload each; device leaves are
    stacked together in one ``_group_stack`` call.  ``tally["bytes"]``
    counts the bytes uploaded, ``tally["launches"]`` the uploads and
    compiled calls issued."""
    out = []
    dev = []
    for i, col in enumerate(zip(*leaf_rows)):
        if isinstance(col[0], np.ndarray):
            host = np.stack(col)
            tally["bytes"] += host.nbytes
            tally["launches"] += 1
            out.append(jnp.asarray(host))
        else:
            dev.append(i)
            out.append(None)
    if dev:
        tally["launches"] += 1
        stacked = _group_stack([[row[i] for i in dev] for row in leaf_rows])
        for i, x in zip(dev, stacked):
            out[i] = x
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclasses.dataclass
class DecisionRequest:
    """One job's pending rescaling decision, already shape-bucketed.

    ``base``, ``h_onehot`` and ``deltas`` are fresh host arrays every
    decision, uploaded once per decision group; the edge lists and the
    candidate arrays are object-stable while their values are unchanged,
    so the stack memo can reuse their stacks.

    ``current_scaleout`` carries the requester's live allocation so a
    fallback answer can step FROM somewhere; ``best_effort`` marks requests
    the service may shed first under overload.  ``rid`` names the request
    in the ``enel.prep`` span and any ``decision.fallback`` event;
    ``prepared_at`` starts its ``enel_decision_latency_seconds`` sample.
    """
    params: Dict                      # this tenant's model parameters
    base: Dict                        # (K, N, ...) template arrays
    h_onehot: np.ndarray              # (K, N)
    deltas: Dict[str, np.ndarray]     # (C, K, ...)
    edge_dst: np.ndarray              # (K, E) int32
    edge_src: np.ndarray              # (K, E) int32
    edge_valid: np.ndarray            # (K, E) bool
    candidates: np.ndarray            # (C,) float32, padded ascending
    cand_valid: np.ndarray            # (C,) bool
    elapsed: float
    target: float
    levels: int
    candidate_list: List[int]         # the real candidate scale-outs
    n_components: int                 # real K (pre-padding)
    current_scaleout: int = 0         # requester's live allocation
    best_effort: bool = False         # sheddable under overload
    rid: int = -1                     # request id (prep span, fallbacks)
    prepared_at: Optional[float] = None  # perf_counter at prep's return

    @property
    def bucket_key(self):
        k, n = self.h_onehot.shape
        return (len(self.candidates), k, n, self.edge_dst.shape[1],
                self.levels)


class DecisionResult:
    """Pick + totals (fetched in one transfer); per-component preds lazy.

    ``service_seconds`` is this request's amortized share of the service
    call that produced it — the runner bills it to the run's decision
    latency instead of timing across its generator suspension (which,
    under fleet interleaving, would charge one job for the whole round).

    ``fallback``/``shed`` flag decisions the model did not make: answered
    by the heuristic policy (guardrail trip, breaker open, retries
    exhausted) or rejected under overload, respectively.  ``rid`` names
    the request it answers, so a result delivered to the wrong requester
    can be told apart.
    """

    def __init__(self, scaleout: int, predicted: float,
                 totals: Dict[int, float], per_component_dev,
                 n_candidates: int, n_components: int, rid: int = -1):
        self.rid = rid                  # the answered request's rid
        self.scaleout = scaleout
        self.predicted = predicted
        self.totals = totals
        self.service_seconds = 0.0
        self.fallback = False
        self.shed = False
        self._per_dev = per_component_dev       # (C_bucket, K_bucket) device
        self._shape = (n_candidates, n_components)
        self._per_np: Optional[np.ndarray] = None

    @property
    def per_component(self) -> np.ndarray:
        """(C, K) per-component predictions; device->host on first access.
        Fallback decisions carry no sweep: their diagnostics read as 0."""
        if self._per_np is None:
            if self._per_dev is None:
                self._per_np = np.zeros(self._shape, np.float32)
            else:
                c, k = self._shape
                self._per_np = np.asarray(self._per_dev)[:c, :k]
        return self._per_np


def sweep_eval_one(p, b, oh, d, ed, es, ev, cd, cv, el, tg, levels):
    """One job's sweep: assemble + sparse totals + on-device compliant pick.

    Returns (pick index, per-candidate totals, (C, K) per-component
    predictions, finite-totals ok flag).  Module-level so the fused campaign
    kernel (``core/campaign_kernel.py``) evaluates decisions with EXACTLY the
    ops the fleet service dispatches — one numerics contract, two drivers.
    """
    c, k = d["a_raw"].shape[:2]
    flat = assemble_sweep_batch(b, oh, d)
    tile = lambda a: jnp.broadcast_to(
        a[None], (c,) + a.shape).reshape((c * k,) + a.shape[1:])
    per = sweep_sparse_totals(p, flat, tile(ed), tile(es), tile(ev),
                              levels).reshape(c, k)
    totals = per.sum(axis=1) + el
    idx = pick_candidate(cd, cv, totals, tg)
    ok = sweep_totals_ok(totals, cv)
    return idx, totals, per, ok


def _fleet_impl(params, base, h_onehot, deltas, edge_dst, edge_src,
                edge_valid, cand, cand_valid, elapsed, target, levels):
    """vmap over the job axis: assemble + sparse sweep + on-device pick.

    Returns per job row (pick index, per-candidate totals, (C, K)
    per-component predictions, finite-totals ok flag).  The ok reduce is
    folded into this dispatch so the guardrail costs no extra dispatch and
    rides the existing pick+totals transfer.
    """
    record_trace("fleet_sweep")

    def one(p, b, oh, d, ed, es, ev, cd, cv, el, tg):
        return sweep_eval_one(p, b, oh, d, ed, es, ev, cd, cv, el, tg,
                              levels)

    return jax.vmap(one)(params, base, h_onehot, deltas, edge_dst, edge_src,
                         edge_valid, cand, cand_valid, elapsed, target)


_fleet_jit = jax.jit(_fleet_impl, static_argnums=(11,))


def apply_capacity(request: DecisionRequest, max_scaleout: int
                   ) -> DecisionRequest:
    """Capacity-capped pick: mask candidates above ``max_scaleout`` (a
    multi-tenant executor-pool constraint) so the on-device compliant pick
    can only choose a scale-out the shrunken pool can actually grant.

    Returns ``request`` unchanged when the cap does not bind.  If the cap
    excludes every candidate, the smallest valid candidate stays eligible
    (a job never picks below the range floor; the pool accounting admits
    jobs only with at least that much headroom).
    """
    over = request.cand_valid & (request.candidates > max_scaleout)
    if not over.any():
        return request
    cv = request.cand_valid & ~over
    if not cv.any():
        lo = request.candidates[request.cand_valid].min()
        cv = request.cand_valid & (request.candidates <= lo)
    return dataclasses.replace(request, cand_valid=cv)


class CircuitBreaker:
    """Dispatch-path circuit breaker: CLOSED -> OPEN after ``threshold``
    consecutive failed dispatch calls; OPEN serves every request from the
    fallback policy; after ``probe_after`` blocked calls the breaker
    HALF-OPENs and lets one probe call through — success closes it,
    failure re-opens (counting another trip)."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, threshold: int = 3, probe_after: int = 4,
                 name: str = "breaker"):
        self.threshold = int(threshold)
        self.probe_after = int(probe_after)
        self.name = name
        self.state = self.CLOSED
        self.consecutive_failures = 0
        self._blocked_calls = 0
        self.last_transition_seq = -1   # flight-recorder seq of last flip
        reg = obs.registry()
        self._trips = reg.counter(
            "enel_breaker_trips_total",
            "breaker transitions into OPEN").labels(service=name)
        self._state_gauge = reg.gauge(
            "enel_breaker_state",
            "1 for the current breaker state, 0 otherwise")
        self._sync_state_gauge()

    @property
    def trips(self) -> int:
        return int(self._trips.value)

    @trips.setter
    def trips(self, v: int) -> None:
        self._trips.set(v)

    def _sync_state_gauge(self) -> None:
        for s in (self.CLOSED, self.OPEN, self.HALF_OPEN):
            self._state_gauge.labels(service=self.name, state=s).set(
                1.0 if s == self.state else 0.0)

    def _transition(self, new_state: str, reason: str) -> None:
        if new_state == self.state:
            return
        self.last_transition_seq = obs.emit(
            "breaker.transition", service=self.name,
            src=self.state, dst=new_state, reason=reason,
            trips=self.trips, failures=self.consecutive_failures)
        self.state = new_state
        self._sync_state_gauge()

    def allow(self) -> bool:
        """One call per service decide(): may this call dispatch?"""
        if self.state == self.OPEN:
            self._blocked_calls += 1
            if self._blocked_calls >= self.probe_after:
                self._transition(self.HALF_OPEN, "probe_window")
            return False
        return True                     # closed, or half-open (the probe)

    def record(self, success: bool) -> None:
        if success:
            self.consecutive_failures = 0
            self._transition(self.CLOSED, "dispatch_ok")
            return
        self.consecutive_failures += 1
        if self.state == self.HALF_OPEN or \
                self.consecutive_failures >= self.threshold:
            reason = ("probe_failed" if self.state == self.HALF_OPEN
                      else "failure_threshold")
            self._blocked_calls = 0
            self.trips += 1
            self._transition(self.OPEN, reason)

    def snapshot(self) -> Dict:
        return {"state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "trips": self.trips,
                "blocked_calls": self._blocked_calls,
                "last_transition_seq": self.last_transition_seq}

    def restore(self, st: Dict) -> None:
        self.state = st["state"]
        self.consecutive_failures = st["consecutive_failures"]
        self.trips = st["trips"]
        self._blocked_calls = st["blocked_calls"]
        self.last_transition_seq = st.get("last_transition_seq", -1)
        self._sync_state_gauge()        # registry labels track restored state


class DecisionService:
    """Collects concurrent decision requests and dispatches them batched.

    ``decide`` groups requests by bucket key (at most ``JOB_LADDER[-1]`` to a
    group; a larger bucket splits), pads each group to a JOB_LADDER
    rung along the job axis, evaluates every group in one jit dispatch and
    fetches each group's picks + totals in a single host transfer.

    Dispatch is double-buffered by default: every group is stacked and
    dispatched first (jax dispatch is async), and the host transfers are
    fetched in a second pass — so host request-stacking of the next bucket
    overlaps device compute of the current one.  ``double_buffer=False``
    restores the synchronous stack->dispatch->fetch loop (decision parity
    between the two modes is asserted in tests).

    Failure envelope: each group dispatch retries up to ``max_retries``
    times under capped exponential backoff with seeded jitter, bounded by
    ``deadline_s`` per decide() call; consecutive decide() calls whose
    dispatches fail trip the :class:`CircuitBreaker` into fallback-for-all
    mode.  Rows whose predictions come back non-finite are answered by the
    :class:`~repro.core.fallback.FallbackPolicy` WITHOUT tripping the
    breaker (a poisoned tenant model is a per-row condition, not a service
    outage; its fallback rate is visible in the counters).  ``fault_injector``
    is the chaos hook: a callable invoked once per dispatch attempt that
    may raise :class:`DispatchFault`.
    """

    _ids = itertools.count()        # default obs label allocator

    def __init__(self, double_buffer: bool = True, *,
                 fallback: Optional[FallbackPolicy] = None,
                 max_retries: int = 2, backoff_base_s: float = 0.02,
                 backoff_cap_s: float = 0.25,
                 deadline_s: Optional[float] = None,
                 breaker_threshold: int = 3, breaker_probe_after: int = 4,
                 shed_capacity: Optional[int] = None, seed: int = 0,
                 obs_name: Optional[str] = None):
        self.double_buffer = double_buffer
        self.fallback = fallback or FallbackPolicy()
        self.max_retries = int(max_retries)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.deadline_s = deadline_s
        # obs_name keys this instance's registry series; pass a stable name
        # to make a restored-from-checkpoint service label-identical.
        self.obs_name = obs_name or f"svc{next(self._ids)}"
        reg = obs.registry()
        self._obs_counters = {
            attr: reg.counter(family, help).labels(service=self.obs_name)
            for attr, (family, help) in _SERVICE_COUNTERS.items()}
        self.breaker = CircuitBreaker(breaker_threshold, breaker_probe_after,
                                      name=self.obs_name)
        self.shed_capacity = shed_capacity
        self.fault_injector = None      # chaos hook (see repro.sim.chaos)
        self._rng = np.random.RandomState(seed ^ 0xbac0ff)  # backoff jitter
        # identity-memoized stacks: params / edge lists / candidate arrays
        # are object-stable across decision rounds (the scalers' caches
        # re-serve the same ndarrays while values are unchanged), so
        # their (J, ...) stacks are reused instead of re-stacked per round.
        # LRU-bounded so a long campaign over many bucket/fleet shapes
        # cannot pin stacked device arrays without limit.
        self._stack_memo: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._stack_memo_slots = 64

    @property
    def breaker_trips(self) -> int:
        return self.breaker.trips

    def _stack_tree(self, cache_key: tuple, rows, get, tally):
        trees = [get(r) for r in rows]
        all_leaves = [jax.tree_util.tree_leaves(t) for t in trees]
        ids = tuple(id(l) for row in all_leaves for l in row)
        hit = self._stack_memo.get(cache_key)
        self.memo_lookups += 1
        if hit is not None and hit[0] == ids:
            self._stack_memo.move_to_end(cache_key)
            tally["hits"] += 1
            self.memo_hits += 1
            return hit[2]
        tally["misses"] += 1
        stacked = _stack_rows(jax.tree_util.tree_structure(trees[0]),
                              all_leaves, tally)
        # keep the leaf refs alive so the memo's ids cannot be recycled
        self._stack_memo[cache_key] = (ids, all_leaves, stacked)
        while len(self._stack_memo) > self._stack_memo_slots:
            self._stack_memo.popitem(last=False)
        return stacked

    def _dispatch_group(self, key: tuple, group: List[DecisionRequest]):
        """Stack one bucket group and issue its (async) jit dispatch."""
        if self.fault_injector is not None:
            self.fault_injector()       # chaos: may raise DispatchFault
        j_b = _job_bucket(len(group))
        rows = group + [group[-1]] * (j_b - len(group))
        tally = {"hits": 0, "misses": 0, "bytes": 0, "launches": 0}
        with obs.span("enel.decide.stack") as sp:
            memo = lambda field: self._stack_tree(
                (key, j_b, field), rows, lambda r: getattr(r, field), tally)
            stacked = [memo("params"), memo("base"), memo("h_onehot")]
            deltas = _stack_rows(
                jax.tree_util.tree_structure(rows[0].deltas),
                [jax.tree_util.tree_leaves(r.deltas) for r in rows], tally)
            stacked += [deltas] + [memo(field) for field in (
                "edge_dst", "edge_src", "edge_valid", "candidates",
                "cand_valid")]
            for field in ("elapsed", "target"):
                host = np.asarray([getattr(r, field) for r in rows],
                                  np.float32)
                tally["bytes"] += host.nbytes
                tally["launches"] += 1
                stacked.append(jnp.asarray(host))
            sp.set(**tally)
        with obs.span("enel.decide.launch"):
            out = _fleet_jit(*stacked, group[0].levels)
        self.dispatches += 1
        self.batched_away += len(group) - 1
        return out

    # ------------------------------------------------------ failure envelope
    def _fallback_result(self, req: DecisionRequest,
                         totals_row: Optional[np.ndarray] = None,
                         shed: bool = False, cause: str = "guardrail",
                         cause_seq: int = -1) -> DecisionResult:
        """Answer one request from the bounded heuristic policy.

        ``cause`` names why the model did not answer (shed, breaker_open,
        retries_exhausted, guardrail); ``cause_seq`` links the span to the
        flight-recorder event that forced the fallback."""
        totals = None
        if totals_row is not None:
            totals = {s: float(totals_row[ci])
                      for ci, s in enumerate(req.candidate_list)}
        s, pred = self.fallback.decide(
            req.candidate_list, totals, req.current_scaleout,
            req.elapsed, req.target)
        res = DecisionResult(
            scaleout=int(s), predicted=pred,
            totals=self.fallback._finite_totals(req.candidate_list, totals),
            per_component_dev=None,
            n_candidates=len(req.candidate_list),
            n_components=req.n_components, rid=req.rid)
        res.fallback = True
        res.shed = shed
        self.fallback_decisions += 1
        if shed:
            self.shed_requests += 1
        obs.emit("decision.fallback", service=self.obs_name, cause=cause,
                 cause_seq=cause_seq, rid=req.rid, shed=shed,
                 scaleout=int(s),
                 from_scaleout=int(req.current_scaleout))
        return res

    def _dispatch_with_retry(self, key: tuple,
                             group: List[DecisionRequest],
                             t_start: float, deadline: Optional[float]):
        """Dispatch one group under the retry/backoff/deadline envelope;
        returns (jit output or None when the envelope is exhausted,
        retries used, flight-recorder seq of the last fault span)."""
        attempt = 0
        fault_seq = -1
        while True:
            try:
                return self._dispatch_group(key, group), attempt, fault_seq
            except DispatchFault as e:
                self.dispatch_failures += 1
                fault_seq = obs.emit(
                    "dispatch.fault", service=self.obs_name,
                    bucket=str(key), group=len(group), attempt=attempt,
                    fault=type(e).__name__)
                sleep = min(self.backoff_cap_s,
                            self.backoff_base_s * (2 ** attempt))
                sleep *= 0.5 + self._rng.rand()     # seeded jitter
                if attempt >= self.max_retries or (
                        deadline is not None and
                        time.perf_counter() - t_start + sleep > deadline):
                    return None, attempt, fault_seq
                time.sleep(sleep)
                self.retries += 1
                attempt += 1

    def _shed(self, requests: Sequence[DecisionRequest],
              results: List[Optional[DecisionResult]]) -> List[int]:
        """Admission control: above ``shed_capacity`` pending requests,
        reject the excess — best-effort requests first, newest first —
        straight to the fallback policy.  Returns the surviving indices."""
        live = list(range(len(requests)))
        if self.shed_capacity is None or len(live) <= self.shed_capacity:
            return live
        excess = len(live) - int(self.shed_capacity)
        order = [i for i in reversed(live) if requests[i].best_effort] + \
                [i for i in reversed(live) if not requests[i].best_effort]
        for i in order[:excess]:
            results[i] = self._fallback_result(requests[i], shed=True)
        return [i for i in live if results[i] is None]

    def decide(self, requests: Sequence[DecisionRequest]
               ) -> List[DecisionResult]:
        with obs.span("enel.decide", _ring=True,
                      requests=len(requests)) as sp:
            return self._decide(requests, sp)

    def _decide(self, requests: Sequence[DecisionRequest], sp
                ) -> List[DecisionResult]:
        t_start = time.perf_counter()
        results: List[Optional[DecisionResult]] = [None] * len(requests)
        live = self._shed(requests, results)
        if live and not self.breaker.allow():       # open: fallback for all
            for i in live:
                results[i] = self._fallback_result(
                    requests[i], cause="breaker_open",
                    cause_seq=self.breaker.last_transition_seq)
            live = []
        by_key: Dict[tuple, List[int]] = defaultdict(list)
        for i in live:
            by_key[requests[i].bucket_key].append(i)
        # a group fills at most the top rung: a larger bucket splits, so a
        # burst never pads to a shape past the ladder (a new compile)
        cap = JOB_LADDER[-1]
        groups = [(key, idxs[lo:lo + cap]) for key, idxs in by_key.items()
                  for lo in range(0, len(idxs), cap)]
        sp.set(groups=len(groups))
        deadline = self.deadline_s
        staged = []
        dispatch_ok = True
        for key, idxs in groups:
            out, retried, fault_seq = self._dispatch_with_retry(
                key, [requests[i] for i in idxs], t_start, deadline)
            if out is None:                         # envelope exhausted
                dispatch_ok = False
                for i in idxs:
                    results[i] = self._fallback_result(
                        requests[i], cause="retries_exhausted",
                        cause_seq=fault_seq)
                continue
            if not self.double_buffer:
                # synchronous mode: fetch before stacking the next bucket
                with obs.span("enel.decide.fetch"):
                    out = (jax.device_get((out[0], out[1], out[3])), out[2])
            staged.append((idxs, key, retried, out))
        for idxs, key, retried, out in staged:
            if self.double_buffer:
                picked, totals, per, ok = out
                # ONE host transfer per group: picks + totals + ok flags
                with obs.span("enel.decide.fetch"):
                    picked_np, totals_np, ok_np = jax.device_get(
                        (picked, totals, ok))
            else:
                (picked_np, totals_np, ok_np), per = out
            obs.emit("decision.dispatch", service=self.obs_name,
                     bucket=str(key), group=len(idxs), retries=retried)
            for gi, ri in enumerate(idxs):
                req = requests[ri]
                if not bool(ok_np[gi]):     # guardrail: poisoned sweep row
                    self.guardrail_trips += 1
                    trip_seq = obs.emit(
                        "guardrail.trip", service=self.obs_name,
                        bucket=str(key), row=gi)
                    results[ri] = self._fallback_result(
                        req, totals_row=totals_np[gi], cause="guardrail",
                        cause_seq=trip_seq)
                    continue
                sl = int(picked_np[gi])
                tot = {s: float(totals_np[gi, ci])
                       for ci, s in enumerate(req.candidate_list)}
                results[ri] = DecisionResult(
                    scaleout=req.candidate_list[sl],
                    predicted=float(totals_np[gi, sl]), totals=tot,
                    per_component_dev=per[gi],
                    n_candidates=len(req.candidate_list),
                    n_components=req.n_components, rid=req.rid)
        if groups:
            self.breaker.record(dispatch_ok)
        self.decisions += len(requests)
        if requests:
            share = (time.perf_counter() - t_start) / len(requests)
            for r in results:
                r.service_seconds = share
            if obs.enabled():
                hist = obs.registry().histogram(
                    "enel_decision_latency_seconds",
                    "from a request's prepare_request return to the end "
                    "of the decide() that answered it"
                ).labels(service=self.obs_name)
                now = time.perf_counter()
                for r in requests:
                    if r.prepared_at is not None:
                        hist.observe(now - r.prepared_at)
        return results

    # ----------------------------------------------------------- telemetry
    def stats(self) -> Dict:
        """All robustness counters + breaker state as one plain dict (the
        registry-backed successor of reading the attributes one by one)."""
        out = {attr: getattr(self, attr) for attr in _SERVICE_COUNTERS}
        out["breaker_trips"] = self.breaker_trips
        out["breaker_state"] = self.breaker.state
        return out

    # --------------------------------------------------- checkpoint support
    def snapshot_state(self) -> Dict:
        """Counters + breaker + jitter-RNG state for campaign checkpoints
        (the stack memo is a pure performance cache and is rebuilt)."""
        st = {"decisions": self.decisions, "dispatches": self.dispatches,
              "batched_away": self.batched_away,
              "fallback_decisions": self.fallback_decisions,
              "guardrail_trips": self.guardrail_trips,
              "retries": self.retries,
              "dispatch_failures": self.dispatch_failures,
              "shed_requests": self.shed_requests,
              "breaker": self.breaker.snapshot(),
              "rng": self._rng.get_state()}
        if self.fault_injector is not None and \
                hasattr(self.fault_injector, "snapshot"):
            st["fault_injector"] = self.fault_injector.snapshot()
        return st

    def restore_state(self, st: Dict) -> None:
        self.decisions = st["decisions"]
        self.dispatches = st["dispatches"]
        self.batched_away = st["batched_away"]
        self.fallback_decisions = st["fallback_decisions"]
        self.guardrail_trips = st["guardrail_trips"]
        self.retries = st["retries"]
        self.dispatch_failures = st["dispatch_failures"]
        self.shed_requests = st["shed_requests"]
        self.breaker.restore(st["breaker"])
        self._rng.set_state(st["rng"])
        if "fault_injector" in st and self.fault_injector is not None and \
                hasattr(self.fault_injector, "restore"):
            self.fault_injector.restore(st["fault_injector"])


def _install_counter_properties():
    """Expose the registry-backed service counters behind the original
    attribute API (``svc.retries``, ``svc.decisions += 1`` ...): reads and
    read-modify-writes hit the labeled CounterSeries in the obs registry."""
    def make(attr):
        def fget(self):
            return int(self._obs_counters[attr].value)

        def fset(self, value):
            self._obs_counters[attr].set(value)
        return property(fget, fset)

    for attr in _SERVICE_COUNTERS:
        setattr(DecisionService, attr, make(attr))


_install_counter_properties()
