"""Decision guardrails + service fault envelope.

Contracts under test:

* the model-free :class:`FallbackPolicy` ALWAYS answers with one of the
  real candidates, for arbitrary finite/non-finite prediction vectors,
  elapsed times and targets (property-tested when hypothesis is
  available, seeded-sweep otherwise);
* NaN-poisoned model parameters trip the on-device guardrail and the
  service answers from the fallback policy — never a non-finite pick —
  for fleet requests and for the single-job ``recommend`` alike;
* exhausted dispatch retries degrade a whole group to fallback decisions
  and feed the circuit breaker through its CLOSED -> OPEN -> HALF_OPEN ->
  CLOSED lifecycle;
* overload shedding rejects best-effort requests first and the shed
  answers are bounded too.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fallback import FallbackPolicy
from repro.core.service import (CircuitBreaker, DecisionService,
                                DispatchFault, DispatchTimeout)
from repro.dataflow import JobExperiment
from repro.dataflow.runner import _component_nodes, _future_nodes, _to_graph
from repro.core.graph import summary_node

CANDS = [4, 8, 12, 16, 24, 36]
WEIRD = [float("nan"), float("inf"), float("-inf"), -1e30, 0.0, 1e30, 7.5]


# ------------------------------------------------------- policy bounds
def test_fallback_clamp_always_a_candidate():
    pol = FallbackPolicy()
    rng = np.random.RandomState(0)
    for _ in range(300):
        cur = float(rng.choice(WEIRD + [4, 9, 36, 100, -3]))
        elapsed = float(rng.choice(WEIRD))
        target = float(rng.choice(WEIRD))
        s = pol.clamp(CANDS, cur, elapsed, target)
        assert s in CANDS


def test_fallback_decide_always_a_candidate_with_garbage_totals():
    pol = FallbackPolicy()
    rng = np.random.RandomState(1)
    for _ in range(300):
        totals = [float(rng.choice(WEIRD)) for _ in CANDS]
        s, pred = pol.decide(CANDS, totals, current=int(rng.choice(CANDS)),
                             elapsed=float(rng.choice(WEIRD)),
                             target=float(rng.choice(WEIRD)))
        assert s in CANDS
        finite = {c: t for c, t in zip(CANDS, totals) if math.isfinite(t)}
        if finite:
            assert math.isfinite(pred)      # salvage path used a real pred
        else:
            assert math.isnan(pred)         # blind clamp: no prediction


def test_fallback_salvage_prefers_smallest_compliant():
    pol = FallbackPolicy()
    totals = {4: float("nan"), 8: 50.0, 12: 30.0, 16: 20.0, 24: 25.0}
    s, pred = pol.decide([4, 8, 12, 16, 24], totals, current=8,
                         elapsed=10.0, target=31.0)
    assert (s, pred) == (12, 30.0)          # smallest finite compliant
    # nothing compliant -> least violating finite
    s, pred = pol.decide([4, 8, 12, 16, 24], totals, current=8,
                         elapsed=10.0, target=5.0)
    assert (s, pred) == (16, 20.0)


def test_fallback_urgency_steps_are_bounded():
    pol = FallbackPolicy(max_step=4)
    assert pol.clamp(CANDS, 8, elapsed=1.0, target=100.0) == 8    # no rush
    assert pol.clamp(CANDS, 8, elapsed=60.0, target=100.0) == 12  # half step
    assert pol.clamp(CANDS, 8, elapsed=95.0, target=100.0) == 12  # full step
    assert pol.clamp(CANDS, 36, elapsed=95.0, target=100.0) == 36  # capped


def test_fallback_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    anyfloat = st.floats(allow_nan=True, allow_infinity=True, width=32)

    @hyp.given(
        cands=st.lists(st.integers(min_value=1, max_value=64), min_size=1,
                       max_size=8, unique=True),
        totals=st.lists(anyfloat, min_size=8, max_size=8),
        current=anyfloat, elapsed=anyfloat, target=anyfloat)
    @hyp.settings(max_examples=200, deadline=None)
    def check(cands, totals, current, elapsed, target):
        pol = FallbackPolicy()
        s, _ = pol.decide(cands, totals[:len(cands)], current=current,
                          elapsed=elapsed, target=target)
        assert s in set(int(c) for c in cands)
        assert min(cands) <= s <= max(cands)

    check()


# --------------------------------------------------- service-level fixtures
@pytest.fixture(scope="module")
def profiled_exp():
    exp = JobExperiment("kmeans", seed=31)
    exp.profile(2)
    return exp


def _decision_kwargs(exp, seed=500):
    job = exp.job
    builder = lambda ci, a, z, pr: _to_graph(
        _future_nodes(exp.encoder, job, ci, a, z), pr, ci)
    comp = exp.sim.run_component(job, 0, clock=0.0, start_scaleout=8,
                                 end_scaleout=8, inject_failures=False,
                                 failures_log=[])
    summ = summary_node(_component_nodes(exp.encoder, job, comp), name="P0")
    exp.encoder.rng = np.random.RandomState(seed)
    return dict(graph_builder=builder, next_comp=1,
                n_components=job.n_components, elapsed=comp.runtime,
                current_scaleout=8, target_runtime=exp.target,
                current_summary=summ)


def _request(exp, seed=500):
    return exp.enel.prepare_request(**_decision_kwargs(exp, seed))


# ------------------------------------------------ guardrail: poisoned model
def test_guardrail_nan_params_falls_back(profiled_exp):
    import dataclasses
    exp = profiled_exp
    req = _request(exp)
    bad = jax.tree_util.tree_map(lambda p: jnp.full_like(p, jnp.nan),
                                 req.params)
    svc = DecisionService()
    req_bad = dataclasses.replace(req, params=bad)
    res = svc.decide([req_bad])[0]
    assert res.fallback
    assert res.scaleout in req.candidate_list
    assert svc.guardrail_trips == 1 and svc.fallback_decisions == 1
    # the same request with healthy params is a model decision again
    res2 = svc.decide([req])[0]
    assert not res2.fallback and math.isfinite(res2.predicted)


@pytest.mark.parametrize("poison", [float("nan"), float("inf")])
def test_recommend_nonfinite_model_takes_bounded_fallback(profiled_exp,
                                                          poison):
    """A poisoned single-job model: recommend() answers from the service's
    bounded fallback policy, counted in that service's fallback
    decisions, and never picks outside the candidates."""
    exp = profiled_exp
    trainer = exp.trainer.copy(exp.seed)
    trainer.params = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, poison), trainer.params)
    scaler = exp.enel.copy(trainer)
    kw = _decision_kwargs(exp)
    s, pred, totals = scaler.recommend(**kw)
    assert s in scaler.candidate_scaleouts(kw["current_scaleout"])
    assert all(math.isfinite(t) for t in totals.values())
    assert scaler._service.guardrail_trips == 1
    assert scaler._service.fallback_decisions == 1


# ------------------------------- retry exhaustion + circuit breaker lifecycle
def test_retries_exhausted_fallback_and_breaker(profiled_exp):
    class AlwaysDown:
        def __call__(self):
            raise DispatchTimeout("injected")

    svc = DecisionService(max_retries=1, backoff_base_s=0.0,
                          breaker_threshold=2, breaker_probe_after=2)
    svc.fault_injector = AlwaysDown()
    req = _request(profiled_exp)
    r1 = svc.decide([req])[0]
    assert r1.fallback and r1.scaleout in req.candidate_list
    assert svc.retries == 1 and svc.dispatch_failures == 2
    assert svc.breaker.state == CircuitBreaker.CLOSED
    r2 = svc.decide([req])[0]               # second failure trips (thr 2)
    assert r2.fallback
    assert svc.breaker.state == CircuitBreaker.OPEN
    assert svc.breaker_trips == 1
    # open breaker: no dispatch attempts at all, straight to fallback
    before = svc.dispatch_failures
    r3 = svc.decide([req])[0]
    assert r3.fallback and svc.dispatch_failures == before
    # after probe_after blocked calls the breaker half-opens; a healthy
    # probe dispatch closes it again
    r4 = svc.decide([req])[0]
    assert r4.fallback
    assert svc.breaker.state == CircuitBreaker.HALF_OPEN
    svc.fault_injector = None
    r5 = svc.decide([req])[0]
    assert not r5.fallback
    assert svc.breaker.state == CircuitBreaker.CLOSED


def test_circuit_breaker_unit_lifecycle():
    br = CircuitBreaker(threshold=3, probe_after=2)
    for _ in range(2):
        assert br.allow()
        br.record(False)
    assert br.state == CircuitBreaker.CLOSED
    assert br.allow()
    br.record(False)                        # third consecutive failure
    assert br.state == CircuitBreaker.OPEN and br.trips == 1
    assert not br.allow()                   # blocked
    assert not br.allow()                   # blocked, then half-open
    assert br.state == CircuitBreaker.HALF_OPEN
    assert br.allow()                       # the probe
    br.record(False)                        # probe failed: re-open
    assert br.state == CircuitBreaker.OPEN and br.trips == 2
    br._blocked_calls = br.probe_after
    assert not br.allow()
    assert br.allow()
    br.record(True)                         # probe succeeded
    assert br.state == CircuitBreaker.CLOSED
    # snapshot/restore round-trips the full lifecycle state
    st = br.snapshot()
    br2 = CircuitBreaker()
    br2.restore(st)
    assert br2.snapshot() == st


# ----------------------------------------------------------- load shedding
def test_overload_sheds_best_effort_first(profiled_exp):
    svc = DecisionService(shed_capacity=1)
    req_a = _request(profiled_exp, seed=600)
    req_b = _request(profiled_exp, seed=601)
    req_b.best_effort = True
    res_a, res_b = svc.decide([req_a, req_b])
    assert not res_a.shed and res_b.shed
    assert res_b.fallback
    assert res_b.scaleout in req_b.candidate_list
    assert svc.shed_requests == 1


def test_dispatch_fault_hierarchy():
    assert issubclass(DispatchTimeout, DispatchFault)
    assert issubclass(DispatchFault, RuntimeError)
