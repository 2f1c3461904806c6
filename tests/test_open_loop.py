"""Open-loop fleet serving (``FleetCampaign.serve_arrivals``).

The contracts under test, on a fake clock (no real sleeping):

* per-tenant parity: under an arrival schedule that visits every decision
  point, each tenant's runs equal its runs under the lockstep
  ``adaptive_campaign`` from the same state, bit for bit;
* every decision equals the per-graph reference
  ``EnelScaler.recommend_pergraph`` for the same request;
* accounting: every arrival is answered exactly once, by the result of its
  own request, also when a tenant is due again before it is ready;
* tenants copied from a profiled leader share no device buffer.
"""
import jax
import numpy as np
import pytest

from repro.core.graph import NodeAttrs
from repro.core.service import DecisionRequest, DecisionService
from repro.dataflow import FleetCampaign, JobExperiment
from repro.dataflow.runner import _to_graph

JOBS = ("lr", "mpc", "kmeans", "gbt")
DECISIONS_PER_RUN = 11         # every job class decides 11 times a run

# Totals of the service's batched sweep against the per-graph reference,
# as a share of the job's target: both evaluate the same graphs in float32,
# the sweep with its components padded and its sums in another order, so
# they differ by float32 rounding (about 1e-7 of a total); 1e-4 leaves room
# for that and fails any real difference in the graphs or the model.
PERGRAPH_TOL = 1e-4


class FakeClock:
    """Time moves only when the loop sleeps."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds


def _fleet(n=8):
    exps = [JobExperiment(JOBS[i % 4], seed=300 + i) for i in range(n)]
    camp = FleetCampaign(exps, DecisionService(), engine="batched")
    camp.profile(3)
    return camp


@pytest.fixture(scope="module")
def fleet():
    camp = _fleet()
    return camp, [e.snapshot_state() for e in camp.experiments]


def _restore(fleet):
    """The fleet back at its profiled state, in a new campaign (fresh
    open-loop state) over the same tenants and service."""
    camp, snaps = fleet
    for e, s in zip(camp.experiments, snaps):
        e.restore_state(s)
    return FleetCampaign(camp.experiments, camp.service)


def _every_point(n_tenants, runs, gap=0.25):
    """Each tenant due at every decision point of ``runs`` runs, tenants
    interleaved, ``gap`` seconds apart."""
    return [(gap * (r * DECISIONS_PER_RUN + d) + 0.01 * i, i)
            for r in range(runs) for d in range(DECISIONS_PER_RUN)
            for i in range(n_tenants)]


def _row(st):
    return (st.run_idx, st.runtime, st.target, st.violation, st.predicted,
            tuple(st.scaleouts), st.n_failures, st.n_rescales,
            st.decide_calls, st.fallback_decisions, st.shed_requests)


def test_open_loop_matches_lockstep_per_tenant(fleet):
    camp = _restore(fleet)
    lock, _ = camp.adaptive_campaign(2)
    params_lock = [jax.tree_util.tree_map(np.asarray, e.trainer.params)
                   for e in camp.experiments]
    camp = _restore(fleet)
    arrivals = camp.serve_arrivals(_every_point(8, 2), clock=FakeClock())
    assert all(a.applied >= a.taken >= a.due for a in arrivals)
    for i, e in enumerate(camp.experiments):
        got = camp.open_stats[i]
        assert [_row(st) for st in got] == \
            [_row(lock[r][i]) for r in range(2)], f"tenant {i}"
        for x, y in zip(jax.tree_util.tree_leaves(params_lock[i]),
                        jax.tree_util.tree_leaves(e.trainer.params)):
            np.testing.assert_array_equal(x, np.asarray(y))


def _frozen_builder(exp, s_now):
    """The runner's future-graph builder with contexts taken at the current
    scale-out and no version dropout: the candidate-invariant contexts the
    sweep assumes, so the per-graph path builds the same graphs."""
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


def test_open_loop_decisions_match_pergraph(fleet):
    camp = _restore(fleet)
    expected = {}                # rid -> (pick, totals, target)
    for exp in camp.experiments:
        prepare = exp.enel.prepare_request

        def prep(exp=exp, prepare=prepare, **kw):
            kw["graph_builder"] = _frozen_builder(exp,
                                                  kw["current_scaleout"])
            req = prepare(**kw)
            kw.pop("best_effort")
            s, _, totals = exp.enel.recommend_pergraph(**kw)
            expected[req.rid] = (s, totals, exp.target)
            return req
        exp.enel.prepare_request = prep
    results = []
    decide = camp.service.decide

    def record(reqs):
        out = decide(reqs)
        results.extend(out)
        return out
    camp.service.decide = record
    try:
        camp.serve_arrivals(_every_point(8, 1), clock=FakeClock())
    finally:
        del camp.service.decide
        for exp in camp.experiments:
            del exp.enel.prepare_request
    assert len(results) == 8 * DECISIONS_PER_RUN
    agree = 0
    for res in results:
        s, totals, target = expected[res.rid]
        dev = max(abs(res.totals[c] - totals[c]) for c in totals) / target
        assert not res.fallback and dev <= PERGRAPH_TOL
        assert _pick_consistent(res.scaleout, totals, target,
                                PERGRAPH_TOL * target)
        agree += res.scaleout == s
    assert agree >= len(results) - 2          # the rest are near ties


def _pick_consistent(s, totals, target, tol):
    """Whether the pick rule could return ``s`` for some totals within
    ``tol`` of ``totals``: where no candidate meets the target, nearly
    equal least totals are a tie that rounding may break either way."""
    lo = {c: t - tol for c, t in totals.items()}
    hi = {c: t + tol for c, t in totals.items()}
    if lo[s] <= target and all(hi[c] > target for c in totals if c < s):
        return True
    return all(hi[c] > target for c in totals) and \
        lo[s] <= min(hi.values())


def test_open_loop_answers_each_arrival_once(fleet):
    """Bursts, and tenants due again before they are ready: each arrival
    is applied once, by its own request's result, and late ones wait."""
    camp = _restore(fleet)
    seen = {i: [] for i in range(8)}
    for i, exp in enumerate(camp.experiments):
        make = exp.adaptive_run_gen

        def gen(*args, i=i, make=make):
            inner = make(*args)
            req = next(inner)
            while True:
                res = yield req
                if isinstance(req, DecisionRequest):
                    seen[i].append((req.rid, res.rid))
                try:
                    req = inner.send(res)
                except StopIteration as stop:
                    return stop.value
        exp.adaptive_run_gen = gen
    rng = np.random.default_rng(5)
    sched = [(float(t), int(i)) for t, i in zip(
        np.sort(rng.uniform(0, 2.0, 60)), rng.integers(0, 8, 60))]
    sched += [(2.5, i) for i in range(8)] * 3       # a burst, 3 per tenant
    try:
        arrivals = camp.serve_arrivals(sched, clock=FakeClock())
    finally:
        for exp in camp.experiments:
            del exp.adaptive_run_gen
    assert len(arrivals) == len(sched)
    assert all(np.isfinite(a.applied) and a.applied >= a.taken >= a.due
               and 1 <= a.batch <= 8 for a in arrivals)
    for i in range(8):
        assert len(seen[i]) == sum(a.tenant == i for a in arrivals)
        assert all(rid == got for rid, got in seen[i])
        assert len({rid for rid, _ in seen[i]}) == len(seen[i])
    assert not camp._stepping and len(camp._held) == 8


def test_adopted_profile_aliases_no_buffer(fleet):
    camp = _restore(fleet)
    lead = camp.experiments[0]
    copies = [JobExperiment(lead.job_key, seed=900 + k,
                            share_models_from=lead) for k in range(2)]
    for c in copies:
        c.adopt_profile(lead)
    trees = [(e.trainer.params, e.trainer.opt, e.trainer.cache.buffers)
             for e in [lead] + copies]
    ptrs = [x.unsafe_buffer_pointer() for t in trees
            for x in jax.tree_util.tree_leaves(t)]
    assert len(set(ptrs)) == len(ptrs)
    for t in trees[1:]:
        for x, y in zip(jax.tree_util.tree_leaves(trees[0]),
                        jax.tree_util.tree_leaves(t)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    c = copies[0]
    assert c.encoder is lead.encoder and c.enel is not lead.enel
    assert c.target == lead.target and c.trainer.seed == c.seed
    assert c.trainer.obs_name != lead.trainer.obs_name
    assert c.enel.hist_summaries == lead.enel.hist_summaries
    assert c.trainer.cache.count == lead.trainer.cache.count
