"""Fleet decision service: shape bucketing, sparse engine, batched dispatch.

The contracts under test:

* padding a sweep to the bucket ladders changes nothing — the padded
  sparse sweep equals the per-graph prediction of every materialised
  candidate on the real JOBS builders, and padded components read exactly 0;
* the sparse-edge engine equals the dense per-graph engine on random
  masked DAGs;
* one batched service dispatch over a multi-job fleet returns exactly the
  decisions the jobs would get from sequential per-job ``recommend``, which
  is itself one one-request ``decide``;
* requests hand their template to the service as host arrays and upload
  nothing in prep;
* a group's request stack is one compiled call per memo field, equal to
  the per-leaf stack bit for bit and compiled once per job rung;
* the on-device pick replicates the host pick's tie-breaking.
"""
import copy
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import model as enel_model
from repro.core.graph import (CTX_DIM, N_METRICS, NodeAttrs, SweepTemplate,
                              bucket_sweep, build_graph, materialize_candidate,
                              stack_graphs, summary_node, sweep_edge_list)
from repro.core.model import pick_candidate, sweep_sparse_totals
from repro.core.scaling import EnelScaler
from repro.core import service as service_mod
from repro.core.service import DecisionService, sweep_eval_one
from repro.dataflow import FleetCampaign, JobExperiment
from repro.dataflow.runner import (_component_nodes, _future_nodes, _to_graph)


# --------------------------------------------------------------- fixtures
FLEET_JOBS = ("lr", "kmeans", "gbt")


@pytest.fixture(scope="module")
def fleet_exps():
    """Three profiled job experiments (distinct classes) sharing nothing."""
    exps = []
    for i, key in enumerate(FLEET_JOBS):
        exp = JobExperiment(key, seed=20 + i)
        exp.profile(2)
        exps.append(exp)
    return exps


def _decision_kwargs(exp):
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


# ---------------------------------------------- padded sweep == per-graph
_sweep_one = jax.jit(sweep_eval_one, static_argnums=(11,))


@pytest.mark.parametrize("job_key", ["lr", "mpc", "kmeans", "gbt"])
def test_bucketed_sweep_matches_unpadded_exactly(job_key):
    """The sparse sweep on ladder-padded template/deltas == the per-graph
    prediction of every unpadded materialised candidate (up to float
    summation order), and padded components read exactly 0, on the real
    JOBS builders, across K/C shapes that cross the bucket boundaries
    (incl. exact-rung K and small tails)."""
    exp = JobExperiment(job_key, seed=7)
    job = exp.job
    builder = lambda ci, a, z, pr: _to_graph(
        _future_nodes(exp.encoder, job, ci, a, z), pr, ci)
    # a little history so H-summary slots participate too
    rng = np.random.RandomState(0)
    for _ in range(4):
        for k in range(job.n_components):
            s = float(rng.choice([4, 8, 16, 24, 36]))
            nodes = _future_nodes(exp.encoder, job, k, s, s)
            for nd in nodes:
                nd.metrics = rng.rand(N_METRICS).astype(np.float32)
                nd.runtime = float(5.0 + rng.rand())
            exp.enel.record_component(k, nodes, 10.0)
    n = job.n_components
    # (next_comp, stride): K crosses rungs (incl. K==rung exactly), C varies
    cases = [(1, 2), (max(1, n - 12), 2), (n - 4, 2), (n - 1, 2), (1, 8)]
    for next_comp, stride in cases:
        exp.enel.candidate_stride = stride
        candidates = exp.enel.candidate_scaleouts(9)
        template, deltas = exp.enel.build_sweep(
            graph_builder=builder, next_comp=next_comp, n_components=n,
            current_scaleout=9, candidates=candidates)
        padded_t, padded_d, (c_real, k_real) = bucket_sweep(template, deltas)
        c_b = padded_d["a_raw"].shape[0]
        assert c_b >= c_real
        assert padded_t.base["mask"].shape[0] >= k_real
        _, _, per, _ = _sweep_one(
            exp.enel.trainer.params, padded_t.base, padded_t.h_onehot,
            padded_d, *sweep_edge_list(padded_t.base), jnp.zeros(c_b),
            jnp.ones(c_b, bool), 0.0, 0.0, padded_t.levels)
        got = np.asarray(per)[:c_real, :k_real]
        graphs = [materialize_candidate(template, deltas, c)
                  for c in range(c_real)]
        ref = exp.enel.trainer.predict_stacked(
            {key: np.concatenate([g[key] for g in graphs])
             for key in graphs[0]}).reshape(c_real, k_real)
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
        # padded components must read out EXACTLY 0
        tail = np.asarray(per)[:, k_real:]
        np.testing.assert_array_equal(tail, np.zeros_like(tail))


# ------------------------------------------------------ sparse == dense
def _random_graphs(seed, count=7, max_nodes=8):
    rng = np.random.RandomState(seed)
    graphs = []
    for k in range(count):
        n = rng.randint(1, max_nodes)
        nodes = [NodeAttrs(
            f"n{i}", np.tanh(rng.randn(CTX_DIM)).astype(np.float32),
            rng.rand(N_METRICS).astype(np.float32) if rng.rand() < 0.5
            else None,
            float(rng.randint(2, 30)), float(rng.randint(2, 30)),
            time_fraction=float(0.5 + 0.5 * rng.rand()),
            is_summary=bool(rng.rand() < 0.3)) for i in range(n)]
        edges = [(i, j) for j in range(n) for i in range(j)
                 if rng.rand() < 0.4]
        graphs.append(build_graph(nodes, edges, k, max_nodes=max_nodes))
    return graphs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_engine_matches_dense(seed):
    graphs = _random_graphs(seed)
    batch = stack_graphs(graphs)
    params = enel_model.init_enel(jax.random.PRNGKey(seed))
    dense = enel_model.forward_stacked(
        params, {k: jnp.asarray(v) for k, v in batch.items()}
    )["total_runtime"]
    dst, src, val = sweep_edge_list(batch)
    sparse = sweep_sparse_totals(
        params, {k: jnp.asarray(v) for k, v in batch.items()},
        jnp.asarray(dst), jnp.asarray(src), jnp.asarray(val))
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------- batched dispatch == sequential picks
def test_service_matches_sequential_recommend(fleet_exps):
    """3-job fleet: one batched decide == per-job sequential recommend."""
    service = DecisionService()
    kwargs = [_decision_kwargs(exp) for exp in fleet_exps]
    # warm the probe caches so both paths below see identical builder state
    for exp, kw in zip(fleet_exps, kwargs):
        exp.enel.recommend(**kw)
        exp.enel.prepare_request(**kw)
    sequential, requests = [], []
    for i, (exp, kw) in enumerate(zip(fleet_exps, kwargs)):
        # identical encoder RNG draws for both paths' graph builds
        exp.encoder.rng = np.random.RandomState(1000 + i)
        sequential.append(exp.enel.recommend(**kw))
        exp.encoder.rng = np.random.RandomState(1000 + i)
        requests.append(exp.enel.prepare_request(**kw))
    results = service.decide(requests)
    assert service.dispatches >= 1
    assert service.decisions == len(fleet_exps)
    for (s_seq, tot_seq, totals_seq), res in zip(sequential, results):
        assert res.scaleout == s_seq
        assert set(res.totals) == set(totals_seq)
        for s in totals_seq:
            np.testing.assert_allclose(res.totals[s], totals_seq[s],
                                       rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.predicted, tot_seq,
                                   rtol=1e-4, atol=1e-3)


@pytest.fixture(scope="module")
def table2_exps(fleet_exps):
    """The four Table II jobs, profiled: the fleet's three and MPC."""
    mpc = JobExperiment("mpc", seed=23)
    mpc.profile(2)
    return {exp.job_key: exp for exp in list(fleet_exps) + [mpc]}


@pytest.mark.parametrize("job_key", ["lr", "mpc", "kmeans", "gbt"])
def test_recommend_is_one_request_decide(table2_exps, job_key):
    """recommend() is exactly a one-request decide on a service of its
    own: the same scale-out, prediction and totals, and its per-component
    diagnostics are the result's."""
    exp = table2_exps[job_key]
    kw = _decision_kwargs(exp)
    exp.enel.prepare_request(**kw)              # warm the probe cache
    exp.encoder.rng = np.random.RandomState(4000)
    s, predicted, totals = exp.enel.recommend(**kw)
    per = exp.enel.last_per_component
    exp.encoder.rng = np.random.RandomState(4000)
    res = DecisionService().decide([exp.enel.prepare_request(**kw)])[0]
    assert not res.fallback
    assert (s, predicted, totals) == (res.scaleout, res.predicted,
                                      res.totals)
    np.testing.assert_array_equal(per, res.per_component)


def test_result_per_component_lazy_shape(fleet_exps):
    exp = fleet_exps[0]
    kw = _decision_kwargs(exp)
    req = exp.enel.prepare_request(**kw)
    res = DecisionService().decide([req])[0]
    per = res.per_component
    assert per.shape == (len(req.candidate_list), req.n_components)
    s, predicted, totals = exp.enel.apply_decision(req, res)
    assert s == res.scaleout
    # scaler-side lazy diagnostics mirror the result
    np.testing.assert_array_equal(exp.enel.last_per_component, per)


def test_fleet_campaign_round_batches(fleet_exps):
    """A campaign round over 3 jobs batches concurrent decisions and yields
    the same RunStats surface as individual adaptive runs."""
    campaign = FleetCampaign(fleet_exps)
    stats = campaign.adaptive_round("enel", inject_failures=False)
    assert len(stats) == len(fleet_exps)
    for st, exp in zip(stats, fleet_exps):
        assert st.kind == "enel" and st.runtime > 0
        assert st.decide_calls > 0
        assert exp.stats[-1] is st
    assert campaign.service.batched_away > 0      # real cross-job batching
    assert campaign.service.decisions == sum(st.decide_calls for st in stats)


# ------------------------------------------------------- mini templates
def _mini_template(k, n=4, seed=0):
    rng = np.random.RandomState(seed)
    base = {
        "context": rng.rand(k, n, CTX_DIM).astype(np.float32),
        "metrics": rng.rand(k, n, N_METRICS).astype(np.float32),
        "metrics_valid": np.ones((k, n), bool),
        "a_raw": np.ones((k, n), np.float32),
        "z_raw": np.ones((k, n), np.float32),
        "r": np.ones((k, n), np.float32),
        "adj": np.zeros((k, n, n), bool),
        "mask": np.ones((k, n), bool),
        "is_summary": np.zeros((k, n), bool),
    }
    flags = np.zeros((k, n), bool)
    return SweepTemplate(base=base, h_onehot=np.zeros((k, n), np.float32),
                         a_follows_a=flags, a_follows_z=flags,
                         z_follows_a=flags, z_follows_z=flags,
                         r_eq=base["r"], r_neq=base["r"])


# ------------------------------------------- double-buffered dispatch parity
def test_double_buffered_dispatch_matches_sync(fleet_exps):
    """Overlapped stack-next-while-device-computes dispatch returns exactly
    the synchronous path's decisions (same picks, totals, diagnostics)."""
    kwargs = [_decision_kwargs(exp) for exp in fleet_exps]
    for exp, kw in zip(fleet_exps, kwargs):
        exp.enel.prepare_request(**kw)          # warm probe caches
    def requests():
        reqs = []
        for i, (exp, kw) in enumerate(zip(fleet_exps, kwargs)):
            exp.encoder.rng = np.random.RandomState(2000 + i)
            reqs.append(exp.enel.prepare_request(**kw))
        return reqs
    sync = DecisionService(double_buffer=False)
    buf = DecisionService(double_buffer=True)
    res_s = sync.decide(requests())
    res_b = buf.decide(requests())
    assert sync.dispatches == buf.dispatches
    for a, b in zip(res_s, res_b):
        assert a.scaleout == b.scaleout
        assert a.predicted == b.predicted
        assert a.totals == b.totals
        np.testing.assert_array_equal(a.per_component, b.per_component)


# ------------------------------------------------ host templates in requests
def test_lockstep_round_uploads_templates_in_decide(monkeypatch):
    """One adaptive lockstep round through the service: each prep span
    reports the template bytes it hands over, and the service stacks those
    host arrays itself."""
    from repro import obs
    exps = [JobExperiment(k, seed=80 + i, candidate_stride=4)
            for i, k in enumerate(("lr", "lr", "kmeans"))]
    camp = FleetCampaign(exps)
    camp.profile(2)
    spans, stacked, reqs = [], [], []
    real_span, real_stack = obs.span, service_mod._stack_rows

    def recording_span(kind, _ring=False, **attrs):
        sp = real_span(kind, _ring, **attrs)
        spans.append(sp)
        return sp

    def recording_stack(treedef, leaf_rows, tally):
        stacked.extend(leaf for row in leaf_rows for leaf in row
                       if isinstance(leaf, np.ndarray))
        return real_stack(treedef, leaf_rows, tally)

    def recording_prepare(prepare):
        def wrapped(**kw):
            req = prepare(**kw)
            reqs.append(req)
            return req
        return wrapped

    monkeypatch.setattr(obs, "span", recording_span)
    monkeypatch.setattr(service_mod, "_stack_rows", recording_stack)
    for exp in exps:
        exp.enel.prepare_request = recording_prepare(
            exp.enel.prepare_request)
    stats = camp.adaptive_round("enel", inject_failures=False)
    assert reqs and len(stats) == len(exps)
    adopt = [sp.attrs["host_bytes"] for sp in spans
             if sp.kind == "enel.prep.adopt"]
    assert len(adopt) == len(reqs) and min(adopt) > 0
    assert adopt == [req.h_onehot.nbytes + sum(
        v.nbytes for v in req.base.values()) for req in reqs]
    stack_bytes = sum(sp.attrs["bytes"] for sp in spans
                      if sp.kind == "enel.decide.stack")
    assert stack_bytes >= sum(adopt)
    uploaded = {id(leaf) for leaf in stacked}
    for req in reqs:
        assert id(req.h_onehot) in uploaded
        assert all(id(v) in uploaded for v in req.base.values())


# ----------------------------------------------- cross-engine runner parity
def test_runner_parity_numpy_vs_batched_engine():
    """Same seed -> identical RunRecords and decisions through the FULL
    runner (profiling targets, adaptive scale-out trajectory) whether the
    simulation runs on the numpy event loop or the vectorized engine."""
    from repro.dataflow.runner import JobExperiment
    en = JobExperiment("gbt", seed=9, engine="numpy")
    eb = JobExperiment("gbt", seed=9, engine="batched")
    en.profile(2)
    eb.profile(2)
    for a, b in zip(en.stats, eb.stats):
        assert np.float32(a.runtime) == np.float32(b.runtime)
    assert en.target == eb.target
    sa = en.adaptive_run("enel", inject_failures=True)
    sb = eb.adaptive_run("enel", inject_failures=True)
    assert np.float32(sa.runtime) == np.float32(sb.runtime)
    assert sa.scaleouts == sb.scaleouts
    assert sa.n_failures == sb.n_failures
    assert sa.n_rescales == sb.n_rescales


# ------------------------------------------------------- device pick parity
def test_pick_candidate_matches_host_pick():
    cand = np.array([4, 6, 8, 10, 12, 12], np.float32)
    valid = np.array([1, 1, 1, 1, 1, 0], bool)
    for seed in range(30):
        rng = np.random.RandomState(seed)
        totals = (rng.rand(6) * 30 + 5).astype(np.float32)
        target = float(rng.rand() * 40)
        t_host = {float(s): float(t)
                  for s, t, v in zip(cand, totals, valid) if v}
        host_s, _, _ = EnelScaler._pick(
            sorted(t_host), {s: t_host[s] for s in t_host}, target)
        idx = int(pick_candidate(jnp.asarray(cand), jnp.asarray(valid),
                                 jnp.asarray(totals), jnp.asarray(target)))
        assert valid[idx]
        assert float(cand[idx]) == host_s


# ------------------------------------------------------- buffer donation
def test_fit_donation_leaves_no_stale_holder(fleet_exps):
    """Fits donate the trainer's params/opt buffers on every backend, so
    nothing may serve the replaced leaves afterwards: a request prepared
    after the fit carries live params, and the service's identity-keyed
    stack memo restacks them instead of reusing the stack of the old ones."""
    exp = fleet_exps[0]
    # a deep copy: on CPU np.asarray views device memory, and a buffer with
    # a live host view is never donated
    saved = copy.deepcopy(exp.trainer.snapshot_state())
    try:
        kw = _decision_kwargs(exp)
        svc = DecisionService()
        svc.decide([exp.enel.prepare_request(**kw)])   # memo holds old params
        old = jax.tree_util.tree_leaves((exp.trainer.params,
                                         exp.trainer.opt[:2]))
        exp.trainer.fit_resident(steps=8, latest_only=True)
        assert all(leaf.is_deleted() for leaf in old)
        req = exp.enel.prepare_request(**kw)
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(req.params))
        res = svc.decide([req])[0]
        fresh = DecisionService().decide([req])[0]
        assert not res.fallback and res.scaleout == fresh.scaleout
        assert res.totals == fresh.totals
    finally:
        exp.trainer.restore_state(saved)


# ------------------------------------------------- compiled group stacking
def _device_rows(n):
    """``n`` request-like rows with device params, base and h_onehot, as the
    scaler's template cache hands them to the service."""
    rows = []
    for i in range(n):
        tmpl = _mini_template(3, seed=i)
        rows.append(types.SimpleNamespace(
            params=enel_model.init_enel(jax.random.PRNGKey(i)),
            base={k: jnp.asarray(v) for k, v in tmpl.base.items()},
            h_onehot=jnp.asarray(tmpl.h_onehot)))
    return rows


@pytest.mark.parametrize("n_real", [1, 3, 8])
def test_group_stack_equals_per_leaf_stack(n_real):
    """A memo miss stacks a field's device leaves in one compiled call that
    equals the per-leaf ``jnp.stack`` bit for bit, padding included; a hit
    returns the stored stack and traces nothing."""
    group = _device_rows(n_real)
    j_b = service_mod._job_bucket(n_real)
    rows = group + [group[-1]] * (j_b - n_real)
    svc = DecisionService()
    tally = {"hits": 0, "misses": 0, "bytes": 0, "launches": 0}
    fields = ("params", "base", "h_onehot")
    got = {f: svc._stack_tree(("k", j_b, f), rows,
                              lambda r, f=f: getattr(r, f), tally)
           for f in fields}
    assert tally == {"hits": 0, "misses": 3, "bytes": 0, "launches": 3}
    for f in fields:
        trees = [getattr(r, f) for r in rows]
        want = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
        assert (jax.tree_util.tree_structure(got[f])
                == jax.tree_util.tree_structure(want))
        for a, b in zip(jax.tree_util.tree_leaves(got[f]),
                        jax.tree_util.tree_leaves(want)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    traces = enel_model.trace_count("group_stack")
    for f in fields:
        again = svc._stack_tree(("k", j_b, f), rows,
                                lambda r, f=f: getattr(r, f), tally)
        assert again is got[f]
    assert tally["hits"] == 3 and tally["launches"] == 3
    assert enel_model.trace_count("group_stack") == traces


def test_group_stack_compiles_stay_bounded():
    """Groups of different real sizes in one job rung share one compiled
    stack per field, and a second same-shape campaign round traces neither
    the stack nor the sweep again (the window's zero-compile rule)."""
    exps = [JobExperiment("lr", seed=60 + i) for i in range(4)]
    camp = FleetCampaign(exps)
    camp.profile(2)
    camp.adaptive_round("enel", inject_failures=False)      # warm-up round
    svc = camp.service
    decide = svc.decide
    seen = []

    def decide_with_subgroup(reqs):
        # the first three of a four-request group alone: real size 3 in the
        # same rung of 4, answered exactly as inside the full group
        if len(reqs) == 4:
            part = decide(reqs[:3])
            full = decide(reqs)
            for a, b in zip(part, full):
                assert a.scaleout == b.scaleout and a.totals == b.totals
            seen.append(len(reqs))
            return full
        return decide(reqs)

    svc.decide = decide_with_subgroup
    before = dict(enel_model.TRACE_COUNTS)
    camp.adaptive_round("enel", inject_failures=False)
    assert seen                             # sizes 3 and 4 both stacked
    for name in ("group_stack", "fleet_sweep"):
        assert enel_model.TRACE_COUNTS[name] == before.get(name, 0), name


def test_burst_splits_at_top_rung_without_new_trace(fleet_exps):
    """A burst of 40 same-bucket requests is split into groups of at most
    the top job rung (32 + 8), each answered by its own request's result,
    and after a warm-up of every rung it traces no new shape (padding 40
    to 64 would)."""
    exp = fleet_exps[0]
    req = exp.enel.prepare_request(**_decision_kwargs(exp))
    svc = DecisionService()
    for j in service_mod.JOB_LADDER:                       # warm-up
        svc.decide([dataclasses.replace(req) for _ in range(j)])
    sizes = []
    dispatch = svc._dispatch_group

    def record(key, group):
        sizes.append(len(group))
        return dispatch(key, group)
    svc._dispatch_group = record
    burst = [dataclasses.replace(req, rid=1000 + k) for k in range(40)]
    before = dict(enel_model.TRACE_COUNTS)
    out = svc.decide(burst)
    assert sorted(sizes) == [8, 32]
    assert dict(enel_model.TRACE_COUNTS) == before
    assert [r.rid for r in out] == [q.rid for q in burst]
    assert len({(r.scaleout, tuple(r.totals.items())) for r in out}) == 1
