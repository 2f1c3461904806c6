"""Batched candidate-sweep engine correctness.

The sweep must reproduce the per-graph predict path: one template per
remaining component + per-candidate deltas, evaluated by the decision
service in a single jit, equals building every (candidate x component)
graph and predicting it individually.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import model as enel_model
from repro.core.graph import (CTX_DIM, N_METRICS, NodeAttrs, build_graph,
                              historical_summaries_batch, historical_summary,
                              materialize_candidate, propagation_depth,
                              stack_graphs, summary_node)
from repro.core.scaling import EnelScaler
from repro.core.service import DecisionService
from repro.core.training import EnelTrainer

RNG = np.random.RandomState(0)


def _ctx(i):
    return np.tanh(np.random.RandomState(300 + i).randn(CTX_DIM)
                   ).astype(np.float32)


def _nodes(k, a, z, observe=True):
    nodes = []
    for i in range(3):
        s = a if i == 0 else z
        rt = (20.0 / z + 0.5) if observe else None
        met = np.array([0.6, 1.0 / z, 0.2, 0.08, 0.0],
                       np.float32) if observe else None
        nodes.append(NodeAttrs(f"st{i}", _ctx(i), met, s, z,
                               1.0 if a == z else 0.8, rt))
    return nodes


def _graph(nodes, preds, k):
    n = len(nodes)
    edges = [(i, i + 1) for i in range(n - 1)] + \
        [(n + j, 0) for j in range(len(preds))]
    return build_graph(nodes + preds, edges, k)


def _builder(k, a, z, preds):
    return _graph(_nodes(k, a, z, observe=False), preds, k)


@pytest.fixture(scope="module")
def scaler():
    trainer = EnelTrainer(seed=0)
    sc = EnelScaler(trainer, (4, 36))
    for _ in range(6):
        for k in range(5):
            s = int(RNG.choice([4, 8, 16, 24, 32, 36]))
            nodes = _nodes(k, s, s)
            sc.record_component(k, nodes, sum(n.runtime for n in nodes))
    trainer.fit([_graph(_nodes(k, 8, 8), [], k) for k in range(5)], steps=8)
    return sc


@pytest.mark.parametrize("next_comp", [0, 2, 4])
def test_sweep_matches_pergraph_predict(scaler, next_comp):
    """The service's per-component sweep == per-graph
    EnelTrainer.predict over every candidate."""
    cands = scaler.candidate_scaleouts(8)
    summ = summary_node(_nodes(1, 8, 8), name="P1")
    template, deltas = scaler.build_sweep(
        graph_builder=_builder, next_comp=next_comp, n_components=5,
        current_scaleout=8, candidates=cands, current_summary=summ)
    req = scaler.prepare_request(
        graph_builder=_builder, next_comp=next_comp, n_components=5,
        elapsed=10.0, current_scaleout=8, target_runtime=25.0,
        current_summary=summ)
    per = DecisionService().decide([req])[0].per_component
    assert per.shape == (len(cands), 5 - next_comp)
    for c in range(len(cands)):
        ref = scaler.trainer.predict_stacked(
            materialize_candidate(template, deltas, c))
        np.testing.assert_allclose(per[c], ref, atol=1e-5)


@pytest.mark.parametrize("current_scaleout", [4, 8, 20])
@pytest.mark.parametrize("next_comp", [0, 2, 4])
def test_sweep_recommend_matches_pergraph_recommend(scaler, next_comp,
                                                    current_scaleout):
    """With a candidate-invariant-context builder, the batched recommend and
    the original per-candidate-graph path agree on totals and choice."""
    kw = dict(graph_builder=_builder, next_comp=next_comp, n_components=5,
              elapsed=10.0, current_scaleout=current_scaleout,
              target_runtime=25.0,
              current_summary=summary_node(_nodes(1, 8, 8), name="P1"))
    s_new, tot_new, totals_new = scaler.recommend(**kw)
    s_old, tot_old, totals_old = scaler.recommend_pergraph(**kw)
    assert s_new == s_old
    assert set(totals_new) == set(totals_old)
    for s in totals_new:
        np.testing.assert_allclose(totals_new[s], totals_old[s], atol=1e-4)
    np.testing.assert_allclose(tot_new, tot_old, atol=1e-4)


def test_historical_summaries_batch_matches_scalar(scaler):
    hist = scaler.hist_summaries[2]
    targets = np.array([4.0, 9.0, 17.0, 36.0], np.float32)
    batch = historical_summaries_batch(hist, targets, beta=3)
    for i, t in enumerate(targets):
        h = historical_summary(hist, float(t), beta=3)
        np.testing.assert_allclose(batch["context"][i], h.context, atol=1e-6)
        np.testing.assert_allclose(batch["metrics"][i], h.metrics, atol=1e-6)
        np.testing.assert_allclose(batch["start"][i], h.start_scaleout,
                                   atol=1e-5)
        np.testing.assert_allclose(batch["end"][i], h.end_scaleout, atol=1e-5)


def test_propagation_depth():
    g = build_graph([NodeAttrs(f"n{i}", _ctx(i), None, 4, 4)
                     for i in range(4)], [(0, 1), (1, 2), (2, 3)])
    assert propagation_depth(g.adj, g.mask) == 3
    diamond = build_graph([NodeAttrs(f"n{i}", _ctx(i), None, 4, 4)
                           for i in range(4)],
                          [(0, 1), (0, 2), (1, 3), (2, 3)])
    assert propagation_depth(diamond.adj, diamond.mask) == 2
    empty = build_graph([], [])
    assert propagation_depth(empty.adj, empty.mask) == 0


def test_depth_lowered_levels_are_exact():
    """Propagation is a fixed point after `depth` rounds: running the chain
    graph at its exact depth equals running MAX_LEVELS rounds bit-for-bit."""
    params = enel_model.init_enel(jax.random.PRNGKey(1))
    nodes = [NodeAttrs(f"n{i}", _ctx(i),
                       RNG.rand(N_METRICS).astype(np.float32)
                       if i == 0 else None, 4, 4) for i in range(5)]
    g = build_graph(nodes, [(i, i + 1) for i in range(4)])
    batch = {k: jnp.asarray(v) for k, v in stack_graphs([g]).items()}
    depth = propagation_depth(g.adj, g.mask)
    full = enel_model.forward_stacked(params, batch)
    low = enel_model.forward_stacked(params, batch, levels=depth)
    np.testing.assert_array_equal(np.asarray(full["metrics"]),
                                  np.asarray(low["metrics"]))
    np.testing.assert_array_equal(np.asarray(full["total_runtime"]),
                                  np.asarray(low["total_runtime"]))
