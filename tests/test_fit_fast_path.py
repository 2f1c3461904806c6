"""Online-learning fast path: the device-resident TrainingCache.

Cache equivalence: incremental ring-buffer appends must reproduce a
one-shot ``stack_graphs`` and the resident fit must match the legacy
list-of-graphs fit when metric dropout is disabled.  The non-finite guard
skips poisoned Adam steps.  (No hypothesis dependency — plain seeded RNG.)
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (CTX_DIM, MAX_NODES, N_METRICS, NodeAttrs,
                              TrainingCache, build_graph, stack_graphs)
from repro.core.training import EnelTrainer


def _tree_allclose(a, b, atol, rtol):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=rtol)


# ---------------------------------------------------------- cache equivalence
def _chain_graph(k, n=4, seed=0, max_nodes=MAX_NODES):
    r = np.random.RandomState(100 + seed)
    nodes = [NodeAttrs(f"n{i}", np.tanh(r.randn(CTX_DIM)).astype(np.float32),
                       r.rand(N_METRICS).astype(np.float32), 4 + i, 8, 0.9,
                       runtime=5.0 + i, overhead=0.5 if i == 0 else None)
             for i in range(n)]
    return build_graph(nodes, [(i, i + 1) for i in range(n - 1)], k,
                       max_nodes=max_nodes)


def test_cache_incremental_equals_one_shot_stack():
    graphs = [_chain_graph(k, seed=k) for k in range(5)]
    cache = TrainingCache(capacity=8, max_nodes=8)
    cache.extend(graphs[:2])
    cache.extend(graphs[2:])
    host = cache.stacked_host()
    ref = stack_graphs(graphs)
    for k, v in host.items():
        r = ref[k][:, :8, :8] if k == "adj" else \
            (ref[k][:, :8] if ref[k].ndim > 1 else ref[k])
        np.testing.assert_array_equal(v[:5], r, err_msg=k)


def test_cache_ring_wraparound_keeps_newest():
    graphs = [_chain_graph(k, seed=k) for k in range(7)]
    cache = TrainingCache(capacity=4, max_nodes=8)
    for g in graphs:
        cache.extend([g])
    host = cache.stacked_host()
    ref = stack_graphs(graphs[-4:])
    np.testing.assert_array_equal(host["runtime"], ref["runtime"][:, :8])
    np.testing.assert_array_equal(host["adj"], ref["adj"][:, :8, :8])
    assert cache.count == 4


def test_cache_grows_node_slots():
    cache = TrainingCache(capacity=4, max_nodes=4)
    cache.extend([_chain_graph(0, n=3, seed=0)])
    cache.extend([_chain_graph(1, n=7, seed=1)])       # forces 4 -> 8 slots
    assert cache.max_nodes == 8
    host = cache.stacked_host()
    ref = stack_graphs([_chain_graph(0, n=3, seed=0),
                        _chain_graph(1, n=7, seed=1)])
    np.testing.assert_array_equal(host["mask"], ref["mask"][:, :8])
    np.testing.assert_array_equal(host["metrics"], ref["metrics"][:, :8])


def test_fit_resident_matches_legacy_fit_no_dropout():
    """With per-step dropout off, training on the ring == the legacy host
    restack path (same graphs, same step count, same seed)."""
    graphs = [_chain_graph(k, seed=k) for k in range(5)]
    tr_res = EnelTrainer(seed=0, cache_capacity=8)
    tr_res.extend_history(graphs)
    l_res = tr_res.fit_resident(steps=8, metric_dropout=0.0)
    tr_leg = EnelTrainer(seed=0)
    l_leg = tr_leg.fit(graphs, steps=8, metric_dropout=0.0)
    np.testing.assert_allclose(l_res, l_leg, rtol=1e-4)
    _tree_allclose(tr_res.params, tr_leg.params, atol=1e-5, rtol=1e-3)


def test_fit_resident_latest_only_ignores_older_history():
    """Fine-tuning on the newest extend() == training on just those graphs."""
    old = [_chain_graph(k, seed=k) for k in range(3)]
    new = [_chain_graph(k, seed=10 + k) for k in range(2)]
    tr_a = EnelTrainer(seed=0, cache_capacity=8)
    tr_a.extend_history(old)
    tr_a.extend_history(new)
    l_a = tr_a.fit_resident(steps=8, metric_dropout=0.0, latest_only=True)
    tr_b = EnelTrainer(seed=0, cache_capacity=8)
    tr_b.extend_history(new)
    l_b = tr_b.fit_resident(steps=8, metric_dropout=0.0, latest_only=True)
    np.testing.assert_allclose(l_a, l_b, rtol=1e-5)
    _tree_allclose(tr_a.params, tr_b.params, atol=1e-6, rtol=1e-5)


def test_fit_resident_per_step_dropout_trains():
    tr = EnelTrainer(seed=0, cache_capacity=8)
    tr.extend_history([_chain_graph(k, seed=k) for k in range(5)])
    l1 = tr.fit_resident(steps=8, metric_dropout=0.5)
    l2 = tr.fit_resident(steps=64, metric_dropout=0.5)
    assert np.isfinite(l1) and np.isfinite(l2)
    assert l2 < l1


# ----------------------------------------------------- non-finite fit guard
def test_nonfinite_guard_skips_poisoned_legacy_fit():
    """Legacy fit() on a batch with a NaN runtime target: every Adam step's
    loss is non-finite, the in-scan guard skips them all, and the params
    stay exactly the (finite) pre-fit values."""
    bad = _chain_graph(0, seed=0)
    bad.runtime[bad.runtime_valid] = np.nan
    tr = EnelTrainer(seed=3)
    before = jax.tree_util.tree_map(np.asarray, tr.params)
    loss = tr.fit([bad], steps=8, metric_dropout=0.0)
    assert not np.isfinite(loss)
    assert tr.last_skipped_steps == 8
    assert tr.nonfinite_steps == 8
    assert tr.poisoned_fits == 1
    _tree_allclose(tr.params, before, atol=0, rtol=0)
    assert tr.params_finite()


def test_fit_resident_quarantine_retry_heals_in_place_corruption():
    """NaN written straight into resident ring rows (past the entry
    quarantine): the first fit skips every step, sweeps the ring, and the
    automatic retry trains to a finite loss on the healed buffers."""
    tr = EnelTrainer(seed=4, cache_capacity=8)
    tr.extend_history([_chain_graph(k, seed=k) for k in range(4)])
    tr.cache.buffers["metrics"] = \
        tr.cache.buffers["metrics"].at[1].set(jnp.nan)
    q0 = tr.cache.quarantined
    loss = tr.fit_resident(steps=8, from_scratch=True, metric_dropout=0.0)
    assert np.isfinite(loss)
    assert tr.cache.quarantined == q0 + 1
    assert not tr.cache.slot_ok[1]
    assert tr.params_finite()
    # without the retry the poisoned fit reports non-finite and skips all
    tr2 = EnelTrainer(seed=4, cache_capacity=8)
    tr2.extend_history([_chain_graph(k, seed=k) for k in range(4)])
    tr2.cache.buffers["metrics"] = \
        tr2.cache.buffers["metrics"].at[1].set(jnp.nan)
    loss2 = tr2.fit_resident(steps=8, from_scratch=True,
                             metric_dropout=0.0, _retry=False)
    assert not np.isfinite(loss2)
    assert tr2.last_skipped_steps == 8
    assert tr2.params_finite()
