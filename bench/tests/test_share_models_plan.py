"""Followers attached to their class leader's models (``share_models_from``)
yield the same fused-campaign plan as tenants built and restored one by
one: same device tables, same initial carry, same static shape."""
import jax
import numpy as np

import harness
from drivers import fused_campaign as fc

CFG = dict(harness.load_json(harness.ROOT,
                             "bench/configs/table2-fleet256.json"),
           tenants=8)


def _one_by_one(class_seeds):
    from repro.core.service import DecisionService
    from repro.dataflow import FleetCampaign, JobExperiment
    jobs = CFG["jobs"]
    exps = [JobExperiment(jobs[i % 4], seed=class_seeds[i % 4],
                          candidate_stride=CFG["candidate_stride"])
            for i in range(CFG["tenants"])]
    camp = FleetCampaign(exps, DecisionService(), engine="batched")
    for exp in exps[:4]:
        exp.profile(CFG["profiling_runs"])
    snaps = [exp.snapshot_state() for exp in exps[:4]]
    for i, exp in enumerate(exps[4:], start=4):
        exp.restore_state(snaps[i % 4])
    return camp


def _leaves(plan):
    return jax.tree_util.tree_leaves((plan.dev, plan.init))


def test_shared_followers_build_the_same_plan():
    from repro.core import campaign_kernel as ck
    class_seeds = harness.seeds(2 ** 31 + 11, 4)
    shared, leaders = fc.build_fleet(CFG, class_seeds)
    fc.profile_fleet(shared, leaders, CFG["profiling_runs"])
    p_shared = ck.build_plan(shared.experiments, 5)
    p_solo = ck.build_plan(_one_by_one(class_seeds).experiments, 5)
    assert p_shared.static == p_solo.static
    a, b = _leaves(p_shared), _leaves(p_solo)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert jax.tree_util.tree_structure(p_shared.dev) == \
        jax.tree_util.tree_structure(p_solo.dev)
