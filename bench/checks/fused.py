"""The comparisons that decide ``correct`` for fused-campaign cells.

* every window campaign reproduces the warm-up campaign bit for bit, and
  the stepped driver (one jitted step per dispatch) the last of them;
* tenants of one class are copies (same seed, same profiled state), so
  each tenant's columns equal its class leader's: a tenant the scan drops
  or computes otherwise shows here;
* sampled tenants' stage runtimes against a replay of their scale-out
  schedule through the numpy simulator;
* three fits of each sampled tenant against the plain reference fit
  (``reference/enel_ref.py``, compared as ``checks/fits.py`` says): the
  profile's scratch fit and the last run's scratch retrain, both from the
  reference's own initialisation over the recorded ring, and one
  fine-tune inside the campaign, from the program's state before it over
  the newest run's rows, which the reference gathers from the ring
  itself;
* sampled first-run decisions, made with the weights the benchmark made
  (the reference's own profile fit) and loaded in set-up, against the
  plain reference sweep and pick with those weights, on graphs rebuilt
  from the plan's context and history tables and the replayed
  observations.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import harness
from checks.fits import (fit_key, fresh_state, newest_rows,
                         scratch_weights)
from reference import enel_ref


def check_config(cfg: Dict) -> None:
    """The configuration's stated widths, history window and scale-out
    range are the ones the program runs."""
    from repro.core import model
    from repro.dataflow import runner, workloads
    got = {"hidden": model.HIDDEN, "edge_dim": model.EDGE_DIM,
           "ctx_dim": model.CTX_DIM, "n_metrics": model.N_METRICS,
           "history_window": runner.HISTORY_WINDOW,
           "scaleout_range": list(workloads.SCALEOUT_RANGE)}
    for key, value in got.items():
        if cfg[key] != value:
            raise ValueError(f"config {key}={cfg[key]}, program runs {value}")


def sample_tenants(n_tenants: int, n_classes: int, check: Dict,
                   seed: int) -> List[int]:
    """``tenants_per_class`` tenants of each class, drawn from the seed."""
    rng = np.random.default_rng(harness.seeds(seed, 2)[1])
    out = []
    for c in range(n_classes):
        members = np.arange(c, n_tenants, n_classes)
        out += [int(j) for j in rng.choice(
            members, check["tenants_per_class"], replace=False)]
    return sorted(out)


def as_report(carry, ys):
    to_host = lambda t: jax.tree_util.tree_map(np.asarray, t)
    return SimpleNamespace(carry=to_host(carry), ys=to_host(ys))


def _leaves(report):
    return jax.tree_util.tree_leaves((report.carry, report.ys))


def mismatched_leaves(a, b) -> int:
    """Output leaves of two campaigns that are not bit-identical."""
    return sum(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(_leaves(a), _leaves(b)))


def copy_mismatch(report, n_classes: int) -> int:
    """Tenants whose outputs differ from their class leader's (tenant
    ``i % n_classes``).  Step outputs carry the tenant on the last axis,
    the carry on the first."""
    n = np.asarray(report.ys["decided"]).shape[-1]
    lead = np.arange(n) % n_classes
    bad = np.zeros(n, bool)
    for y in jax.tree_util.tree_leaves(report.ys):
        y = np.asarray(y)
        diff = y != y[..., lead]
        bad |= diff.reshape(-1, n).any(axis=0)
    for c in jax.tree_util.tree_leaves(report.carry):
        c = np.asarray(c)
        diff = c != c[lead]
        bad |= diff.reshape(n, -1).any(axis=1)
    return int(bad.sum())


def sim_replay(exps, slot_state0: Dict, ys, plan):
    """Replay each sampled tenant's a/z schedule through the numpy
    simulator from its pre-campaign state.  Returns (max relative stage
    runtime deviation, {tenant: {step: (stages, 5) metrics}})."""
    from repro.dataflow.simulator import ClusterSim
    from repro.sim.engine import NumpySimBackend, SimStepRequest
    a = np.asarray(ys["a"]).astype(int)
    z = np.asarray(ys["z"]).astype(int)
    rt = np.asarray(ys["rt"])
    c_max = plan.static.c_max
    worst, observed = 0.0, {}
    for j, state in slot_state0.items():
        exp = exps[j]
        sim = ClusterSim(seed=exp.seed, scenario=exp.scenario)
        sim.load_state_dict(state)
        npb = NumpySimBackend()
        slot = npb.adopt(sim, exp.job)
        observed[j] = {}
        for r in range(plan.n_runs):
            npb.begin_run(slot)
            clock = 0.0
            for k in range(exp.job.n_components):
                t = r * c_max + k
                res = npb.step([SimStepRequest(slot, k, int(a[t, j]),
                                               int(z[t, j]), clock,
                                               False)])[0]
                clock = res.clock_end
                observed[j][t] = np.stack(
                    [st.metrics for st in res.component.stages]
                ).astype(np.float32)
                for i, st in enumerate(res.component.stages):
                    ref = float(np.float32(st.runtime))
                    worst = max(worst, abs(float(rt[t, i, j]) - ref) / ref)
    return worst, observed


def _tenant_tree(tree, j):
    return jax.tree_util.tree_map(lambda x: np.asarray(x)[j], tree)


def tune_run(plan, seed: int) -> int:
    """The fine-tuned run (after the first) whose fit the check compares,
    drawn from the seed."""
    scratch = np.asarray(plan.dev["scratch_at"])
    runs = [r for r in range(1, plan.n_runs) if not scratch[r]]
    rng = np.random.default_rng(harness.seeds(seed, 4)[3])
    return int(rng.choice(runs))


def stepped_run(plan, t_fit: int, sample: List[int]):
    """The campaign through the stepped driver (one jitted step per
    dispatch), split around step ``t_fit``: returns its report and the
    sampled tenants' carries before and after that step, on the host."""
    from repro.core import campaign_kernel as ck
    part = lambda c: jax.tree_util.tree_map(
        lambda x: np.asarray(x)[np.asarray(sample)], c)
    c, ys_a = ck.run_stepped(plan, stop=t_fit)
    before = part(c)
    c, ys_b = ck.run_stepped(plan, carry=c, start=t_fit, stop=t_fit + 1)
    after = part(c)
    c, ys_c = ck.run_stepped(plan, carry=c, start=t_fit + 1)
    ys = jax.tree_util.tree_map(lambda *x: jnp.concatenate(x),
                                ys_a, ys_b, ys_c)
    return as_report(c, ys), before, after


def fit_cases(cfg: Dict, plan, exps, sample: List[int], made: Dict,
              t_fit: int, before, after, last) -> List[Dict]:
    """Per sampled tenant, the three fits the check compares, each with the
    reference's inputs and the program's result: the profile's scratch fit
    (``made``'s case of its class, whose reference result the first run's
    decisions used), the fine-tune at the end of run ``t_fit // c_max``
    (from the program's parameters and optimizer state before it) and the
    last run's scratch retrain."""
    fit = cfg["fit"]
    c_max = plan.static.c_max
    r_tune, r_last = t_fit // c_max, plan.n_runs - 1
    if not bool(np.asarray(plan.dev["scratch_at"])[r_last]):
        raise ValueError("the campaign's last run must be a scratch retrain")
    rows = 1 << (c_max - 1).bit_length()
    ring = last.carry["ring"]
    out = []
    for s, j in enumerate(sample):
        seed = exps[j].seed
        out.append(dict(made[j % len(made)], tenant=j))
        r_after = after["ring"]
        batch, w = newest_rows(_tenant_tree(r_after["buffers"], s),
                               int(r_after["pos"][s]), r_after["slot_ok"][s],
                               exps[j].job.n_components, rows)
        out.append({"tenant": j, "kind": "tune", "program_scratch": False,
                    "p0": _tenant_tree(before["params"], s),
                    "opt0": _tenant_tree(before["opt"], s),
                    "batch": batch, "w": w,
                    "key": fit_key(seed, 1 + r_tune),
                    "steps": fit["tune_steps"],
                    "got": _tenant_tree(after["params"], s)})
        p0, opt0 = fresh_state(seed)
        out.append({"tenant": j, "kind": "scratch", "program_scratch": True,
                    "p0": p0, "opt0": opt0,
                    "batch": _tenant_tree(ring["buffers"], j),
                    "w": scratch_weights(int(ring["count"][j]),
                                         ring["slot_ok"][j]),
                    "key": fit_key(seed, 1 + r_last),
                    "steps": fit["scratch_steps"],
                    "got": _tenant_tree(last.carry["params"], j)})
    return out


def decision_graphs(plan, j: int, t: int, ys, pm: np.ndarray) -> Dict:
    """The sweep graphs of tenant ``j``'s decision at step ``t``, rebuilt
    from the plan's tables as (real candidates, component slots, N, ...)
    arrays; components that are not still to run are fully masked."""
    d = {k: np.asarray(v) for k, v in plan.dev.items()
         if k.startswith(("sw_", "hsw_", "obs_ctx", "p_ctx", "cand", "cls",
                          "hcls", "n_comp"))}
    st = plan.static
    k = t % st.c_max
    g, h, nc = int(d["cls"][j]), int(d["hcls"][j]), int(d["n_comp"][j])
    nsg = d["obs_ctx"].shape[3]
    zi = lambda s: int(np.clip(int(s) - st.lo, 0, nsg - 1))
    s_cur = float(ys["z"][t, j])
    pa, pz = float(ys["a"][t, j]), float(ys["z"][t, j])
    cand = d["cand"][d["cand_valid"]]
    n_c, k_pad = len(cand), d["sw_mask0"].shape[1]
    comp = np.arange(1, k_pad + 1)
    stg, sidx = d["sw_is_stage"][g], d["sw_stage_idx"][g]
    isp, ish = d["sw_is_p"][g], d["sw_is_h"][g]
    isn = (comp == k + 1)[:, None]
    mask = d["sw_mask0"][g] & ((comp > k) & (comp < nc))[:, None] \
        & (~isp | isn)
    cc = np.clip(comp, 0, d["obs_ctx"].shape[1] - 1)
    ctx_st = d["obs_ctx"][g, cc[:, None], sidx, zi(s_cur)]        # (K, N, C)
    pctx = d["p_ctx"][g, k, zi(pz)]
    oh = (d["sw_oh"][g] > 0)[None, :, :, None]                     # H slot
    context = np.where(stg[..., None], ctx_st, 0.0) \
        + np.where(isp[..., None], pctx, 0.0)
    context = np.where(oh, d["hsw_ctx"][h][:n_c, :, None, :], context[None])
    metrics = np.where(isp[..., None], pm, 0.0)
    metrics = np.where(oh, d["hsw_met"][h][:n_c, :, None, :], metrics[None])
    z3 = cand[:, None, None]
    a3 = np.where(isn[None], s_cur, z3)
    hs = d["hsw_start"][h][:n_c, :, None]
    he = d["hsw_end"][h][:n_c, :, None]
    st0 = stg & (sidx == 0)
    a_raw = np.where(st0, a3, np.where(stg, z3, np.where(
        isp, pa, np.where(ish, hs, 1.0))))
    z_raw = np.where(stg, z3, np.where(isp, pz, np.where(ish, he, 1.0)))
    r = np.where(stg & (a3 != z3), 0.8, 1.0)
    valid = (isp | (ish & d["hsw_val"][h][:n_c, :, None])) & mask
    shape = (n_c, k_pad, mask.shape[-1])
    full = lambda x: np.broadcast_to(x, shape + x.shape[3:]) \
        if x.ndim >= 3 else np.broadcast_to(x, shape)
    return {"context": context.astype(np.float32),
            "metrics": np.broadcast_to(metrics, shape + (5,)).astype(
                np.float32),
            "metrics_valid": full(valid), "a_raw": full(a_raw).astype(
                np.float32), "z_raw": full(z_raw).astype(np.float32),
            "r": full(r).astype(np.float32),
            "adj": np.broadcast_to(d["sw_adj"][g][None],
                                   shape + (mask.shape[-1],)),
            "mask": full(mask[None]), "is_summary": full(
                d["sw_summ"][g][None])}, cand


def sample_decisions(plan, sample: List[int], per_tenant: int,
                     seed: int) -> List[tuple]:
    """(tenant, step) of ``per_tenant`` first-run decisions per sampled
    tenant, drawn from the seed."""
    rng = np.random.default_rng(harness.seeds(seed, 3)[2])
    tab = np.asarray(plan.dev["decide_tab"])
    out = []
    for j in sample:
        steps = np.flatnonzero(tab[:, j])
        pick = rng.choice(steps, min(per_tenant, len(steps)), replace=False)
        out += [(j, int(t)) for t in sorted(pick)]
    return out


def decision_numbers(plan, made: Dict, ys, observed, points,
                     operands=None) -> Tuple[Dict, Dict]:
    """Pick gaps of the program's first-run decisions against the reference
    sweep with the weights the benchmark made and loaded (``made``, by
    class); with ``operands`` set, the control's pick, a forward with the
    same weights and its products' operands rounded to that type, stands
    in for the program's.  Returns the number compared, the mean gap over
    the decisions, and the worst gap, for the record: a sound program's
    worst gap is a near tie met by its bfloat16 products, and it swings
    from seed to seed as widely as the control's does."""
    ys = {k: np.asarray(v) for k, v in ys.items()}
    target = np.asarray(plan.dev["target"])
    gaps = []
    for j, t in points:
        p = made[j % len(made)]["ref"]
        n = int(np.asarray(plan.dev["n_stage_f"])[t % plan.static.c_max, j])
        pm = observed[j][t][:n].sum(axis=0) / np.float32(n)
        graphs, cand = decision_graphs(plan, j, t, ys, pm)
        n_c, k_pad = graphs["mask"].shape[:2]
        flat = {k: np.ascontiguousarray(v).reshape((n_c * k_pad,)
                                                   + v.shape[2:])
                for k, v in graphs.items()}
        el = float(ys["clock"][t, j])
        tot = el + enel_ref.graph_totals(p, flat).reshape(n_c, k_pad).sum(1)
        if operands is None:
            chosen = float(ys["s_next"][t, j])
        else:
            low = el + enel_ref.graph_totals(p, flat, operands).reshape(
                n_c, k_pad).sum(1)
            chosen = enel_ref.pick(list(cand), list(low), target[j])
        gaps.append(enel_ref.pick_gap(chosen, list(cand), list(tot),
                                      float(target[j])))
    return ({"pick_gap_mean": float(np.mean(gaps))},
            {"pick_gap_max": float(max(gaps))})
