"""Model FLOPs of Enel's graph propagation, counted from graph structure.

A matrix product of (m, k) by (k, n) counts 2*m*k*n.  Only the work the
model needs on a graph's real parts counts: eq. 6 (f3 and the attention
dot) on each real edge, the edge half of f4's first layer once per edge,
one metric half plus f4's second layer and the weighting per edge and
propagation round, with as many rounds as the graph's longest predecessor
chain, and eqs. 3-4 (f1, f2) on each real non-summary node.  Padding
(masked nodes, masked edges, candidate and component ladder rungs, rounds
beyond a graph's depth) and rematerialised recomputation do not count, nor
do the element-wise ops, the critical-path max of eq. 5 and the
simulator.  A training step counts three forward passes (forward,
backward through activations, backward through weights).
"""
from __future__ import annotations

import numpy as np

HIDDEN, EDGE_DIM, CTX_DIM, N_METRICS = 32, 16, 24, 5
X_DIM = 3 + CTX_DIM + 3

PER_EDGE = 2 * (2 * X_DIM * HIDDEN + HIDDEN * EDGE_DIM) \
    + 2 * EDGE_DIM + 2 * EDGE_DIM * HIDDEN
PER_EDGE_ROUND = 2 * N_METRICS * HIDDEN + 2 * HIDDEN * N_METRICS \
    + 2 * N_METRICS
PER_NODE = 2 * ((CTX_DIM + N_METRICS + 7) * HIDDEN + HIDDEN) \
    + 2 * ((CTX_DIM + N_METRICS + 4) * HIDDEN + HIDDEN)


def depth(adj: np.ndarray, mask: np.ndarray) -> int:
    """Longest predecessor chain, in edges, of a masked DAG."""
    a = adj & mask[None, :] & mask[:, None]
    d = np.zeros(a.shape[0], np.int64)
    for _ in range(a.shape[0]):
        nd = np.where(a.any(axis=1), (a * (d[None, :] + 1)).max(axis=1), 0)
        if (nd == d).all():
            break
        d = nd
    return int(d.max())


def graph_flops(adj: np.ndarray, mask: np.ndarray,
                is_summary: np.ndarray) -> int:
    """Forward FLOPs of one graph (``adj[i, j]``: edge j -> i)."""
    a = adj & mask[None, :] & mask[:, None]
    edges = int(a.sum())
    nodes = int((mask & ~is_summary).sum())
    return edges * PER_EDGE + depth(adj, mask) * edges * PER_EDGE_ROUND \
        + nodes * PER_NODE


class _Memo:
    def __init__(self):
        self._seen = {}

    def __call__(self, adj, mask, summ) -> int:
        key = (adj.tobytes(), mask.tobytes(), summ.tobytes())
        if key not in self._seen:
            self._seen[key] = graph_flops(adj, mask, summ)
        return self._seen[key]


def fused_campaign_flops(plan) -> dict:
    """Model FLOPs of one fused campaign of ``plan``: every decision's sweep
    over the real candidates and real remaining components, and every
    per-run fit over the real ring rows its weights select, replaying the
    ring's append order from the plan's initial ring."""
    memo = _Memo()
    dev = {k: np.asarray(plan.dev[k]) for k in (
        "cls", "sw_mask0", "sw_adj", "sw_summ", "sw_is_p", "row_mask",
        "row_adj", "row_summ", "comp_valid", "decide_tab", "n_comp",
        "cand_valid", "scratch_at")}
    st = plan.static
    n_runs = plan.n_runs
    n_cand = int(dev["cand_valid"].sum())
    ring0 = plan.init["ring"]
    cap = ring0["slot_ok"].shape[1]
    sweep = fit = 0
    groups = {}
    for j in range(plan.n_jobs):
        key = (int(dev["cls"][j]), int(dev["n_comp"][j]),
               dev["decide_tab"][:, j].tobytes(),
               dev["comp_valid"][:, j].tobytes(),
               ring0["buffers"]["mask"][j].tobytes(),
               ring0["buffers"]["adj"][j].tobytes(),
               ring0["buffers"]["is_summary"][j].tobytes(),
               int(ring0["pos"][j]), int(ring0["count"][j]),
               ring0["slot_ok"][j].tobytes())
        groups.setdefault(key, []).append(j)
    for jobs in groups.values():
        j = jobs[0]
        g = int(dev["cls"][j])
        nc = int(dev["n_comp"][j])
        # ---- sweeps: one graph per real candidate and remaining component
        per_decision = {}
        for k in range(st.c_max):
            if not dev["decide_tab"][k, j]:
                continue
            if k not in per_decision:
                f = 0
                for ki in range(dev["sw_mask0"].shape[1]):
                    comp = ki + 1
                    if not k < comp < nc:
                        continue
                    mask = dev["sw_mask0"][g, ki] & (
                        ~dev["sw_is_p"][g, ki] | (comp == k + 1))
                    f += memo(dev["sw_adj"][g, ki], mask,
                              dev["sw_summ"][g, ki])
                per_decision[k] = f * n_cand
            sweep += per_decision[k] * n_runs * len(jobs)
        # ---- fits: replay the ring, count the rows each fit weighs
        mask = ring0["buffers"]["mask"][j].copy()
        adj = ring0["buffers"]["adj"][j].copy()
        summ = ring0["buffers"]["is_summary"][j].copy()
        slot_ok = ring0["slot_ok"][j].copy()
        pos, count = int(ring0["pos"][j]), int(ring0["count"][j])
        for r in range(n_runs):
            for k in range(st.c_max):
                if dev["comp_valid"][k, j]:
                    mask[pos] = dev["row_mask"][g, k]
                    adj[pos] = dev["row_adj"][g, k]
                    summ[pos] = dev["row_summ"][g, k]
                    slot_ok[pos] = True
                    pos = (pos + 1) % cap
                    count = min(count + 1, cap)
            if dev["scratch_at"][r]:
                rows = [i for i in range(cap) if i < count and slot_ok[i]]
                steps = st.scratch_steps
            else:
                rows = [(pos - nc + i) % cap for i in range(nc)]
                rows = [i for i in rows if slot_ok[i]]
                steps = st.tune_steps
            per_step = sum(memo(adj[i], mask[i], summ[i]) for i in rows)
            fit += 3 * per_step * steps * len(jobs)
    return {"sweep": int(sweep), "fit": int(fit), "total": int(sweep + fit)}
