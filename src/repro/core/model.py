"""Enel's graph-propagation prediction model (paper §III-D, eqs. 3-7).

Four 2-layer MLPs (f1..f4) + a GATv2-style attention vector define a spatial
GNN over padded component DAGs:

  eq.6  |e_ij| = softmax_j( a^T sigma( f3(x_i, x_j) ) ),  x = a_vec‖c‖z_vec
  eq.7  m_hat_i = sum_j |e_ij| * f4( f3(x_i,x_j), m_j )   (metric propagation)
  eq.3  o_hat_i = f1(c_i, m_i, a_vec_i, z_vec_i, r_i)     (rescale overhead)
  eq.4  t_hat_i = f2(c_i, m_i, z_vec_i, o_hat_i)          (node runtime)
  eq.5  tt_hat_i = t_hat_i + max_{j in N(i)} tt_hat_j     (critical path)

Metric propagation runs level-synchronously (fori over MAX_NODES levels) so
predictions flow to nodes whose real metrics are unobserved (future
iterations), exactly the paper's online-inference mode.  ~5k parameters —
"allows for training even using a CPU" (§IV-C).

Three engines share the math:

* ``forward`` / ``forward_batch`` — the per-graph path.
* ``forward_stacked`` — ``vmap(forward)`` over stacked (B, N, ...) arrays at
  a given propagation depth: training (``enel_loss``) differentiates
  through it, and it is the plain reference the decision engine is tested
  against.
* ``sweep_sparse_totals`` — the decision engine: eqs. 3-7 on each graph's
  real edges only, evaluated for every (candidate x component) graph of a
  sweep by :func:`repro.core.service.sweep_eval_one` (the fleet service
  and the fused campaign scan both call it).
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import CTX_DIM, MAX_NODES, N_METRICS

HIDDEN = 32
EDGE_DIM = 16
X_DIM = 3 + CTX_DIM + 3          # a_vec ‖ c ‖ z_vec
MAX_LEVELS = 8                   # longest DAG chain the propagation supports

# -------------------------------------------------------------- trace counter
# Every (re)compilation of a counted jit traces its Python body once, so a
# plain counter bumped inside the function IS a compile counter.  The fleet
# benchmark asserts a campaign-level budget against these (shape bucketing
# exists precisely to keep them bounded).
TRACE_COUNTS: Counter = Counter()


def record_trace(name: str) -> None:
    TRACE_COUNTS[name] += 1
    # mirror into the unified obs registry (same count, queryable alongside
    # the other controller metrics); TRACE_COUNTS stays the canonical API.
    from repro import obs
    if obs.enabled():
        obs.registry().counter(
            "enel_jit_traces_total", "jit retraces per instrumented fn"
        ).labels(fn=name).inc()


def trace_count(name: str) -> int:
    return TRACE_COUNTS[name]


def reset_trace_counts() -> None:
    TRACE_COUNTS.clear()


def _mlp_init(key, dims):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (i, o), jnp.float32) / jnp.sqrt(i),
             "b": jnp.zeros(o, jnp.float32)}
            for k, i, o in zip(ks, dims[:-1], dims[1:])]


def _mlp(layers, x, final_linear=True):
    for li, l in enumerate(layers):
        x = x @ l["w"] + l["b"]
        if li < len(layers) - 1 or not final_linear:
            x = jax.nn.leaky_relu(x, 0.1)
    return x


def init_enel(key) -> Dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        # eq.3: f1(c, m, a_vec, z_vec, r) -> overhead
        "f1": _mlp_init(k1, [CTX_DIM + N_METRICS + 3 + 3 + 1, HIDDEN, 1]),
        # eq.4: f2(c, m, z_vec, o_hat) -> runtime
        "f2": _mlp_init(k2, [CTX_DIM + N_METRICS + 3 + 1, HIDDEN, 1]),
        # eq.6: f3(x_i, x_j) -> edge hidden
        "f3": _mlp_init(k3, [2 * X_DIM, HIDDEN, EDGE_DIM]),
        # eq.7: f4(edge hidden, m_j) -> propagated metrics
        "f4": _mlp_init(k4, [EDGE_DIM + N_METRICS, HIDDEN, N_METRICS]),
        "attn_a": jax.random.normal(k5, (EDGE_DIM,), jnp.float32) / 4.0,
    }


def n_params(params: Dict) -> int:
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(params))


def scaleout_vec(s: jax.Array) -> jax.Array:
    s = jnp.maximum(s, 1e-6)
    return jnp.stack([1.0 - 1.0 / s, jnp.log(s), s], axis=-1)


def _prelude(g: Dict) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Shared input lift; works on single (N, ...) and stacked (B, N, ...)."""
    a_vec = scaleout_vec(g["a_raw"])
    z_vec = scaleout_vec(g["z_raw"])
    x = jnp.concatenate([a_vec, g["context"], z_vec], axis=-1)
    adj = g["adj"] & g["mask"][..., None, :] & g["mask"][..., :, None]
    return a_vec, z_vec, x, adj


def _edge_hidden(params, x):
    """f3 on all (i, j) pairs -> (N, N, EDGE_DIM); i = dst, j = src."""
    n = x.shape[0]
    xi = jnp.broadcast_to(x[:, None, :], (n, n, x.shape[-1]))
    xj = jnp.broadcast_to(x[None, :, :], (n, n, x.shape[-1]))
    return _mlp(params["f3"], jnp.concatenate([xi, xj], axis=-1))


def edge_weights(params, x, adj) -> Tuple[jax.Array, jax.Array]:
    """eq.6: masked softmax over predecessors. Returns (e (N,N), h3 (N,N,E))."""
    h3 = _edge_hidden(params, x)
    logits = jnp.einsum("ije,e->ij", jax.nn.leaky_relu(h3, 0.1),
                        params["attn_a"])
    logits = jnp.where(adj, logits, -1e30)
    has_pred = adj.any(axis=1, keepdims=True)
    e = jax.nn.softmax(logits, axis=1)
    return jnp.where(has_pred, e, 0.0), h3


def _propagate(params, x, adj, m_obs, valid,
               levels: int = MAX_LEVELS) -> Tuple[jax.Array, jax.Array]:
    """eqs. 6-7 for ONE graph: edge weights + level-synchronous metric
    propagation (observed metrics are fixed inputs; unobserved nodes adopt
    propagated estimates as they stabilize).  Returns (e, m_hat).

    f4's first layer is split so the level-invariant h3 @ W_h half runs once
    outside the loop; per level only the (N, M) @ W_m half is recomputed.
    ``levels`` may be lowered to the graph's actual DAG depth — propagation
    reaches a fixed point after `depth` rounds, so fewer rounds are exact.
    """
    e, h3 = edge_weights(params, x, adj)
    w0, b0 = params["f4"][0]["w"], params["f4"][0]["b"]
    pre_h = h3 @ w0[:EDGE_DIM]                               # (N, N, HIDDEN)
    w_m = w0[EDGE_DIM:]
    f4_tail = params["f4"][1:]

    # rematerialized under autodiff: the backward pass keeps only each
    # level's (N, M) input instead of its (N, N, HIDDEN) activations — the
    # fused campaign's in-scan fit at 1024 tenants otherwise needs ~16 GB of
    # temporaries on a 16 GiB v5e
    @jax.checkpoint
    def level_step(_, m_cur):
        mj = jnp.where(valid[:, None], m_obs, m_cur)            # (N, M)
        hidden = jax.nn.leaky_relu(pre_h + (mj @ w_m)[None, :, :] + b0, 0.1)
        msg = _mlp(f4_tail, hidden)                              # (N,N,M)
        m_prop = jnp.einsum("ij,ijm->im", e, msg)
        return jnp.where(valid[:, None], m_obs, m_prop)

    m_hat = jax.lax.fori_loop(0, levels, level_step, m_obs)
    return e, m_hat


def _readout(params, g, a_vec, z_vec, adj, e, m_hat,
             levels: int = MAX_LEVELS) -> Dict[str, jax.Array]:
    """eqs. 3-5 for ONE graph given propagated metrics and edge weights.

    ``levels`` bounds the eq.5 accumulation rounds; the longest real-edge
    chain never exceeds the propagation depth, so a depth-lowered value is
    exact (same fixed-point argument as :func:`_propagate`).
    """
    valid = g["metrics_valid"]
    m_used = jnp.where(valid[:, None], g["metrics"], m_hat)

    # eq.3 overhead
    f1_in = jnp.concatenate([g["context"], m_used, a_vec, z_vec,
                             g["r"][:, None]], axis=-1)
    o_hat = _mlp(params["f1"], f1_in)[:, 0]

    # eq.4 runtime (end scale-out only + predicted overhead)
    f2_in = jnp.concatenate([g["context"], m_used, z_vec,
                             o_hat[:, None]], axis=-1)
    t_hat = jax.nn.softplus(_mlp(params["f2"], f2_in)[:, 0])

    # eq.5 accumulated runtime over the DAG (summary nodes excluded)
    t_node = jnp.where(g["mask"] & ~g["is_summary"], t_hat, 0.0)
    real_edge = adj & ~g["is_summary"][None, :]       # drop summary precedents

    def acc_step(_, tt):
        pred_best = jnp.max(
            jnp.where(real_edge, tt[None, :], 0.0), axis=1)
        return t_node + pred_best

    tt_hat = jax.lax.fori_loop(0, levels, acc_step, t_node)
    tt_hat = jnp.where(g["mask"] & ~g["is_summary"], tt_hat, 0.0)

    return {"overhead": o_hat, "runtime": t_hat, "acc_runtime": tt_hat,
            "metrics": m_hat, "edges": e,
            "total_runtime": jnp.max(tt_hat)}


def forward(params: Dict, g: Dict,
            levels: int = MAX_LEVELS) -> Dict[str, jax.Array]:
    """Full propagation over one padded graph (dict of (N,...) arrays).

    Returns overhead/runtime/accumulated-runtime/propagated-metric predictions.
    """
    a_vec, z_vec, x, adj = _prelude(g)
    e, m_hat = _propagate(params, x, adj, g["metrics"], g["metrics_valid"],
                          levels)
    return _readout(params, g, a_vec, z_vec, adj, e, m_hat, levels)


forward_batch = jax.vmap(forward, in_axes=(None, 0))


def forward_stacked(params: Dict, batch: Dict,
                    levels: int = MAX_LEVELS) -> Dict[str, jax.Array]:
    """Batched inference over stacked (B, N, ...) graph arrays:
    ``vmap(forward)`` at ``levels`` propagation rounds."""
    if levels == MAX_LEVELS:
        return forward_batch(params, batch)
    return jax.vmap(lambda p, g: forward(p, g, levels),
                    in_axes=(None, 0))(params, batch)


def predict_total_runtime(params: Dict, graphs: Dict) -> jax.Array:
    """Total predicted runtime per component graph in a stacked batch."""
    return forward_stacked(params, graphs)["total_runtime"]


# ---------------------------------------------------- candidate sweep batch
def assemble_sweep_batch(base, h_onehot, deltas) -> Dict[str, jax.Array]:
    """Template + per-candidate deltas -> flat stacked (C*K, N, ...) batch.

    Shapes:

      base[...]           (K, N, ...)   candidate-invariant template
      h_onehot            (K, N)        H-summary slot indicator
      deltas["a_raw"|"z_raw"|"r"|"metrics_valid"]   (C, K, N)
      deltas["h_context"] (C, K, CTX)   per-candidate H-node context
      deltas["h_metrics"] (C, K, M)     per-candidate H-node metrics
    """
    c, k = deltas["a_raw"].shape[:2]
    n = base["mask"].shape[-1]
    oh = h_onehot[None, :, :, None]                         # (1, K, N, 1)
    ctx = (base["context"][None] * (1.0 - oh) +
           oh * deltas["h_context"][:, :, None, :])
    met = (base["metrics"][None] * (1.0 - oh) +
           oh * deltas["h_metrics"][:, :, None, :])
    batch = {
        "context": ctx, "metrics": met,
        "metrics_valid": deltas["metrics_valid"],
        "a_raw": deltas["a_raw"], "z_raw": deltas["z_raw"],
        "r": deltas["r"],
        "adj": jnp.broadcast_to(base["adj"][None], (c, k, n, n)),
        "mask": jnp.broadcast_to(base["mask"][None], (c, k, n)),
        "is_summary": jnp.broadcast_to(base["is_summary"][None], (c, k, n)),
    }
    return {key: v.reshape((c * k,) + v.shape[2:]) for key, v in batch.items()}


# ---------------------------------------------------- sparse-edge sweep engine
# The component DAGs are near-chains: a graph holds at most a handful of real
# edges, yet ``forward`` evaluates f3/f4 on all N x N node pairs and masks
# the rest away.  The decision engine instead gathers the few real
# (dst, src) pairs into padded (B, E) edge lists and runs eqs. 6-7 with
# segment reductions — identical math on the real edges (``forward``'s
# masked pairs contribute exact zeros), at E/N^2 of the pair work.

def sweep_sparse_totals(params: Dict, flat: Dict, edge_dst: jax.Array,
                        edge_src: jax.Array, edge_valid: jax.Array,
                        levels: int = MAX_LEVELS) -> jax.Array:
    """Total predicted runtime per graph of a flat stacked batch, sparse.

    ``flat`` holds (B, N, ...) graph arrays (``adj`` unused); ``edge_dst`` /
    ``edge_src`` / ``edge_valid`` are (B, E) padded edge lists (j -> i edges
    as (dst=i, src=j)).  Returns (B,) totals equal (up to float summation
    order) to ``forward_stacked(...)["total_runtime"]`` on the same graphs.
    """
    b, n = flat["mask"].shape
    a_vec = scaleout_vec(flat["a_raw"])
    z_vec = scaleout_vec(flat["z_raw"])
    x = jnp.concatenate([a_vec, flat["context"], z_vec], axis=-1)
    bi = jnp.arange(b)[:, None]
    # Scatter-free edge->node reduction: XLA CPU lowers segment ops to
    # serial scatters, so edge->node sums/maxes run as one-hot
    # broadcast-multiply-sums over the (small) padded edge axis instead;
    # node->edge reads stay row gathers.
    oh_dst = (edge_dst[..., None] == jnp.arange(n)) & edge_valid[..., None]
    oh_dst_f = jnp.where(oh_dst, 1.0, 0.0)               # (B, E, N)

    # eq.6 on real edges only: masked softmax over each node's predecessors
    xe = jnp.concatenate([x[bi, edge_dst], x[bi, edge_src]], axis=-1)
    h3 = _mlp(params["f3"], xe)                          # (B, E, EDGE_DIM)
    logits = jnp.einsum("bef,f->be", jax.nn.leaky_relu(h3, 0.1),
                        params["attn_a"])
    lmax = jnp.max(jnp.where(oh_dst, logits[..., None], -jnp.inf),
                   axis=1)                               # (B, N)
    lmax = jnp.where(jnp.isfinite(lmax), lmax, 0.0)      # no-pred nodes
    lm_e = jnp.take_along_axis(lmax, edge_dst, axis=1)
    w = jnp.where(edge_valid, jnp.exp(logits - lm_e), 0.0)
    den = (oh_dst_f * w[..., None]).sum(axis=1)          # (B, N)
    den_e = jnp.take_along_axis(den, edge_dst, axis=1)
    e = w / jnp.where(den_e > 0, den_e, 1.0)

    # eq.7 level-synchronous propagation via per-edge messages
    w0, b0 = params["f4"][0]["w"], params["f4"][0]["b"]
    pre_h = h3 @ w0[:EDGE_DIM]                           # (B, E, HIDDEN)
    w_m = w0[EDGE_DIM:]
    f4_tail = params["f4"][1:]
    m_obs, valid = flat["metrics"], flat["metrics_valid"]

    def level_step(_, m_cur):
        mj = jnp.where(valid[..., None], m_obs, m_cur)   # (B, N, M)
        hidden = jax.nn.leaky_relu(pre_h + mj[bi, edge_src] @ w_m + b0, 0.1)
        msg = _mlp(f4_tail, hidden)                      # (B, E, M)
        m_prop = (oh_dst_f[..., None] *
                  (e[..., None] * msg)[:, :, None, :]).sum(axis=1)
        return jnp.where(valid[..., None], m_obs, m_prop)

    m_hat = jax.lax.fori_loop(0, levels, level_step, m_obs)

    # eqs. 3-5 readout (per node; eq.5 max-over-predecessors via segment_max)
    m_used = jnp.where(valid[..., None], m_obs, m_hat)
    f1_in = jnp.concatenate([flat["context"], m_used, a_vec, z_vec,
                             flat["r"][..., None]], axis=-1)
    o_hat = _mlp(params["f1"], f1_in)[..., 0]
    f2_in = jnp.concatenate([flat["context"], m_used, z_vec,
                             o_hat[..., None]], axis=-1)
    t_hat = jax.nn.softplus(_mlp(params["f2"], f2_in)[..., 0])

    real_node = flat["mask"] & ~flat["is_summary"]
    t_node = jnp.where(real_node, t_hat, 0.0)
    oh_real = oh_dst & ~flat["is_summary"][bi, edge_src, None]

    def acc_step(_, tt):
        best = jnp.max(jnp.where(oh_real, tt[bi, edge_src, None], 0.0),
                       axis=1)                           # no-pred nodes -> 0
        return t_node + best

    tt_hat = jax.lax.fori_loop(0, levels, acc_step, t_node)
    return jnp.max(jnp.where(real_node, tt_hat, 0.0), axis=-1)


# ------------------------------------------------------------ on-device pick
def pick_candidate(candidates: jax.Array, cand_valid: jax.Array,
                   totals: jax.Array, target: jax.Array) -> jax.Array:
    """Device-side :meth:`EnelScaler._pick`: index of the smallest compliant
    candidate scale-out, else the least-violating one.  ``candidates`` must
    be ascending over the valid entries (argmin then matches the host pick's
    first-of-min tie-breaking).

    Guardrail: non-finite totals (a poisoned model) are treated as +inf so
    they can neither look compliant (NaN <= target is False anyway) nor win
    the least-violating argmin; callers still detect the condition via
    :func:`sweep_totals_ok` and route to the fallback policy."""
    totals = jnp.where(jnp.isfinite(totals), totals, jnp.inf)
    feasible = cand_valid & (totals <= target)
    idx_feasible = jnp.argmin(jnp.where(feasible, candidates, jnp.inf))
    idx_min = jnp.argmin(jnp.where(cand_valid, totals, jnp.inf))
    return jnp.where(feasible.any(), idx_feasible, idx_min)


def sweep_totals_ok(totals: jax.Array, cand_valid: jax.Array) -> jax.Array:
    """Divergence guardrail over one sweep's per-candidate totals: True iff
    every VALID candidate's predicted total is finite.  Computed on device
    and fetched alongside the pick (one transfer, no extra dispatch); a
    False row routes that request to the model-free fallback policy."""
    return jnp.all(jnp.where(cand_valid, jnp.isfinite(totals), True),
                   axis=-1)
