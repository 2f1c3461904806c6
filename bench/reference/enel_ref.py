"""Plain reference of Enel's graph-propagation model, loss, fit and pick.

Written from the paper (Scheinert et al., arXiv:2108.12211, eqs. 3-7) and
the parameter layout the controller serves; it imports nothing of the
controller.  One dense graph at a time, float32 throughout with every
matrix product at ``highest`` precision.  ``operands=CONTROL`` gives the
benchmark's control: the same reference with the operands of every matrix
product rounded to float8 (e4m3), one precision step below the bfloat16
operands the configuration states for the TPU's default single-pass
products; sums and everything else stay float32.

A graph is a dict of (N, ...) arrays: ``context`` (N, 24), ``metrics``
(N, 5), ``metrics_valid``, ``a_raw``, ``z_raw``, ``r``, ``mask``,
``is_summary`` (N,) and ``adj`` (N, N) with ``adj[i, j]`` an edge j -> i.
Training rows add ``runtime``, ``runtime_valid``, ``overhead``,
``overhead_valid``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIDDEN, EDGE_DIM, CTX_DIM, N_METRICS = 32, 16, 24, 5
X_DIM = 3 + CTX_DIM + 3
HUBER_DELTA = 10.0
CONTROL = "float8_e4m3fn"


# ------------------------------------------------------------------ params
def init_params(seed: int) -> Dict:
    """The controller's documented initialisation from ``PRNGKey(seed)``:
    five subkeys (f1..f4, attention), each MLP layer a normal(0, 1/fan_in)
    weight from its own split and a zero bias."""
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(seed), 5)

    def mlp(key, dims):
        keys = jax.random.split(key, len(dims) - 1)
        return [{"w": jax.random.normal(k, (i, o), jnp.float32)
                 / math.sqrt(i), "b": jnp.zeros(o, jnp.float32)}
                for k, i, o in zip(keys, dims[:-1], dims[1:])]

    return {
        "f1": mlp(k1, [CTX_DIM + N_METRICS + 7, HIDDEN, 1]),
        "f2": mlp(k2, [CTX_DIM + N_METRICS + 4, HIDDEN, 1]),
        "f3": mlp(k3, [2 * X_DIM, HIDDEN, EDGE_DIM]),
        "f4": mlp(k4, [EDGE_DIM + N_METRICS, HIDDEN, N_METRICS]),
        "attn_a": jax.random.normal(k5, (EDGE_DIM,), jnp.float32) / 4.0,
    }


def _operand(x, operands):
    """``x`` as a matrix product's operand: as it is, or rounded to the
    ``operands`` type (saturating at its largest finite value)."""
    if operands is None:
        return x
    dt = jnp.dtype(operands)
    big = float(jnp.finfo(dt).max)
    return jnp.clip(x, -big, big).astype(dt).astype(x.dtype)


def _mm(a, b, operands):
    return _operand(a, operands) @ _operand(b, operands)


def _leaky(x):
    return jnp.where(x >= 0, x, 0.1 * x)


def _two_layer(layers, x, operands=None):
    h = _leaky(_mm(x, layers[0]["w"], operands) + layers[0]["b"])
    return _mm(h, layers[1]["w"], operands) + layers[1]["b"]


def _svec(s):
    s = jnp.maximum(s, 1e-6)
    return jnp.stack([1.0 - 1.0 / s, jnp.log(s), s], axis=-1)


# ----------------------------------------------------------------- forward
def forward(p: Dict, g: Dict, operands=None) -> Dict:
    """Eqs. 3-7 on one padded graph; propagation and the critical-path
    accumulation run N rounds, past the fixed point of any N-node DAG."""
    n = g["mask"].shape[0]
    dt = jnp.float32
    mask, summ = g["mask"], g["is_summary"]
    adj = g["adj"] & mask[:, None] & mask[None, :]
    a_vec, z_vec = _svec(g["a_raw"].astype(dt)), _svec(g["z_raw"].astype(dt))
    x = jnp.concatenate([a_vec, g["context"].astype(dt), z_vec], axis=-1)

    # eq. 6: attention over each node's predecessors
    pair = jnp.concatenate([jnp.broadcast_to(x[:, None], (n, n, X_DIM)),
                            jnp.broadcast_to(x[None, :], (n, n, X_DIM))], -1)
    h3 = _two_layer(p["f3"], pair, operands)              # (N, N, EDGE)
    logit = _mm(_leaky(h3), p["attn_a"], operands)
    logit = jnp.where(adj, logit, -jnp.inf)
    lmax = jnp.max(logit, axis=1, keepdims=True)
    w = jnp.where(adj, jnp.exp(logit - jnp.where(adj.any(1, keepdims=True),
                                                 lmax, 0.0)), 0.0)
    den = w.sum(axis=1, keepdims=True)
    e = w / jnp.where(den > 0, den, 1.0)

    # eq. 7: observed metrics fixed, the rest propagated level by level
    valid = g["metrics_valid"][:, None]
    m_obs = g["metrics"].astype(dt)
    w4, b4 = p["f4"][0]["w"], p["f4"][0]["b"]
    m_cur = m_obs
    for _ in range(n):
        mj = jnp.where(valid, m_obs, m_cur)
        f4_in = jnp.concatenate(
            [h3, jnp.broadcast_to(mj[None], (n, n, N_METRICS))], -1)
        msg = _mm(_leaky(_mm(f4_in, w4, operands) + b4), p["f4"][1]["w"],
                  operands) + p["f4"][1]["b"]
        m_cur = jnp.where(valid, m_obs, jnp.einsum(
            "ij,ijm->im", _operand(e, operands), _operand(msg, operands)))
    m_used = jnp.where(valid, m_obs, m_cur)

    # eqs. 3-4: overhead, then runtime at the end scale-out
    ctx = g["context"].astype(dt)
    o_hat = _two_layer(p["f1"], jnp.concatenate(
        [ctx, m_used, a_vec, z_vec, g["r"].astype(dt)[:, None]], -1),
        operands)[:, 0]
    t_hat = jax.nn.softplus(_two_layer(p["f2"], jnp.concatenate(
        [ctx, m_used, z_vec, o_hat[:, None]], -1), operands)[:, 0])

    # eq. 5: critical path over real (non-summary) precedents
    real = mask & ~summ
    t_node = jnp.where(real, t_hat, 0.0)
    edge = adj & ~summ[None, :]
    tt = t_node
    for _ in range(n):
        tt = t_node + jnp.max(jnp.where(edge, tt[None, :], 0.0), axis=1)
    return {"overhead": o_hat, "runtime": t_hat, "metrics": m_cur,
            "total": jnp.max(jnp.where(real, tt, 0.0))}


@functools.partial(jax.jit, static_argnums=(0,))
def _totals_many(operands, p, g):
    fwd = functools.partial(forward, operands=operands)
    return jax.vmap(fwd, in_axes=(None, 0))(p, g)["total"]


def graph_totals(params: Dict, graphs: Dict, operands=None) -> np.ndarray:
    """Predicted total runtime of each graph of a (B, N, ...) stack."""
    g = {k: jnp.asarray(v) for k, v in graphs.items()}
    with jax.default_matmul_precision("highest"):
        out = _totals_many(operands, params, g)
    return np.asarray(out, np.float64)


# -------------------------------------------------------------------- pick
def pick(cands: Sequence[float], totals: Sequence[float],
         target: float) -> float:
    """Smallest candidate predicted to meet the target, else the one with
    the least predicted runtime (first of equals)."""
    ok = [c for c, t in zip(cands, totals) if t <= target]
    if ok:
        return min(ok)
    return cands[int(np.argmin(totals))]


def pick_gap(chosen: float, cands: Sequence[float], totals: Sequence[float],
             target: float) -> float:
    """Least error in the reference totals, as a share of the target, for
    which the pick rule could return ``chosen``: 0 where the reference
    picks it too.  Either ``chosen`` meets the target and no smaller
    candidate does, or none meets it and ``chosen`` is among the least."""
    t = dict(zip(cands, totals))
    s = t[chosen]
    below = [target - t[c] for c in cands if c < chosen]
    meets = max([s - target] + below + [0.0])
    misses = max([target - v for v in totals]
                 + [(s - min(totals)) / 2.0, 0.0])
    return float(min(meets, misses) / target)


# --------------------------------------------------------------------- fit
def _huber(err):
    a = jnp.abs(err)
    return jnp.where(a <= HUBER_DELTA, 0.5 * err * err,
                     HUBER_DELTA * (a - 0.5 * HUBER_DELTA))


def loss(p: Dict, batch: Dict, weights, operands=None) -> jax.Array:
    """Huber runtime + Huber overhead + half the squared metric error, each
    a weighted mean over the rows' real targets."""
    fwd = functools.partial(forward, operands=operands)
    out = jax.vmap(fwd, in_axes=(None, 0))(p, batch)
    mask = batch["mask"]
    w1 = weights[:, None]
    rt_m = batch["runtime_valid"] & mask & ~batch["is_summary"]
    ov_m = batch["overhead_valid"] & mask
    m_m = (batch["metrics_valid"] & mask)[..., None]

    def wmean(err, m, w):
        return jnp.sum(err * m * w) / jnp.maximum(jnp.sum(m * w), 1.0)

    l_rt = wmean(_huber(jnp.where(rt_m, out["runtime"] - batch["runtime"],
                                  0.0)), rt_m, w1)
    l_ov = wmean(_huber(jnp.where(ov_m, out["overhead"] - batch["overhead"],
                                  0.0)), ov_m, w1)
    l_m = wmean(jnp.square(jnp.where(m_m, out["metrics"] - batch["metrics"],
                                     0.0)), m_m, weights[:, None, None])
    return l_rt + l_ov + 0.5 * l_m


def _adam_step(batch, weights, lr, dropout_p, operands, carry, _):
    p, mu, nu, t, k = carry
    k, sub = jax.random.split(k)
    drop = (jax.random.uniform(sub, batch["metrics_valid"].shape)
            < dropout_p) & ~batch["is_summary"]
    b = dict(batch, metrics_valid=batch["metrics_valid"] & ~drop)
    lval, g = jax.value_and_grad(loss)(p, b, weights, operands)
    ok = jnp.isfinite(lval)
    for leaf in jax.tree_util.tree_leaves(g):
        ok = ok & jnp.all(jnp.isfinite(leaf))
    t1 = t + 1
    mu1 = jax.tree_util.tree_map(lambda m, gg: 0.9 * m + 0.1 * gg, mu, g)
    nu1 = jax.tree_util.tree_map(
        lambda v, gg: 0.999 * v + 0.001 * gg * gg, nu, g)
    c1 = 1 - 0.9 ** t1.astype(jnp.float32)
    c2 = 1 - 0.999 ** t1.astype(jnp.float32)
    p1 = jax.tree_util.tree_map(
        lambda x, m, v: x - lr * (m / c1) / (jnp.sqrt(v / c2) + 1e-8),
        p, mu1, nu1)
    keep = lambda new, old: jax.tree_util.tree_map(
        lambda a, b: jnp.where(ok, a, b), new, old)
    return (keep(p1, p), keep(mu1, mu), keep(nu1, nu),
            jnp.where(ok, t1, t), k), (lval, g)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _adam_run(steps, operands, p, mu, nu, t, key, batch, weights, lr,
              dropout_p):
    step = functools.partial(_adam_step, batch, weights, lr, dropout_p,
                             operands)
    (p, mu, nu, t, _), (losses, grads) = jax.lax.scan(
        step, (p, mu, nu, t, key), None, length=steps)
    first = jax.tree_util.tree_map(lambda a: a[0], grads)
    return p, (mu, nu, t), losses[-1], first


def adam_fit(params: Dict, opt, batch: Dict, weights, key, lr: float,
             dropout_p: float, steps: int, operands=None):
    """``steps`` Adam steps (0.9, 0.999, eps 1e-8); each draws a fresh
    mask that hides non-summary metrics with probability ``dropout_p``
    from ``key`` split once per step; a step with a non-finite loss or
    gradient leaves the state as it was.  Returns host copies of (params,
    opt, last loss, first step's gradient)."""
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), t)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        out = _adam_run(int(steps), operands, f32(params), f32(opt[0]),
                        f32(opt[1]), jnp.asarray(opt[2], jnp.int32),
                        jnp.asarray(key), batch,
                        jnp.asarray(weights, jnp.float32),
                        jnp.asarray(lr, jnp.float32),
                        jnp.asarray(dropout_p, jnp.float32))
    return jax.tree_util.tree_map(np.asarray, out)


def leaf_change_gaps(p0: Dict, p_prog: Dict, p_ref: Dict,
                     first_grad_ref: Dict) -> np.ndarray:
    """Per leaf, the gap between the program's and the reference's
    parameter change norms, against the larger of that leaf's reference
    change and the median leaf's.  Leaves whose first reference gradient
    is under a thousandth of the median leaf's move by round-off alone and
    are left out."""
    leaves = lambda t: [np.asarray(x, np.float64).ravel()
                        for x in jax.tree_util.tree_leaves(t)]
    g = [np.linalg.norm(x) for x in leaves(first_grad_ref)]
    g_med = float(np.median(g))
    d_prog = [np.linalg.norm(b - a) for a, b in zip(leaves(p0),
                                                    leaves(p_prog))]
    d_ref = [np.linalg.norm(b - a) for a, b in zip(leaves(p0),
                                                   leaves(p_ref))]
    kept = [i for i in range(len(g)) if g[i] >= 1e-3 * g_med]
    med = float(np.median([d_ref[i] for i in kept]))
    return np.array([abs(d_prog[i] - d_ref[i]) / max(d_ref[i], med, 1e-30)
                     for i in kept])
