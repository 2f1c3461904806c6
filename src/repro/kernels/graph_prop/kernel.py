"""Fused Enel graph-propagation (eqs. 6-7) as a Pallas TPU kernel.

One kernel instance handles a block of G padded component graphs: the dense
N x N f3 edge MLP, the predecessor-masked softmax and all ``levels`` rounds
of f4 metric message passing run fused in VMEM — no HBM round-trips for the
(G, N, N, EDGE_DIM) edge activations between the stages, which is where the
XLA path spends its bandwidth.  Pair features are flattened to (G*N*N, 2*XD)
so every MLP layer is a single MXU matmul.

Grid is 1-D over graph blocks.  The forward block is ``block_g`` graphs; the
backward kernel keeps far more (pairs, HIDDEN) temporaries live, so its
block is capped at ``BWD_PAIR_ROWS`` node pairs to stay inside the 16 MiB
scoped VMEM of a v5e core (``tests/test_tpu_compile.py`` compiles both for a
described v5e at the sweep's B=1152, N in {8, 16}).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BWD_PAIR_ROWS = 512    # node pairs (graphs x N x N) per backward block


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    # f32 contract precision: Mosaic's default feeds f32 operands to the MXU
    # as bf16, and the backward pass amplifies that rounding to ~20% of the
    # reference gradient's scale on a v5e
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _kernel(x_ref, adj_ref, m_ref, valid_ref,
            w31_ref, b31_ref, w32_ref, b32_ref, attn_ref,
            w41_ref, b41_ref, w42_ref, b42_ref,
            e_ref, mh_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)                  # (G, N, XD)
    g, n, xd = x.shape
    adj = adj_ref[...].astype(jnp.float32)              # (G, N, N) 0/1
    m_obs = m_ref[...].astype(jnp.float32)              # (G, N, M)
    nm = m_obs.shape[-1]
    valid = valid_ref[...].astype(jnp.float32)[..., None]   # (G, N, 1)

    # eq.6 — f3 on all (dst i, src j) pairs, one MXU matmul per layer
    xi = jnp.broadcast_to(x[:, :, None, :], (g, n, n, xd))
    xj = jnp.broadcast_to(x[:, None, :, :], (g, n, n, xd))
    pair = jnp.concatenate([xi, xj], axis=-1).reshape(g * n * n, 2 * xd)
    h = jax.nn.leaky_relu(_dot(pair, w31_ref[...]) + b31_ref[...][0], 0.1)
    h3 = _dot(h, w32_ref[...]) + b32_ref[...][0]        # (G*N*N, E)
    logits = _dot(jax.nn.leaky_relu(h3, 0.1),
                  attn_ref[...][0][:, None])[:, 0].reshape(g, n, n)
    logits = jnp.where(adj > 0, logits, -1e30)
    mx = jnp.max(logits, axis=-1, keepdims=True)
    ex = jnp.exp(logits - mx)
    sm = ex / jnp.sum(ex, axis=-1, keepdims=True)
    has_pred = jnp.sum(adj, axis=-1, keepdims=True) > 0
    e = jnp.where(has_pred, sm, 0.0)                    # (G, N, N)
    e_ref[...] = e.astype(e_ref.dtype)

    # eq.7 — level-synchronous metric propagation, h3 stays resident.  f4's
    # first layer is split: the h3 @ W_h half is level-invariant and runs
    # once; per level only the small metric half is recomputed.
    ed = h3.shape[-1]
    w41 = w41_ref[...]
    pre_h = _dot(h3, w41[:ed]).reshape(g, n, n, -1)     # (G, N, N, HIDDEN)
    w_m = w41[ed:]                                      # (M, HIDDEN)
    b41 = b41_ref[...][0]

    def level_step(_, m_cur):
        mj = jnp.where(valid > 0, m_obs, m_cur)         # (G, N, M)
        mh = _dot(mj.reshape(g * n, nm), w_m).reshape(g, 1, n, -1)
        hh = jax.nn.leaky_relu(pre_h + mh + b41, 0.1)
        msg = (_dot(hh.reshape(g * n * n, -1), w42_ref[...])
               + b42_ref[...][0]).reshape(g, n, n, nm)
        m_prop = jnp.sum(e[..., None] * msg, axis=2)
        return jnp.where(valid > 0, m_obs, m_prop)

    m_hat = jax.lax.fori_loop(0, levels, level_step, m_obs)
    mh_ref[...] = m_hat.astype(mh_ref.dtype)


def _dleaky(z: jax.Array, slope: float = 0.1) -> jax.Array:
    """d/dz leaky_relu(z, slope) with jax.nn.leaky_relu's z == 0 convention."""
    return jnp.where(z >= 0, 1.0, slope)


def _bwd_kernel(x_ref, adj_ref, m_ref, valid_ref,
                w31_ref, b31_ref, w32_ref, b32_ref, attn_ref,
                w41_ref, b41_ref, w42_ref, b42_ref,
                ge_ref, gm_ref,
                gx_ref, gmo_ref, gw31_ref, gb31_ref, gw32_ref, gb32_ref,
                ga_ref, gw41_ref, gb41_ref, gw42_ref, gb42_ref,
                *, levels: int):
    """Reverse-mode twin of :func:`_kernel`.

    Recomputes the forward edge hiddens / softmax / level states in VMEM
    (nothing but the primal inputs is saved between fwd and bwd), then
    propagates the (e, m_hat) cotangents back through the level-synchronous
    loop and the f3/f4 MLPs.  Per-graph-block parameter gradients go to a
    per-block output slot; the wrapper sums them over the grid axis.
    """
    x = x_ref[...].astype(jnp.float32)                  # (G, N, XD)
    g, n, xd = x.shape
    adj = adj_ref[...].astype(jnp.float32)              # (G, N, N) 0/1
    m_obs = m_ref[...].astype(jnp.float32)              # (G, N, M)
    nm = m_obs.shape[-1]
    valid = valid_ref[...].astype(jnp.float32)          # (G, N, 1)
    w31, w32 = w31_ref[...], w32_ref[...]
    b31, b32 = b31_ref[...][0], b32_ref[...][0]
    a_row = attn_ref[...]                               # (1, E)
    w41, b41 = w41_ref[...], b41_ref[...][0]
    w42, b42 = w42_ref[...], b42_ref[...][0]
    hid = w31.shape[1]
    ed = w32.shape[1]

    # ---- forward recompute: f3, masked softmax, split f4 first layer
    xi = jnp.broadcast_to(x[:, :, None, :], (g, n, n, xd))
    xj = jnp.broadcast_to(x[:, None, :, :], (g, n, n, xd))
    pair = jnp.concatenate([xi, xj], axis=-1).reshape(g * n * n, 2 * xd)
    z1 = _dot(pair, w31) + b31
    h1 = jax.nn.leaky_relu(z1, 0.1)
    h3 = _dot(h1, w32) + b32                            # (G*N*N, E)
    lrel = jax.nn.leaky_relu(h3, 0.1)
    logits = _dot(lrel, a_row[0][:, None])[:, 0].reshape(g, n, n)
    logits = jnp.where(adj > 0, logits, -1e30)
    mx = jnp.max(logits, axis=-1, keepdims=True)
    ex = jnp.exp(logits - mx)
    sm = ex / jnp.sum(ex, axis=-1, keepdims=True)
    has_pred = jnp.sum(adj, axis=-1, keepdims=True) > 0
    e = jnp.where(has_pred, sm, 0.0)                    # (G, N, N)
    pre_h = _dot(h3, w41[:ed]).reshape(g, n, n, hid)
    w_m = w41[ed:]                                      # (M, HIDDEN)

    # ---- forward level loop again, stashing each level's INPUT state m^t.
    # ``levels`` is static, so the loop unrolls in Python and the stash is a
    # list of values: Mosaic has no lowering for a dynamic_update_slice /
    # dynamic_slice stash indexed by a fori_loop counter.
    def fwd_level(m_cur):
        mj = jnp.where(valid > 0, m_obs, m_cur)
        mh = _dot(mj.reshape(g * n, nm), w_m).reshape(g, 1, n, hid)
        hh = jax.nn.leaky_relu(pre_h + mh + b41, 0.1)
        msg = (_dot(hh.reshape(g * n * n, hid), w42) + b42).reshape(
            g, n, n, nm)
        m_prop = jnp.sum(e[..., None] * msg, axis=2)
        return jnp.where(valid > 0, m_obs, m_prop)

    ms = [m_obs]
    for _ in range(levels - 1):
        ms.append(fwd_level(ms[-1]))

    # ---- reverse sweep through the level loop
    def bwd_level(m_cur, carry):
        (g_m, g_mo, g_e, g_preh, g_wm, g_b41, g_w42, g_b42) = carry
        mj = jnp.where(valid > 0, m_obs, m_cur)
        mh = _dot(mj.reshape(g * n, nm), w_m).reshape(g, 1, n, hid)
        zz = pre_h + mh + b41
        hh = jax.nn.leaky_relu(zz, 0.1)
        msg = (_dot(hh.reshape(g * n * n, hid), w42) + b42).reshape(
            g, n, n, nm)
        # m_next = where(valid, m_obs, sum_j e * msg)
        g_mo = g_mo + valid * g_m
        g_prop = (1.0 - valid) * g_m                      # (G, N, M)
        g_e = g_e + jnp.sum(g_prop[:, :, None, :] * msg, axis=-1)
        g_msg = (e[..., None] * g_prop[:, :, None, :]).reshape(g * n * n, nm)
        g_w42 = g_w42 + _dot(hh.reshape(g * n * n, hid).T, g_msg)
        g_b42 = g_b42 + jnp.sum(g_msg, axis=0, keepdims=True)
        g_zz = _dot(g_msg, w42.T).reshape(g, n, n, hid) * _dleaky(zz)
        g_preh = g_preh + g_zz
        g_b41 = g_b41 + jnp.sum(g_zz.reshape(g * n * n, hid), axis=0,
                                keepdims=True)
        g_mh = jnp.sum(g_zz, axis=1).reshape(g * n, hid)  # bcast over dst i
        g_wm = g_wm + _dot(mj.reshape(g * n, nm).T, g_mh)
        g_mj = _dot(g_mh, w_m.T).reshape(g, n, nm)
        g_mo = g_mo + valid * g_mj
        return (1.0 - valid) * g_mj, g_mo, g_e, g_preh, g_wm, g_b41, \
            g_w42, g_b42

    zero = jnp.zeros
    carry = (gm_ref[...].astype(jnp.float32),
             zero((g, n, nm), jnp.float32),
             zero((g, n, n), jnp.float32),
             zero((g, n, n, hid), jnp.float32),
             zero((nm, hid), jnp.float32),
             zero((1, hid), jnp.float32),
             zero((hid, nm), jnp.float32),
             zero((1, nm), jnp.float32))
    for m_cur in reversed(ms):
        carry = bwd_level(m_cur, carry)
    (g_m, g_mo, g_e_acc, g_preh, g_wm, g_b41, g_w42, g_b42) = carry
    g_mo = g_mo + g_m                                    # m^0 == m_obs

    # ---- masked softmax + attention readout backward
    g_e = ge_ref[...].astype(jnp.float32) + g_e_acc
    g_sm = jnp.where(has_pred, g_e, 0.0)
    g_logits = sm * (g_sm - jnp.sum(sm * g_sm, axis=-1, keepdims=True))
    # kept in the (G, N, N, E) layout: Mosaic cannot flatten (G, N, N) into
    # one lane row, so the attention readout grad reduces over leading axes
    g_logits = jnp.where(adj > 0, g_logits, 0.0)[..., None]    # (G,N,N,1)
    lrel4 = lrel.reshape(g, n, n, ed)
    ga = jnp.sum(g_logits * lrel4, axis=(0, 1))                # (N, E)
    ga_ref[...] = jnp.sum(ga, axis=0, keepdims=True)[None].astype(
        ga_ref.dtype)
    g_h3 = (g_logits * a_row[0] * _dleaky(h3.reshape(g, n, n, ed))
            ).reshape(g * n * n, ed)
    g_preh_f = g_preh.reshape(g * n * n, hid)
    g_h3 = g_h3 + _dot(g_preh_f, w41[:ed].T)
    gw41_ref[...] = jnp.concatenate(
        [_dot(h3.T, g_preh_f), g_wm], axis=0)[None].astype(gw41_ref.dtype)
    gb41_ref[...] = g_b41[None].astype(gb41_ref.dtype)
    gw42_ref[...] = g_w42[None].astype(gw42_ref.dtype)
    gb42_ref[...] = g_b42[None].astype(gb42_ref.dtype)

    # ---- f3 MLP backward
    gw32_ref[...] = _dot(h1.T, g_h3)[None].astype(gw32_ref.dtype)
    gb32_ref[...] = jnp.sum(g_h3, axis=0, keepdims=True)[None].astype(
        gb32_ref.dtype)
    g_z1 = _dot(g_h3, w32.T) * _dleaky(z1)
    gw31_ref[...] = _dot(pair.T, g_z1)[None].astype(gw31_ref.dtype)
    gb31_ref[...] = jnp.sum(g_z1, axis=0, keepdims=True)[None].astype(
        gb31_ref.dtype)
    g_pair = _dot(g_z1, w31.T).reshape(g, n, n, 2 * xd)
    gx_ref[...] = (jnp.sum(g_pair[..., :xd], axis=2) +
                   jnp.sum(g_pair[..., xd:], axis=1)).astype(gx_ref.dtype)
    gmo_ref[...] = g_mo.astype(gmo_ref.dtype)


def graph_prop_bwd_kernel(x, adj, m_obs, valid, f3w1, f3b1, f3w2, f3b2,
                          attn_a, f4w1, f4b1, f4w2, f4b2, g_e, g_mhat, *,
                          levels: int = 8, block_g: int = 8,
                          interpret: bool = True):
    """VJP of :func:`graph_prop_kernel` w.r.t. (x, m_obs, params).

    Same layout contract as the forward kernel; ``g_e``/``g_mhat`` are the
    output cotangents.  Returns ``(gx, gm_obs, gw31, gb31, gw32, gb32, ga,
    gw41, gb41, gw42, gb42)`` with biases/attention as (1, dim) rows —
    parameter gradients are summed over graph blocks here, outside pallas.
    """
    b, n, xd = x.shape
    nm = m_obs.shape[-1]
    # the backward block keeps ~30 (pairs, HIDDEN) f32 temporaries live:
    # cap it at BWD_PAIR_ROWS pairs so it fits the 16 MiB scoped VMEM
    gb = min(block_g, b, max(1, BWD_PAIR_ROWS // (n * n)))
    assert b % gb == 0, (b, gb)
    nb = b // gb
    hid = f3w1.shape[1]
    ed = f3w2.shape[1]
    kernel = functools.partial(_bwd_kernel, levels=levels)
    full = lambda *dims: pl.BlockSpec(dims, lambda i: (0,) * len(dims))
    slot = lambda *dims: pl.BlockSpec((1,) + dims,
                                      lambda i: (i,) + (0,) * len(dims))
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((gb, n, xd), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, nm), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, 1), lambda i: (i, 0, 0)),
            full(2 * xd, hid), full(1, hid), full(hid, ed), full(1, ed),
            full(1, ed), full(ed + nm, hid), full(1, hid), full(hid, nm),
            full(1, nm),
            pl.BlockSpec((gb, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, nm), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gb, n, xd), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, nm), lambda i: (i, 0, 0)),
            slot(2 * xd, hid), slot(1, hid), slot(hid, ed), slot(1, ed),
            slot(1, ed), slot(ed + nm, hid), slot(1, hid), slot(hid, nm),
            slot(1, nm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, xd), jnp.float32),
            jax.ShapeDtypeStruct((b, n, nm), jnp.float32),
            jax.ShapeDtypeStruct((nb, 2 * xd, hid), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, hid), jnp.float32),
            jax.ShapeDtypeStruct((nb, hid, ed), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, ed), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, ed), jnp.float32),
            jax.ShapeDtypeStruct((nb, ed + nm, hid), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, hid), jnp.float32),
            jax.ShapeDtypeStruct((nb, hid, nm), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, nm), jnp.float32),
        ],
        interpret=interpret,
    )(x, adj, m_obs, valid[..., None], f3w1, f3b1, f3w2, f3b2, attn_a,
      f4w1, f4b1, f4w2, f4b2, g_e, g_mhat)
    gx, gmo = outs[0], outs[1]
    return (gx, gmo) + tuple(o.sum(axis=0) for o in outs[2:])


def graph_prop_kernel(x: jax.Array, adj: jax.Array, m_obs: jax.Array,
                      valid: jax.Array, f3w1, f3b1, f3w2, f3b2, attn_a,
                      f4w1, f4b1, f4w2, f4b2, *, levels: int = 8,
                      block_g: int = 8, interpret: bool = True):
    """x: (B,N,XD) f32; adj: (B,N,N) 0/1 f32; m_obs: (B,N,M); valid: (B,N)
    f32.  Biases/attention come in as (1, dim) rows.  B must be a multiple
    of ``block_g`` (ops.py pads).  Returns (e (B,N,N), m_hat (B,N,M))."""
    b, n, xd = x.shape
    nm = m_obs.shape[-1]
    gb = min(block_g, b)
    assert b % gb == 0, (b, gb)
    hid = f3w1.shape[1]
    ed = f3w2.shape[1]
    kernel = functools.partial(_kernel, levels=levels)
    full = lambda *dims: pl.BlockSpec(dims, lambda i: (0,) * len(dims))
    e, m_hat = pl.pallas_call(
        kernel,
        grid=(b // gb,),
        in_specs=[
            pl.BlockSpec((gb, n, xd), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, nm), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n), lambda i: (i, 0)),
            full(2 * xd, hid), full(1, hid), full(hid, ed), full(1, ed),
            full(1, ed), full(ed + nm, hid), full(1, hid), full(hid, nm),
            full(1, nm),
        ],
        out_specs=[
            pl.BlockSpec((gb, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, n, nm), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n, nm), jnp.float32),
        ],
        interpret=interpret,
    )(x, adj, m_obs, valid, f3w1, f3b1, f3w2, f3b2, attn_a,
      f4w1, f4b1, f4w2, f4b2)
    return e, m_hat
