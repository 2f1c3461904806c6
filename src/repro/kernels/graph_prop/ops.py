"""Jit-friendly wrapper: Enel param pytree + bool masks -> fused kernel.

Handles batch padding to the graph-block size and dtype/bias-layout
massaging.  On a TPU both kernels compile through Mosaic; on the CPU backend,
which cannot lower TPU Pallas, they run in interpret mode (same semantics —
the test suite's route).  Any other backend raises instead of silently
interpreting.

The wrapped op carries a ``jax.custom_vjp``: the backward pass is a second
Pallas kernel (:func:`repro.kernels.graph_prop.kernel.graph_prop_bwd_kernel`)
that recomputes the edge hiddens in VMEM and propagates cotangents back
through the level-synchronous loop, so training (``enel_loss`` /
``forward_stacked(use_kernel=True)``) can differentiate straight through the
fused path instead of being pinned to the inline ``vmap(forward)`` route.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.graph_prop.kernel import (graph_prop_bwd_kernel,
                                             graph_prop_kernel)


def _row(v: jax.Array) -> jax.Array:
    return jnp.asarray(v, jnp.float32)[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _graph_prop_core(levels, block_g, interpret, x, adj, m_obs, valid,
                     w31, b31, w32, b32, attn, w41, b41, w42, b42):
    """Differentiable core over already-padded float32 arrays.

    ``adj``/``valid`` are 0/1 float masks at this level so the custom VJP can
    hand back ordinary (zero) cotangents for them.
    """
    return graph_prop_kernel(x, adj, m_obs, valid, w31, b31, w32, b32, attn,
                             w41, b41, w42, b42, levels=levels,
                             block_g=block_g, interpret=interpret)


def _core_fwd(levels, block_g, interpret, *args):
    out = _graph_prop_core(levels, block_g, interpret, *args)
    return out, args


def _core_bwd(levels, block_g, interpret, res, cots):
    (x, adj, m_obs, valid, w31, b31, w32, b32, attn, w41, b41, w42, b42) = res
    g_e, g_mhat = cots
    (gx, gmo, gw31, gb31, gw32, gb32, ga, gw41, gb41, gw42, gb42) = \
        graph_prop_bwd_kernel(x, adj, m_obs, valid, w31, b31, w32, b32, attn,
                              w41, b41, w42, b42, g_e, g_mhat, levels=levels,
                              block_g=block_g, interpret=interpret)
    return (gx, jnp.zeros_like(adj), gmo, jnp.zeros_like(valid),
            gw31, gb31, gw32, gb32, ga, gw41, gb41, gw42, gb42)


_graph_prop_core.defvjp(_core_fwd, _core_bwd)


def graph_prop(params: Dict, x: jax.Array, adj: jax.Array, m_obs: jax.Array,
               valid: jax.Array, *, levels: int = 8, block_g: int = 8,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """eqs. 6-7 for a stacked batch of padded graphs.

    params: the Enel pytree (uses "f3", "f4", "attn_a"); x: (B,N,X_DIM);
    adj: (B,N,N) bool (already mask-ANDed); m_obs: (B,N,M); valid: (B,N)
    bool.  Returns (e (B,N,N) f32, m_hat (B,N,M) f32).  Differentiable in
    ``params``, ``x`` and ``m_obs`` via the backward Pallas kernel.
    """
    if interpret is None:
        backend = jax.default_backend()
        if backend not in ("cpu", "tpu"):
            raise NotImplementedError(
                f"graph_prop has no lowering for the {backend!r} backend")
        interpret = backend == "cpu"
    b = x.shape[0]
    gb = min(block_g, b)
    pad = (-b) % gb
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        adj = jnp.concatenate(
            [adj, jnp.zeros((pad,) + adj.shape[1:], adj.dtype)])
        m_obs = jnp.concatenate(
            [m_obs, jnp.zeros((pad,) + m_obs.shape[1:], m_obs.dtype)])
        valid = jnp.concatenate(
            [valid, jnp.zeros((pad,) + valid.shape[1:], valid.dtype)])
    f3, f4 = params["f3"], params["f4"]
    e, m_hat = _graph_prop_core(
        levels, gb, interpret,
        jnp.asarray(x, jnp.float32),
        jnp.asarray(adj, jnp.float32),
        jnp.asarray(m_obs, jnp.float32),
        jnp.asarray(valid, jnp.float32),
        jnp.asarray(f3[0]["w"], jnp.float32), _row(f3[0]["b"]),
        jnp.asarray(f3[1]["w"], jnp.float32), _row(f3[1]["b"]),
        _row(params["attn_a"]),
        jnp.asarray(f4[0]["w"], jnp.float32), _row(f4[0]["b"]),
        jnp.asarray(f4[1]["w"], jnp.float32), _row(f4[1]["b"]))
    return e[:b], m_hat[:b]
