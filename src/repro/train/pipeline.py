"""Optional pipeline parallelism: GPipe schedule via shard_map + ppermute.

Each device on the 'stage' mesh axis owns one stage's params; microbatches
stream through the 1-D pipeline with a collective_permute per tick.  This is
the PP building block advertised in DESIGN.md §4 — the 40 baseline cells use
DP x TP; PP composes for deeper-than-HBM models (e.g., arctic at dp<16).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_stage_params(key, n_stages: int, d: int) -> Dict[str, jax.Array]:
    k1, k2 = jax.random.split(key)
    s = 1.0 / jnp.sqrt(d)
    return {"w1": jax.random.normal(k1, (n_stages, d, d)) * s,
            "w2": jax.random.normal(k2, (n_stages, d, d)) * s}


def stage_fn(params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    return x + jnp.tanh(x @ params["w1"]) @ params["w2"]


def pipelined_forward(params: Dict[str, jax.Array], x: jax.Array,
                      mesh: Mesh, axis: str = "stage") -> jax.Array:
    """x: (n_micro, b, d) microbatches; params leaves lead with n_stages.

    Returns the full pipeline output, identical to applying the stages
    sequentially (validated in tests/test_multidevice.py)."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def body(stage_params, xs):
        local = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        idx = jax.lax.axis_index(axis)
        recv0 = jnp.zeros(xs.shape[1:], xs.dtype)
        out0 = jnp.zeros_like(xs)

        def tick(t, state):
            recv, outputs = state
            mb_in = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(idx == 0, xs[mb_in], recv)
            out = stage_fn(local, inp)
            mb_out = t - (n_stages - 1)
            valid = (idx == n_stages - 1) & (mb_out >= 0) & (mb_out < n_micro)
            written = jax.lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(mb_out, 0, n_micro - 1), 0)
            outputs = jnp.where(valid, written, outputs)
            recv = jax.lax.ppermute(out, axis, perm)
            return recv, outputs

        _, outputs = jax.lax.fori_loop(0, ticks, tick, (recv0, out0))
        return jax.lax.psum(outputs, axis)   # non-last stages contribute 0

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(None, None, None)),
                       out_specs=P(None, None, None), check_vma=False)
    return fn(params, x)
