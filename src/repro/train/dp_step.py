"""Explicit data-parallel train step with int8+error-feedback gradient
all-reduce (compression.py), built on shard_map.

The GSPMD path (train.make_train_step) lets XLA generate its own reduction
collectives; this variant takes manual control of the DP axis so the grad
all-reduce payload can be quantized — the trick that matters when the DP
axis spans pods (DCI bandwidth << ICI).  Params are replicated across the
DP axis here (pure DP; compose with TP by nesting meshes).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.train.compression import init_error_state, psum_compressed_tree
from repro.train.optimizer import AdamWConfig, adamw_update
from repro.train.train import loss_fn


def make_dp_train_step(cfg: ModelConfig, opt: AdamWConfig, mesh: Mesh,
                       axis: str = "data", compress: bool = True):
    """Returns (step_fn, init_extra_state).

    step_fn(state, err_state, batch) -> (state, err_state, metrics); the
    batch's leading dim is sharded over `axis`, params/opt replicated.
    """

    def body(state, err, batch):
        params = state["params"]

        def local_loss(p):
            return loss_fn(p, cfg, batch)

        (loss, parts), grads = jax.value_and_grad(
            local_loss, has_aux=True)(params)
        if compress:
            grads, err = psum_compressed_tree(grads, err, axis)
        else:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axis), grads)
        loss = jax.lax.pmean(loss, axis)
        parts = jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis),
                                       parts)
        new_params, new_opt, om = adamw_update(params, grads, state["opt"],
                                               opt)
        return ({"params": new_params, "opt": new_opt}, err,
                {"loss": loss, **parts, **om})

    replicated = P()
    sharded = P(axis)

    def batch_spec(tree):
        return jax.tree_util.tree_map(lambda _: sharded, tree)

    def step_fn(state, err_state, batch):
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: replicated, state),
                      jax.tree_util.tree_map(lambda _: replicated, err_state),
                      batch_spec(batch)),
            out_specs=(jax.tree_util.tree_map(lambda _: replicated, state),
                       jax.tree_util.tree_map(lambda _: replicated,
                                              err_state),
                       replicated), check_vma=False)
        return fn(state, err_state, batch)

    def init_extra(params) -> Dict:
        return init_error_state(params)

    return step_fn, init_extra
