"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: each case lowers a jitted program at its real shapes (from
``jax.ShapeDtypeStruct``) and compiles it with the TPU compiler for one chip
of a ``v5e:2x2`` topology that is described, not attached.  That is where
the TPU compiler refuses a primitive or a layout, or a program outgrows the
chip's memory, which a CPU run cannot show.

The topology is described inside a module-scoped fixture (never at import),
and every case skips from there when it cannot be described.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.core import model as enel_model
from repro.core.graph import (CAND_LADDER, COMP_LADDER, CTX_DIM, EDGE_LADDER,
                              LEVEL_LADDER, MAX_NODES, N_METRICS)

FLEET = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)
    return make


def _param_specs(sharding, lead=()):
    shapes = jax.eval_shape(enel_model.init_enel, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype,
                                       sharding=sharding), shapes)


@pytest.mark.parametrize("direction", ["loss", "adam_step"])
@pytest.mark.parametrize("n", [8, 16])
def test_resident_fit_compiles(one_chip, direction, n):
    """The resident fit over a full history ring of ``n``-slot graphs: the
    loss alone, and the scanned guarded Adam run that differentiates it."""
    from repro.core.graph import empty_graph, stack_graphs
    from repro.core.training import _adam_run_resident, enel_loss
    from repro.dataflow.runner import HISTORY_WINDOW
    s = _spec(one_chip)
    batch = {k: s((HISTORY_WINDOW,) + v.shape[1:], v.dtype)
             for k, v in stack_graphs([empty_graph(n)]).items()}
    weights = s((HISTORY_WINDOW,))
    params = _param_specs(one_chip)
    if direction == "loss":
        compiled = jax.jit(lambda p, b, w: enel_loss(p, b, w)[0]).lower(
            params, batch, weights).compile()
    else:
        opt = (params, params, s((), jnp.int32))
        compiled = _adam_run_resident.lower(
            params, opt, batch, weights, s((2,), jnp.uint32), s(()), s(()),
            32).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


def test_fleet_sweep_compiles_at_top_rung(one_chip):
    """service._fleet_jit with every bucket ladder at its top rung."""
    from repro.core.service import JOB_LADDER, _fleet_jit
    s = _spec(one_chip)
    j, c, k = JOB_LADDER[-1], CAND_LADDER[-1], COMP_LADDER[-1]
    n, e, levels = MAX_NODES, EDGE_LADDER[-1], LEVEL_LADDER[-1]
    base = {"context": s((j, k, n, CTX_DIM)),
            "metrics": s((j, k, n, N_METRICS)),
            "metrics_valid": s((j, k, n), jnp.bool_),
            "a_raw": s((j, k, n)), "z_raw": s((j, k, n)), "r": s((j, k, n)),
            "adj": s((j, k, n, n), jnp.bool_),
            "mask": s((j, k, n), jnp.bool_),
            "is_summary": s((j, k, n), jnp.bool_)}
    deltas = {"a_raw": s((j, c, k, n)), "z_raw": s((j, c, k, n)),
              "r": s((j, c, k, n)),
              "metrics_valid": s((j, c, k, n), jnp.bool_),
              "h_context": s((j, c, k, CTX_DIM)),
              "h_metrics": s((j, c, k, N_METRICS))}
    compiled = _fleet_jit.lower(
        _param_specs(one_chip, (j,)), base, s((j, k, n)), deltas,
        s((j, k, e), jnp.int32), s((j, k, e), jnp.int32),
        s((j, k, e), jnp.bool_), s((j, c)), s((j, c), jnp.bool_),
        s((j,)), s((j,)), levels).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


def test_sim_step_kernel_compiles_at_fleet_1024(one_chip):
    """sim.engine._step_kernel for a 1024-tenant fleet of the four jobs."""
    from repro.dataflow.workloads import JOBS
    from repro.sim.engine import _NF, BatchedClusterSim, _step_kernel
    from repro.sim.tables import W_MAX
    sim = BatchedClusterSim()
    for i, key in enumerate(("lr", "mpc", "kmeans", "gbt")):
        sim.register(JOBS[key], seed=i)
    const = sim.fused_sim_constants()
    s = _spec(one_chip)
    like = lambda a, lead=(): s(lead + a.shape, a.dtype)
    per_job = lambda a: like(a[0], (FLEET,))      # 4 slots -> 1024 slots
    compiled = _step_kernel.lower(
        s((const["t_max"], FLEET, _NF)), s((FLEET, 8)), const["s_max"],
        s((FLEET, W_MAX)), per_job(const["burst"]), per_job(const["preempt"]),
        per_job(const["iscale2"]), like(const["mem_tab"]),
        like(const["shuf_tab"])).compile()
    assert compiled.memory_analysis() is not None
