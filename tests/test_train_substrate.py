"""Optimizer, loss masking, checkpointing, gradient compression, data
pipeline determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import TRAIN_4K, get_config, smoke_config
from repro.data.pipeline import DataConfig, global_batch, sample_tokens
from repro.train.checkpoint import (latest_step, prune_checkpoints,
                                    restore_checkpoint, save_checkpoint)
from repro.train.compression import (compression_ratio, dequantize,
                                     init_error_state, psum_compressed,
                                     quantize)
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state, lr_at
from repro.train.train import loss_fn

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------ optimizer
def test_adamw_minimizes_quadratic():
    opt = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=1000, clip_norm=100.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = init_opt_state(params, "float32")
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(params, grads, state, opt)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_lr_schedule_shape():
    opt = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    assert float(lr_at(opt, jnp.int32(0))) < 0.2
    np.testing.assert_allclose(float(lr_at(opt, jnp.int32(9))), 1.0, atol=0.01)
    assert abs(float(lr_at(opt, jnp.int32(100))) - 0.1) < 0.01


def test_grad_clipping_bounds_update():
    opt = AdamWConfig(lr=1e-3, clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = init_opt_state(params, "float32")
    _, _, m = adamw_update(params, {"w": jnp.full(4, 1e6)}, state, opt)
    assert float(m["grad_norm"]) > 1e5     # reported raw


def test_no_weight_decay_on_vectors():
    opt = AdamWConfig(lr=0.1, weight_decay=1.0, warmup_steps=1)
    params = {"norm": jnp.ones(4), "mat": jnp.ones((4, 4))}
    state = init_opt_state(params, "float32")
    zeros = {"norm": jnp.zeros(4), "mat": jnp.zeros((4, 4))}
    new, _, _ = adamw_update(params, zeros, state, opt)
    np.testing.assert_allclose(np.asarray(new["norm"]), 1.0)   # untouched
    assert float(jnp.max(new["mat"])) < 1.0                     # decayed


# --------------------------------------------------------------------- loss
def test_loss_masks_invalid_targets():
    cfg = smoke_config(get_config("qwen3-0.6b"))
    from repro.models import init_model
    params = init_model(KEY, cfg)
    b, s = 2, 8
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.raw_vocab_size)
    targets = jnp.where(jnp.arange(s) < 4, tokens, -1)
    loss_masked, parts = loss_fn(params, cfg, {"tokens": tokens,
                                               "targets": targets})
    assert float(parts["tokens"]) == b * 4
    assert np.isfinite(float(loss_masked))


# --------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_atomic_prune(tmp_path):
    tree = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
            "opt": {"step": jnp.int32(7)}}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 10, tree, metadata={"dp": 4})
    save_checkpoint(d, 20, tree)
    assert latest_step(d) == 20
    restored, step, meta = restore_checkpoint(d, tree, step=10)
    assert step == 10 and meta == {"dp": 4}
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.arange(6.0).reshape(2, 3))
    assert int(restored["opt"]["step"]) == 7
    save_checkpoint(d, 30, tree)
    prune_checkpoints(d, keep=2)
    assert latest_step(d) == 30
    with pytest.raises(Exception):
        restore_checkpoint(d, tree, step=10)    # pruned
    # no tmp dirs left behind
    assert not any(p.name.startswith(".tmp") for p in (tmp_path / "ckpt").iterdir())


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    d = str(tmp_path / "c2")
    save_checkpoint(d, 1, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        restore_checkpoint(d, {"w": jnp.zeros((3, 3))})


# -------------------------------------------------------------- compression
def test_quantization_error_bound():
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(1000) * 5)
    scale = jnp.max(jnp.abs(g)) / 127.0
    err = g - dequantize(quantize(g, scale), scale)
    assert float(jnp.max(jnp.abs(err))) <= float(scale) / 2 + 1e-6


def test_error_feedback_unbiased_over_steps():
    """With EF, the accumulated compressed sum tracks the true sum."""
    rng = np.random.RandomState(1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))

    def one_step(g, e):
        f = jax.shard_map(
            lambda gg, ee: psum_compressed(gg[0], ee[0], "data"),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P(), P()), check_vma=False)
        return f(g[None], e[None])

    true_acc = np.zeros(64)
    comp_acc = np.zeros(64)
    err = jnp.zeros(64)
    for _ in range(30):
        g = jnp.asarray(rng.randn(64))
        out, err = one_step(g, err)
        comp_acc += np.asarray(out)
        true_acc += np.asarray(g)
    # relative error of the accumulated sum shrinks with EF
    rel = np.abs(comp_acc - true_acc).max() / (np.abs(true_acc).max() + 1e-9)
    assert rel < 0.05, rel


def test_compression_ratio_near_4x():
    params = {"a": jnp.zeros((128, 128)), "b": jnp.zeros((512,))}
    assert 3.5 < compression_ratio(params) < 4.0


# ---------------------------------------------------------------- pipeline
def test_pipeline_deterministic_across_dp_resharding():
    dcfg = DataConfig(seed=7)
    mcfg = smoke_config(get_config("qwen3-0.6b"))
    full = global_batch(dcfg, mcfg, TRAIN_4K, step=3, dp_rank=0, dp_size=1,
                        seq_len=64)
    shards = [global_batch(dcfg, mcfg, TRAIN_4K, step=3, dp_rank=r,
                           dp_size=4, seq_len=64) for r in range(4)]
    np.testing.assert_array_equal(
        full["tokens"], np.concatenate([s["tokens"] for s in shards]))


def test_pipeline_targets_shifted():
    dcfg = DataConfig(seed=0)
    mcfg = smoke_config(get_config("qwen3-0.6b"))
    seq = sample_tokens(dcfg, mcfg, step=0, sample=0, seq_len=32)
    b = global_batch(dcfg, mcfg, TRAIN_4K, step=0, dp_size=TRAIN_4K.global_batch,
                     seq_len=32)
    np.testing.assert_array_equal(b["tokens"][0], seq[:-1])
    np.testing.assert_array_equal(b["targets"][0], seq[1:])
    assert b["tokens"].max() < mcfg.raw_vocab_size
