"""Micro-benchmarks: Pallas kernels (interpret mode — correctness-path
timings, regression tracking only) and per-arch smoke train steps."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from repro.compile_cache import enable_compile_cache


def _time(fn, *args, repeats=3) -> float:
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(repeats):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / repeats * 1e6    # us


def kernel_benches() -> List[Dict]:
    from repro.kernels.flash_attention.ops import mha
    from repro.kernels.flash_decode.ops import decode_attn
    from repro.kernels.mlstm_chunk.ops import mlstm
    rng = np.random.RandomState(0)
    rows = []
    q = jnp.asarray(rng.randn(1, 256, 4, 64), jnp.float32)
    kv = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
    rows.append({"name": "kernel_flash_attention_256",
                 "us": _time(lambda: mha(q, kv, kv, block_q=128))})
    qd = jnp.asarray(rng.randn(2, 1, 4, 64), jnp.float32)
    ck = jnp.asarray(rng.randn(2, 512, 2, 64), jnp.float32)
    rows.append({"name": "kernel_flash_decode_512",
                 "us": _time(lambda: decode_attn(qd, ck, ck, jnp.int32(400)))})
    qm = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
    g = jnp.asarray(rng.randn(1, 256, 2), jnp.float32)
    rows.append({"name": "kernel_mlstm_chunk_256",
                 "us": _time(lambda: mlstm(qm, qm, qm, g, g + 2, chunk=64))})
    return rows


def train_step_benches(archs=("qwen3-0.6b", "olmoe-1b-7b", "xlstm-350m",
                              "jamba-v0.1-52b")) -> List[Dict]:
    from repro.configs import get_config, smoke_config
    from repro.train.optimizer import AdamWConfig
    from repro.train.train import init_train_state, make_train_step
    rows = []
    key = jax.random.PRNGKey(0)
    for arch in archs:
        cfg = smoke_config(get_config(arch))
        opt = AdamWConfig()
        state = init_train_state(key, cfg, opt)
        step = jax.jit(make_train_step(cfg, opt))
        batch = {"tokens": jax.random.randint(key, (2, 32), 0,
                                              cfg.raw_vocab_size),
                 "targets": jax.random.randint(key, (2, 32), 0,
                                               cfg.raw_vocab_size)}
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((2, cfg.enc_frames, cfg.d_model))
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros((2, cfg.n_patches, cfg.d_model))

        def run(state=state, batch=batch, step=step):
            s, m = step(state, batch)
            return m["loss"]

        rows.append({"name": f"smoke_train_step_{arch}", "us": _time(run)})
    return rows


def main():
    enable_compile_cache()
    for r in kernel_benches() + train_step_benches():
        print(f"{r['name']},{r['us']:.0f},interpret_or_smoke")
    return True


if __name__ == "__main__":
    main()
