"""Faults planted under the timed path, to see ``correct`` come out false.

Each maker wraps a function of the program and returns the broken one: a
fit that returns its state unchanged, a fine-tune alone that does, half of
the batch left out of the fit (the mean taken over the rest), a decision
altered where it is produced (the largest candidate picked).  The cells
run on one chip, so no exchange between chips exists to leave out.
``plant(cell_kind, name)`` puts one in place for the rest of the process;
the tests use ``target(...)`` with ``monkeypatch`` instead.
"""
from __future__ import annotations

import importlib

import jax.numpy as jnp

TUNE_STEPS_MAX = 64     # fine-tunes run 32 steps, scratch retrains 128


def _fit_unchanged(impl):
    def fit(params, opt, batch, weights, key, lr, dropout_p, steps, *rest):
        return params, opt, jnp.float32(1.0), jnp.int32(0)
    return fit


def _tune_unchanged(impl):
    """Fine-tunes (the shorter fits) return their state unchanged; scratch
    retrains run as they should."""
    def fit(params, opt, batch, weights, key, lr, dropout_p, steps, *rest):
        if steps < TUNE_STEPS_MAX:
            return params, opt, jnp.float32(1.0), jnp.int32(0)
        return impl(params, opt, batch, weights, key, lr, dropout_p, steps,
                    *rest)
    return fit


def _fit_half_batch(impl):
    def fit(params, opt, batch, weights, *rest):
        half = (jnp.arange(weights.shape[-1]) % 2 == 0).astype(weights.dtype)
        return impl(params, opt, batch, weights * half, *rest)
    return fit


def _largest_candidate(impl):
    def sweep(p, b, oh, d, ed, es, ev, cd, cv, el, tg, levels):
        idx, totals, per, ok = impl(p, b, oh, d, ed, es, ev, cd, cv, el, tg,
                                    levels)
        return jnp.sum(cv).astype(idx.dtype) - 1, totals, per, ok
    return sweep


FAULTS = {
    "fused_campaign": {
        "state_unchanged": ("repro.core.campaign_kernel",
                            "_adam_run_resident_impl", _fit_unchanged),
        "tune_unchanged": ("repro.core.campaign_kernel",
                           "_adam_run_resident_impl", _tune_unchanged),
        "half_batch": ("repro.core.campaign_kernel",
                       "_adam_run_resident_impl", _fit_half_batch),
        "answer_altered": ("repro.core.campaign_kernel", "sweep_eval_one",
                           _largest_candidate),
    },
    "live_lockstep": {
        "state_unchanged": ("repro.core.training", "_adam_run_resident",
                            _fit_unchanged),
        "tune_unchanged": ("repro.core.training", "_adam_run_resident",
                           _tune_unchanged),
        "half_batch": ("repro.core.training", "_adam_run_resident",
                       _fit_half_batch),
        "answer_altered": ("repro.core.service", "sweep_eval_one",
                           _largest_candidate),
    },
}


def target(driver: str, name: str):
    """(module, attribute, broken function) of fault ``name`` under the
    traffic driver ``driver``."""
    module, attr, make = FAULTS[driver][name]
    mod = importlib.import_module(module)
    return mod, attr, make(getattr(mod, attr))


def plant(driver: str, name: str) -> None:
    mod, attr, broken = target(driver, name)
    setattr(mod, attr, broken)
