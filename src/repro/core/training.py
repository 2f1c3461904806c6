"""Enel model training / fine-tuning (paper §IV-A, §V-B.3).

Targets: observed node runtimes, observed rescale overheads and observed
metric vectors (propagation loss).  Adam over the ~5k-parameter model; a
"retrain from scratch every 5th run, fine-tune in between" policy mirroring
the paper's protocol lives in :class:`EnelTrainer`.

Two fit routes share the loss/optimizer math:

* ``EnelTrainer.fit`` — legacy list-of-graphs API: host restack + power-of-2
  bucketing + a frozen metric-dropout copy appended to the batch.
* ``EnelTrainer.fit_resident`` — the online fast path: trains directly on the
  device-resident :class:`~repro.core.graph.TrainingCache` ring buffer (fed
  incrementally by the runner), with metric dropout sampled on-device PER
  STEP inside the scanned Adam loop (fresh mask each step, no 2x batch) and
  per-slot weights selecting the scratch window vs. the newest run.  Both
  differentiate through ``forward_stacked``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import model as enel_model
from repro.core.graph import (ComponentGraph, TrainingCache, copy_tree,
                              pow2_bucket, stack_graphs)

HUBER_DELTA = 10.0

# trainer non-finite-guard telemetry: attribute -> (family, kind, help).
# Registered in the unified obs registry behind the original attribute API.
_TRAINER_COUNTERS = {
    "nonfinite_steps": ("enel_trainer_nonfinite_steps_total", "counter",
                        "Adam steps skipped by the non-finite guard"),
    "last_skipped_steps": ("enel_trainer_last_skipped_steps", "gauge",
                           "guard-skipped steps in the most recent fit"),
    "poisoned_fits": ("enel_trainer_poisoned_fits_total", "counter",
                      "fits where every step was guard-skipped"),
}


def _huber(err: jax.Array, delta: float = HUBER_DELTA) -> jax.Array:
    a = jnp.abs(err)
    return jnp.where(a <= delta, 0.5 * err * err, delta * (a - 0.5 * delta))


def enel_loss(params: Dict, batch: Dict, weights: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Dict]:
    """Training loss over a stacked graph batch.

    ``weights`` (B,) 0/1 scales each graph's contribution (ring-buffer slots
    outside the training window).
    """
    out = enel_model.forward_stacked(params, batch)
    rt_mask = batch["runtime_valid"] & batch["mask"] & ~batch["is_summary"]
    rt_err = jnp.where(rt_mask, out["runtime"] - batch["runtime"], 0.0)

    ov_mask = batch["overhead_valid"] & batch["mask"]
    ov_err = jnp.where(ov_mask, out["overhead"] - batch["overhead"], 0.0)

    # metric propagation loss: predict observed metrics from predecessors
    m_mask = (batch["metrics_valid"] & batch["mask"])[..., None]
    m_err = jnp.where(m_mask, out["metrics"] - batch["metrics"], 0.0)

    if weights is None:
        l_rt = jnp.sum(_huber(rt_err)) / jnp.maximum(rt_mask.sum(), 1)
        l_ov = jnp.sum(_huber(ov_err)) / jnp.maximum(ov_mask.sum(), 1)
        l_m = jnp.sum(jnp.square(m_err)) / jnp.maximum(m_mask.sum(), 1)
    else:
        w1 = weights[:, None]
        l_rt = jnp.sum(_huber(rt_err) * w1) / \
            jnp.maximum(jnp.sum(rt_mask * w1), 1.0)
        l_ov = jnp.sum(_huber(ov_err) * w1) / \
            jnp.maximum(jnp.sum(ov_mask * w1), 1.0)
        w2 = weights[:, None, None]
        l_m = jnp.sum(jnp.square(m_err) * w2) / \
            jnp.maximum(jnp.sum(m_mask * w2), 1.0)

    loss = l_rt + l_ov + 0.5 * l_m
    return loss, {"runtime": l_rt, "overhead": l_ov, "metrics": l_m}


def _adam_update(params, opt, batch, lr, weights=None):
    """One guarded Adam step: a step whose loss or gradients come back
    non-finite is SKIPPED (params/opt unchanged, ``ok=False``) instead of
    writing NaN into the parameters — one poisoned batch row or a
    divergent step can no longer destroy the model."""
    (loss, parts), g = jax.value_and_grad(enel_loss, has_aux=True)(
        params, batch, weights)
    ok = jnp.isfinite(loss)
    for leaf in jax.tree_util.tree_leaves(g):
        ok = ok & jnp.all(jnp.isfinite(leaf))
    mu0, nu0, t0 = opt
    t = t0 + 1
    mu = jax.tree_util.tree_map(lambda m, gg: 0.9 * m + 0.1 * gg, mu0, g)
    nu = jax.tree_util.tree_map(lambda v, gg: 0.999 * v + 0.001 * gg * gg,
                                nu0, g)

    def upd(p, m, v):
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.999 ** t)
        return p - lr * mh / (jnp.sqrt(vh) + 1e-8)

    new_params = jax.tree_util.tree_map(upd, params, mu, nu)
    sel = lambda a, b: jax.tree_util.tree_map(
        lambda x, y: jnp.where(ok, x, y), a, b)
    return sel(new_params, params), \
        (sel(mu, mu0), sel(nu, nu0), jnp.where(ok, t, t0)), loss, ok


def _adam_run_impl(params, opt, batch, steps, lr):
    """`steps` Adam updates fused into one jit (dispatch-bound otherwise);
    also returns how many steps the non-finite guard skipped."""
    def body(carry, _):
        p, o = carry
        p, o, loss, ok = _adam_update(p, o, batch, lr)
        return (p, o), (loss, ok)

    (params, opt), (losses, oks) = jax.lax.scan(body, (params, opt), None,
                                                length=steps)
    return params, opt, losses[-1], steps - jnp.sum(oks)


# params/opt are replaced by the returned pytrees every call -> donating their
# buffers avoids a copy per fit.  Nothing may keep the old trainer.params /
# opt leaves for later use: they are deleted by the call.
_adam_run = jax.jit(_adam_run_impl, static_argnums=(3,),
                    donate_argnums=(0, 1))


def _adam_run_resident_impl(params, opt, batch, weights, key, lr, dropout_p,
                            steps):
    """Scanned Adam over a resident batch with PER-STEP metric dropout.

    Each step samples a fresh on-device mask hiding task-set metrics with
    probability ``dropout_p`` (summary nodes kept), so runtime prediction is
    trained through the metric-PROPAGATION path — the legacy route froze one
    host-sampled mask and doubled the batch instead.
    """
    def body(carry, _):
        p, o, k = carry
        k, sub = jax.random.split(k)
        drop = (jax.random.uniform(sub, batch["metrics_valid"].shape)
                < dropout_p) & ~batch["is_summary"]
        b = dict(batch, metrics_valid=batch["metrics_valid"] & ~drop)
        p, o, loss, ok = _adam_update(p, o, b, lr, weights)
        return (p, o, k), (loss, ok)

    (params, opt, _), (losses, oks) = jax.lax.scan(body, (params, opt, key),
                                                   None, length=steps)
    return params, opt, losses[-1], steps - jnp.sum(oks)


# batch/weights live in the TrainingCache and MUST NOT be donated; params/opt
# follow the same replace-every-call (donated) pattern as the legacy run.
_adam_run_resident = jax.jit(_adam_run_resident_impl, static_argnums=(7,),
                             donate_argnums=(0, 1))


def _round_steps(steps: int) -> int:
    """Round DOWN to a power of two in [8, 512] (jit cache friendliness;
    the floor keeps step counts comparable with the historical fit rows)."""
    p2 = 1 << max(0, (max(steps, 1)).bit_length() - 1)
    return max(8, min(512, p2 if steps - p2 < p2 else p2 * 2))


class EnelTrainer:
    """One global reusable model + the paper's (re)training cadence."""

    _ids = itertools.count()        # default obs label allocator

    def __init__(self, seed: int = 0, lr: float = 5e-3,
                 cache_capacity: int = 96, obs_name: Optional[str] = None):
        self.seed = seed
        self.lr = lr
        self.params = enel_model.init_enel(jax.random.PRNGKey(seed))
        self._reset_opt()
        self.runs_seen = 0
        # device-resident history ring for the online fast path (lazy: sized
        # to the first graphs seen); legacy fit() keeps working without it
        self.cache: Optional[TrainingCache] = None
        self.cache_capacity = cache_capacity
        self._fit_calls = 0
        self._register_obs(obs_name)

    def _register_obs(self, obs_name: Optional[str]) -> None:
        # non-finite guard telemetry (see _adam_update): registry-backed
        # behind the original attribute API (nonfinite_steps /
        # last_skipped_steps / poisoned_fits properties below)
        self.obs_name = obs_name or f"tr{next(self._ids)}"
        reg = obs.registry()
        self._obs_counters = {
            attr: (reg.counter(fam, help) if kind == "counter"
                   else reg.gauge(fam, help)).labels(trainer=self.obs_name)
            for attr, (fam, kind, help) in _TRAINER_COUNTERS.items()}

    def _emit_fit(self, route: str, scratch: bool, steps: int, loss: float,
                  retried: bool = False) -> None:
        obs.emit("fit", trainer=self.obs_name, route=route,
                 mode="scratch" if scratch else "tune", steps=steps,
                 skipped=self.last_skipped_steps, retried=retried,
                 loss=round(float(loss), 6))

    def _fit_span(self, route: str, scratch: bool, steps: int) -> obs.Span:
        """The ``enel.fit`` ring span around one fit, closed after its
        loss is on the host."""
        return obs.span("enel.fit", _ring=True, trainer=self.obs_name,
                        route=route, mode="scratch" if scratch else "tune",
                        steps=_round_steps(steps))

    def copy(self, seed: int) -> "EnelTrainer":
        """A trainer of its own with this one's learned state: parameters,
        optimizer state and history ring copied into new device buffers
        (one compiled call, nothing aliased), the cadence counters kept,
        telemetry of its own; ``seed`` keys the copy's dropout and its
        scratch retrains."""
        out = EnelTrainer.__new__(EnelTrainer)
        out.__dict__.update(self.__dict__)
        out.seed = seed
        out._register_obs(None)
        out.params = copy_tree(self.params)
        out.opt = copy_tree(self.opt)
        if self.cache is not None:
            out.cache = self.cache.copy()
        return out

    def _reset_opt(self):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        self.opt = (zeros, jax.tree_util.tree_map(jnp.zeros_like, self.params),
                    jnp.zeros((), jnp.int32))

    def n_params(self) -> int:
        return enel_model.n_params(self.params)

    def fit(self, graphs: Sequence[ComponentGraph], *, steps: int = 200,
            from_scratch: bool = False, metric_dropout: float = 0.5) -> float:
        """Train on a set of component graphs; returns final loss.

        ``metric_dropout`` appends a copy of the batch with task-set metrics
        masked out (summary nodes kept), so runtime prediction is also trained
        through the metric-PROPAGATION path — the exact configuration used
        during online inference on not-yet-executed iterations (§III-D).
        """
        if not graphs:
            return float("nan")
        with self._fit_span("legacy", from_scratch, steps):
            return self._fit(graphs, steps, from_scratch, metric_dropout)

    def _fit(self, graphs, steps, from_scratch, metric_dropout) -> float:
        if from_scratch:
            self.params = enel_model.init_enel(jax.random.PRNGKey(self.seed))
            self._reset_opt()
        graphs = list(graphs)
        # bucket the batch to a power of two with empty (all-masked) graphs so
        # jit caches a handful of shapes instead of one per history length
        from repro.core.graph import empty_graph
        n = len(graphs)
        graphs = graphs + [empty_graph()] * (pow2_bucket(n) - n)
        stacked = stack_graphs(graphs)
        if metric_dropout > 0:
            rng = np.random.RandomState(self.seed + self.runs_seen)
            aug = {k: v.copy() for k, v in stacked.items()}
            drop = (rng.rand(*aug["metrics_valid"].shape) < metric_dropout)
            drop &= ~aug["is_summary"]
            aug["metrics_valid"] = aug["metrics_valid"] & ~drop
            stacked = {k: np.concatenate([stacked[k], aug[k]])
                       for k in stacked}
        batch = {k: jnp.asarray(v) for k, v in stacked.items()}
        steps = _round_steps(steps)
        self.params, self.opt, loss, skipped = _adam_run(
            self.params, self.opt, batch, steps, self.lr)
        self._note_skipped(skipped, steps)
        loss = float(loss)
        self._emit_fit("legacy", from_scratch, steps, loss)
        return loss

    def _note_skipped(self, skipped, steps: int) -> None:
        self.last_skipped_steps = int(skipped)
        self.nonfinite_steps += self.last_skipped_steps
        if self.last_skipped_steps >= steps:
            self.poisoned_fits += 1

    def params_finite(self) -> bool:
        """True iff every model parameter is finite (one host fetch)."""
        return all(bool(np.isfinite(np.asarray(l)).all())
                   for l in jax.tree_util.tree_leaves(self.params))

    # ------------------------------------------------- resident fast path
    def extend_history(self, graphs: Sequence[ComponentGraph]) -> None:
        """Append a run's graphs to the device-resident training ring (the
        runner calls this once per run; fits then reuse the buffers)."""
        graphs = list(graphs)
        if not graphs:
            return
        if self.cache is None:
            self.cache = TrainingCache(self.cache_capacity)
        self.cache.extend(graphs)

    def fit_resident(self, *, steps: int = 200, from_scratch: bool = False,
                     metric_dropout: float = 0.5,
                     latest_only: bool = False,
                     _retry: bool = True) -> float:
        """Train on the resident ring buffer; returns final loss.

        ``latest_only`` restricts the loss to the newest ``extend_history``
        batch (the paper's fine-tune step) via a gathered power-of-two slice;
        otherwise the whole ring (scratch-retrain window) trains with
        per-slot weights masking unfilled slots.  Metric dropout is sampled
        on-device per Adam step (see ``_adam_run_resident_impl``).

        The non-finite guard skips poisoned steps instead of writing NaN
        params (counted in ``nonfinite_steps``); a fit where EVERY step was
        skipped triggers one cache :meth:`~repro.core.graph.TrainingCache.
        quarantine_nonfinite` sweep and a single retry — self-healing after
        in-place cache corruption.
        """
        if self.cache is None or self.cache.count == 0:
            return float("nan")
        with self._fit_span("resident", from_scratch, steps):
            return self._fit_resident(steps, from_scratch, metric_dropout,
                                      latest_only, _retry)

    def _fit_resident(self, steps, from_scratch, metric_dropout,
                      latest_only, _retry) -> float:
        if from_scratch:
            self.params = enel_model.init_enel(jax.random.PRNGKey(self.seed))
            self._reset_opt()
        batch, weights = (self.cache.latest_batch() if latest_only
                          else self.cache.full_batch())
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed ^ 0x5eed),
                                 self._fit_calls)
        self._fit_calls += 1
        n_steps = _round_steps(steps)
        self.params, self.opt, loss, skipped = _adam_run_resident(
            self.params, self.opt, batch, jnp.asarray(weights), key, self.lr,
            float(metric_dropout), n_steps)
        self._note_skipped(skipped, n_steps)
        if self.last_skipped_steps >= n_steps and _retry and \
                self.params_finite() and \
                self.cache.quarantine_nonfinite() > 0:
            # params were fine but the batch was poisoned: the corrupt rows
            # are quarantined now, so one retry trains on the healed ring
            self._emit_fit("resident", from_scratch, n_steps, float(loss),
                           retried=True)
            return self.fit_resident(steps=steps, from_scratch=from_scratch,
                                     metric_dropout=metric_dropout,
                                     latest_only=latest_only, _retry=False)
        loss = float(loss)
        self._emit_fit("resident", from_scratch, n_steps, loss)
        return loss

    def observe_run_resident(self, *, retrain_every: int = 5,
                             steps: int = 200,
                             fine_tune_steps: int = 60) -> float:
        """Paper cadence (§V-B.3) on the resident ring: scratch-retrain on
        the full history window every `retrain_every` runs, fine-tune on the
        newest run's graphs (the last ``extend_history``) in between."""
        self.runs_seen += 1
        if (self.runs_seen % retrain_every) == 0:
            return self.fit_resident(steps=steps, from_scratch=True)
        return self.fit_resident(steps=fine_tune_steps, latest_only=True)

    def observe_run(self, latest: Sequence[ComponentGraph],
                    history: Optional[Sequence[ComponentGraph]] = None,
                    retrain_every: int = 5, steps: int = 200,
                    fine_tune_steps: int = 60) -> float:
        """Paper cadence (§V-B.3): train a new model from scratch on the
        history window every `retrain_every` runs, fine-tune on the newest
        run's graphs in between."""
        self.runs_seen += 1
        scratch = (self.runs_seen % retrain_every) == 0 and history is not None
        if scratch:
            return self.fit(history, steps=steps, from_scratch=True)
        return self.fit(latest, steps=fine_tune_steps)

    # --------------------------------------------------- checkpoint support
    def snapshot_state(self) -> Dict:
        """Picklable host copy of params/opt/cadence/ring state (campaign
        checkpoints; see dataflow/fleet.py)."""
        host = lambda t: jax.tree_util.tree_map(np.asarray, t)
        return {"params": host(self.params), "opt": host(self.opt),
                "runs_seen": self.runs_seen, "fit_calls": self._fit_calls,
                "nonfinite_steps": self.nonfinite_steps,
                "last_skipped_steps": self.last_skipped_steps,
                "poisoned_fits": self.poisoned_fits,
                "cache": None if self.cache is None
                else self.cache.snapshot()}

    def restore_state(self, st: Dict) -> None:
        dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        self.params = dev(st["params"])
        self.opt = dev(st["opt"])
        self.runs_seen = int(st["runs_seen"])
        self._fit_calls = int(st["fit_calls"])
        self.nonfinite_steps = int(st["nonfinite_steps"])
        self.last_skipped_steps = int(st["last_skipped_steps"])
        self.poisoned_fits = int(st["poisoned_fits"])
        self.cache = None if st["cache"] is None \
            else TrainingCache.from_snapshot(st["cache"])

    def predict(self, graphs: Sequence[ComponentGraph]) -> np.ndarray:
        """Per-component total-runtime predictions (seconds)."""
        from repro.core.graph import empty_graph
        n = len(graphs)
        padded = list(graphs) + [empty_graph()] * (pow2_bucket(n) - n)
        batch = {k: jnp.asarray(v) for k, v in stack_graphs(padded).items()}
        return np.asarray(
            enel_model.predict_total_runtime(self.params, batch))[:n]

    def predict_stacked(self, batch: Dict[str, np.ndarray]) -> np.ndarray:
        """Totals for an already-stacked (B, N, ...) graph-array dict."""
        dev = {k: jnp.asarray(v) for k, v in batch.items()}
        return np.asarray(enel_model.predict_total_runtime(self.params, dev))


def _install_counter_properties():
    """Registry-backed guard counters behind the original attribute API."""
    def make(attr):
        def fget(self):
            return int(self._obs_counters[attr].value)

        def fset(self, value):
            self._obs_counters[attr].set(value)
        return property(fget, fset)

    for attr in _TRAINER_COUNTERS:
        setattr(EnelTrainer, attr, make(attr))


_install_counter_properties()
