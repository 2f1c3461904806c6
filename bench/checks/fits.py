"""The fit comparisons both cells share, and the weights the benchmark makes.

A fit case holds the reference's inputs (``p0``, ``opt0``, ``batch``,
``w``, ``key``, ``steps``), the program's result (``got``) and its kind
(``scratch`` or ``tune``); :func:`reference_fits` adds the reference's
result.  A fit is compared by the change norm of each parameter leaf: over
128 Adam steps with the TPU's bfloat16 products, a difference in the last
bit of the starting point grows to tenths of the worst leaf's change, so
the number compared is the median leaf's gap, and the worst leaf's is kept
for the record.

The decisions the check compares are made with weights the benchmark makes
itself: the reference's own scratch fit from its own initialisation over a
tenant's recorded rows, loaded into the program in set-up as a deployment
loads a trained model.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference import enel_ref

KINDS = ("scratch", "tune")


def fit_key(seed: int, call: int):
    """The dropout key of a tenant's ``call``-th fit (0: the profile's)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5eed), call)


def fresh_state(seed: int):
    p0 = enel_ref.init_params(seed)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    return p0, (zeros, zeros, 0)


def scratch_weights(count: int, slot_ok) -> np.ndarray:
    """A scratch retrain weighs every ring slot that holds a recorded
    graph."""
    slot_ok = np.asarray(slot_ok)
    return ((np.arange(len(slot_ok)) < count) & slot_ok).astype(np.float32)


def newest_rows(buffers: Dict, pos: int, slot_ok, n: int, rows: int):
    """A fine-tune's rows: the ``n`` graphs of the run just recorded, the
    slots before the ring's write position, oldest first, then empty rows
    up to ``rows`` with weight 0."""
    cap = len(slot_ok)
    idx = (pos - n + np.arange(n)) % cap
    batch = {}
    for k, v in buffers.items():
        v = np.asarray(v)
        pad = np.zeros((rows - n,) + v.shape[1:], v.dtype)
        batch[k] = np.concatenate([v[idx], pad])
    w = np.zeros(rows, np.float32)
    w[:n] = np.asarray(slot_ok)[idx]
    return batch, w


def reference_fits(cfg: Dict, cases: List[Dict], operands=None) -> None:
    """Run the reference fit of every case that lacks it; stores ``ref``
    (params) and ``grad0`` (its first gradient), or with ``operands`` set
    ``ctl``."""
    fit = cfg["fit"]
    for c in cases:
        if operands is None and "ref" in c:
            continue
        out = enel_ref.adam_fit(c["p0"], c["opt0"], c["batch"], c["w"],
                                c["key"], fit["lr"], fit["metric_dropout"],
                                c["steps"], operands=operands)
        if operands is None:
            c["ref"], c["grad0"] = out[0], out[3]
        else:
            c["ctl"] = out[0]


def fit_numbers(cases: List[Dict], control: bool = False
                ) -> Tuple[Dict, Dict]:
    """Per kind of fit, the worst over its fits of the median leaf's
    change gap (compared) and of the worst leaf's (for the record).  A
    kind with no fit, or with one the program ran as the other kind, reads
    NaN.  With ``control`` the control's fit stands in for the
    program's."""
    out, worst = {}, {}
    for kind in KINDS:
        name = kind + "_change_gap"
        mine = [c for c in cases if c["kind"] == kind]
        if not mine or any(c["program_scratch"] != (kind == "scratch")
                           for c in mine):
            out[name] = worst[name] = float("nan")
            continue
        gaps = [enel_ref.leaf_change_gaps(c["p0"], c["ctl"] if control
                                          else c["got"], c["ref"],
                                          c["grad0"]) for c in mine]
        out[name] = max(float(np.median(g)) for g in gaps)
        worst[name] = max(float(g.max()) for g in gaps)
    return out, worst


def made_weights(cfg: Dict, trainer) -> Dict:
    """The reference's scratch fit from its own initialisation over the
    trainer's recorded ring, with the dropout key of a tenant's first fit:
    a fit case whose ``ref`` is the weights the benchmark loads."""
    cache = trainer.cache
    p0, opt0 = fresh_state(trainer.seed)
    case = {"kind": "scratch", "p0": p0, "opt0": opt0,
            "batch": jax.tree_util.tree_map(np.asarray, cache.buffers),
            "w": scratch_weights(cache.count, cache.slot_ok),
            "key": fit_key(trainer.seed, 0),
            "steps": cfg["fit"]["scratch_steps"]}
    reference_fits(cfg, [case])
    return case


def load_weights(trainer, params: Dict) -> None:
    """Put ``params`` in the trainer's place, in the program's own tree
    structure and type, on the device."""
    leaves = jax.tree_util.tree_leaves(params)
    old = jax.tree_util.tree_leaves(trainer.params)
    if [np.shape(x) for x in leaves] != [x.shape for x in old]:
        raise ValueError("made weights do not fit the program's")
    trainer.params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(trainer.params),
        [jnp.asarray(x, o.dtype) for x, o in zip(leaves, old)])
