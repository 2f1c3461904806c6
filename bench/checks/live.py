"""The comparisons that decide ``correct`` for live lockstep cells.

The service's robustness counters stay at zero over the window, every
tenant's parameters stay finite and every run finishes.  Tenants drawn
from the seed run the window's first run with weights the benchmark made
(``checks/fits.py``) and loaded at the end of set-up.  The window keeps, as
device references or plain copies and with no transfer to the host:

* every decision request of those tenants in the window's first run, with
  the service's answer;
* their scratch retrain of the window's retrain run: the ring it trained
  on and its result;
* the first fine-tune of one of them per class: the parameters and
  optimizer state before it (copied, since the fit donates them), the ring
  and its result.

After the window the plain reference answers the kept requests with the
made weights (per-candidate totals and the pick), fits its own parameters
from its own initialisation over the kept ring, and fine-tunes from the
program's state before the fine-tune over the newest run's rows, which it
gathers from the ring itself.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import harness
from checks.fits import fit_key, fresh_state, newest_rows, scratch_weights
from reference import enel_ref

N_PAD = 16          # node slots every reference graph is padded to
ROWS_PAD = 1024     # graphs per reference call (36 candidates x 24 comps)


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _keep(x):
    """A request field as it stands now: device arrays are never written
    in place, host arrays may be, so those are copied."""
    return np.array(x) if isinstance(x, np.ndarray) else x


class Capture:
    """Chooses from the seed which tenants' requests and fits of the window
    to keep, keeps them, and compares them with the reference afterwards."""

    def __init__(self, exps, check: Dict, cfg: Dict, seed: int):
        self.exps, self.cfg = exps, cfg
        rng = np.random.default_rng(harness.seeds(seed, 2)[1])
        n, classes = len(exps), len(cfg["jobs"])
        self.tenants = set()
        self.tune_tenants = set()
        for c in range(classes):
            pick = rng.choice(np.arange(c, n, classes),
                              check["tenants_per_class"], replace=False)
            self.tenants |= {int(j) for j in pick}
            self.tune_tenants.add(int(pick[0]))
        self.made: Dict[int, Dict] = {}
        self.armed = False
        self.run_no = [-1] * n
        self.requests: List[tuple] = []
        self.fits: List[Dict] = []

    def warm(self) -> None:
        """Run the copies once in set-up so none compiles in the window."""
        tr = self.exps[0].trainer
        jax.block_until_ready(_copy((tr.params, tr.opt, tr.cache.buffers)))

    def arm(self) -> None:
        """From the next run on, count the window's runs; the retrain run
        is the first whose fit the cadence makes a scratch one."""
        every = self.cfg["retrain_every"]
        self.seen = self.exps[0].trainer.runs_seen
        self.scratch_run = (every - (self.seen + 1) % every) % every
        self.armed = True

    # --------------------------------------------------------- in the window
    def start_run(self, i: int) -> None:
        if self.armed:
            self.run_no[i] += 1

    def offer(self, i: int, req, res) -> None:
        if not (self.armed and i in self.tenants and self.run_no[i] == 0):
            return
        self.requests.append((i, {
            "base": {k: _keep(v) for k, v in req.base.items()},
            "h_onehot": _keep(req.h_onehot), "deltas": dict(req.deltas),
            "cand_valid": req.cand_valid,
            "candidates": list(req.candidate_list),
            "k_real": int(req.n_components),
            "elapsed": float(req.elapsed), "target": float(req.target)},
            int(res.scaleout), dict(res.totals)))

    def _protect(self, trainer) -> None:
        """A fit donates the trainer's parameters: copy a kept result that
        still is them first (fixed shapes, so the copy never compiles
        here)."""
        for rec in self.fits:
            if rec["after"] is trainer.params:
                rec["after"] = _copy(trainer.params)

    def fit_wrapper(self, i: int, trainer, fit):
        def wrapped(**kw):
            self._protect(trainer)
            run = self.run_no[i]
            scratch = bool(kw.get("from_scratch", False))
            keep = self.armed and (
                (i in self.tenants and run == self.scratch_run) or
                (i in self.tune_tenants and run == 0))
            if not keep:
                return fit(**kw)
            cache = trainer.cache
            rec = {"tenant": i, "run": run, "scratch": scratch,
                   "buffers": _copy(cache.buffers), "pos": int(cache.pos),
                   "count": int(cache.count),
                   "slot_ok": np.array(cache.slot_ok),
                   "seed": int(trainer.seed)}
            if run != self.scratch_run:     # a fine-tune starts from these
                rec["params"] = _copy(trainer.params)
                rec["opt"] = _copy(trainer.opt)
            loss = fit(**kw)
            rec["after"] = trainer.params
            self.fits.append(rec)
            return loss
        return wrapped

    # ----------------------------------------------------- after the window
    def cases(self) -> List[Dict]:
        """The kept fits as the reference's inputs and the program's
        results; the window's retrain run is a scratch retrain, its first
        run a fine-tune, as the configuration's cadence states.  A tenant's
        fits are counted from its profile's scratch fit, call 0, then one
        per run."""
        fit = self.cfg["fit"]
        out = []
        for rec in self.fits:
            kind = "scratch" if rec["run"] == self.scratch_run else "tune"
            buffers = _host(rec["buffers"])
            case = {"tenant": rec["tenant"], "kind": kind,
                    "program_scratch": rec["scratch"],
                    "key": fit_key(rec["seed"],
                                   1 + self.seen + rec["run"]),
                    "got": _host(rec["after"])}
            if kind == "scratch":
                case["p0"], case["opt0"] = fresh_state(rec["seed"])
                case["batch"] = buffers
                case["w"] = scratch_weights(rec["count"], rec["slot_ok"])
                case["steps"] = fit["scratch_steps"]
            else:
                n = self.exps[rec["tenant"]].job.n_components
                case["p0"], case["opt0"] = _host(rec["params"]), \
                    _host(rec["opt"])
                case["batch"], case["w"] = newest_rows(
                    buffers, rec["pos"], rec["slot_ok"], n,
                    1 << (n - 1).bit_length())
                case["steps"] = fit["tune_steps"]
            out.append(case)
        return out

    def decision_numbers(self, operands=None) -> Tuple[Dict, Dict]:
        """|program total - reference total| / target over every candidate
        of the kept requests, and each request's pick gap, the reference
        answering with the made weights; with ``operands`` set, the
        control, a forward with the same weights and its products'
        operands rounded to that type, stands in for the program's answers.
        Returns the numbers compared, the means, and the worst of each, for
        the record: a sound program's worst reading is one near tie or one
        ill-rounded candidate of its bfloat16 products, and swings from
        seed to seed as widely as the control's does."""
        if not self.requests:
            nan = float("nan")
            return {"totals_dev_mean": nan, "pick_gap_mean": nan}, {}
        devs, gaps = [], []
        for i, r, s_prog, tot_prog in self.requests:
            g = request_graphs(r)
            ref = r["elapsed"] + _totals(self.made[i], g)
            cands = r["candidates"]
            if operands is None:
                got = np.array([tot_prog[s] for s in cands])
                chosen = s_prog
            else:
                got = r["elapsed"] + _totals(self.made[i], g, operands)
                chosen = enel_ref.pick(cands, list(got), r["target"])
            devs.append(np.abs(got - ref) / r["target"])
            gaps.append(enel_ref.pick_gap(chosen, cands, list(ref),
                                          r["target"]))
        devs = np.concatenate(devs)
        return ({"totals_dev_mean": float(devs.mean()),
                 "pick_gap_mean": float(np.mean(gaps))},
                {"totals_dev_max": float(devs.max()),
                 "pick_gap_max": float(max(gaps))})


def _totals(p, g: Dict, operands=None) -> np.ndarray:
    """Per-candidate sums of the (C, K, ...) graphs' totals; the stack is
    padded to ``ROWS_PAD`` graphs so every request shares one shape."""
    c, k = g["mask"].shape[:2]
    flat = {key: v.reshape((c * k,) + v.shape[2:]) for key, v in g.items()}
    flat = {key: np.concatenate([v, np.zeros((ROWS_PAD - c * k,)
                                              + v.shape[1:], v.dtype)])
            for key, v in flat.items()}
    return enel_ref.graph_totals(p, flat, operands)[:c * k].reshape(
        c, k).sum(axis=1)


def request_graphs(r: Dict) -> Dict:
    """A decision request's (real candidates, real components, N_PAD, ...)
    graphs: the template's arrays with each candidate's node attributes
    and its historical-summary slot filled in."""
    base = _host(r["base"])
    cv = np.asarray(r["cand_valid"])
    k = r["k_real"]
    d = {key: np.asarray(v)[cv][:, :k] for key, v in r["deltas"].items()}
    oh = np.asarray(r["h_onehot"])[:k][None, :, :, None] > 0
    c, n = d["a_raw"].shape[0], base["mask"].shape[-1]
    pick = lambda key: base[key][:k]
    g = {
        "context": np.where(oh, d["h_context"][:, :, None, :],
                            pick("context")[None]),
        "metrics": np.where(oh, d["h_metrics"][:, :, None, :],
                            pick("metrics")[None]),
        "metrics_valid": d["metrics_valid"], "a_raw": d["a_raw"],
        "z_raw": d["z_raw"], "r": d["r"],
        "adj": np.broadcast_to(pick("adj")[None], (c, k, n, n)),
        "mask": np.broadcast_to(pick("mask")[None], (c, k, n)),
        "is_summary": np.broadcast_to(pick("is_summary")[None], (c, k, n)),
    }
    pad = N_PAD - n
    out = {}
    for key, v in g.items():
        width = [(0, 0)] * v.ndim
        width[2] = (0, pad)
        if key == "adj":
            width[3] = (0, pad)
        out[key] = np.pad(np.asarray(v), width)
    return out
