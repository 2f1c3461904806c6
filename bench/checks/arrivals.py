"""The comparisons that decide ``correct`` for open-loop arrival cells.

Every arrival of the window is answered exactly once, by the result of its
own request (``DecisionResult.rid`` names the request a result answers),
and applied to its tenant before the unit ends; the service's robustness
counters stay at zero and every tenant's parameters stay finite.  The
window keeps, as device references or plain copies and with no transfer to
the host:

* every decision request of the window with the service's answer and the
  parameters it was made with (copied before a fit donates them);
* every sim step of one sampled tenant per class, from the start of the
  run in which set-up found it, with the stage runtimes it returned;
* the first scratch retrain and the first fine-tune of each class in the
  window (the fits of the runs that end there): the ring each trained on,
  the parameters and optimizer state before a fine-tune (copied, since the
  fit donates them) and each fit's result.

After the window the plain reference answers every kept request with its
parameters (per-candidate totals and the pick), fits its own parameters
over each kept fit's rows (a scratch retrain from its own initialisation, a
fine-tune from the program's state before it), and the numpy simulator
replays the sampled tenants' steps from their saved slot state.  Which
fits are scratch retrains follows from the configuration's cadence and each
tenant's retrain phase at set-up, counted independently of the program.  A
window in which no run of some kind ends (no scratch retrain in about one
run in a hundred) has no fit of that kind to compare; its gap reads 0 and
the record line counts the fits compared.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import numpy as np

from checks.fits import (KINDS, fit_key, fit_numbers, fresh_state,
                         newest_rows, scratch_weights)
from checks.live import _host, _keep, _totals, request_graphs
from reference import enel_ref


def copy_tree(tree):
    """The program's one-call copy of a tree into new device buffers."""
    from repro.core.graph import copy_tree as program_copy
    return program_copy(tree)


class Accounting:
    """Per unit: how many arrivals each tenant has, and what each tenant's
    generator was asked and answered."""

    def __init__(self):
        self.misrouted_or_duplicate = 0
        self.unanswered = 0
        self.expected: Dict[int, int] = {}
        self.applied: Dict[int, int] = {}
        self.answered_rids = set()

    def start_unit(self, expected: Dict[int, int]) -> None:
        self.expected = dict(expected)
        self.applied = {}

    def applied_result(self, tenant: int, req, res) -> None:
        """A generator was sent ``res`` for the request ``req`` it asked."""
        if res.rid != req.rid or req.rid in self.answered_rids:
            self.misrouted_or_duplicate += 1
            return
        self.answered_rids.add(req.rid)
        self.applied[tenant] = self.applied.get(tenant, 0) + 1

    def end_unit(self) -> None:
        for tenant in set(self.expected) | set(self.applied):
            n, due = self.applied.get(tenant, 0), self.expected.get(tenant, 0)
            self.unanswered += max(0, due - n)
            self.misrouted_or_duplicate += max(0, n - due)


class Capture:
    """Keeps the window's decisions and the sampled tenants' sim steps."""

    def __init__(self, exps, sim_tenants: List[int], classes: int,
                 retrain_every: int):
        self.exps = exps
        self.classes, self.every = classes, retrain_every
        self.armed = False
        self.decisions: List[tuple] = []
        self._unprotected: Dict[int, List[Dict]] = {}
        # each tenant's runs seen before its first instrumented fit, and
        # its fits since its profile's (call 0)
        self.phase0 = [e.trainer.runs_seen for e in exps]
        self.fit_count = [0] * len(exps)
        self.fits: List[Dict] = []
        self.sim_tenants = set(sim_tenants)
        self.slot_state0 = {j: exps[j].backend.slot_state(exps[j].sim_slot)
                            for j in sim_tenants}
        self.steps: Dict[int, List] = {j: [] for j in sim_tenants}

    def clear(self) -> None:
        """Forget what an earlier window kept."""
        self.decisions, self._unprotected, self.fits = [], {}, []

    # --------------------------------------------------------- in the window
    def offer_decision(self, i: int, req, res) -> None:
        if not self.armed or res.fallback:
            return
        rec = {"params": req.params,
               "base": {k: _keep(v) for k, v in req.base.items()},
               "h_onehot": _keep(req.h_onehot), "deltas": dict(req.deltas),
               "cand_valid": req.cand_valid,
               "candidates": list(req.candidate_list),
               "k_real": int(req.n_components),
               "elapsed": float(req.elapsed), "target": float(req.target)}
        self.decisions.append((rec, int(res.scaleout), dict(res.totals)))
        self._unprotected.setdefault(i, []).append(rec)

    def _protect(self, i: int, trainer) -> None:
        """Before tenant ``i``'s fit donates its parameters: kept requests
        and fit results that still hold them get a copy."""
        recs = self._unprotected.pop(i, [])
        held = [r for r in recs if r["params"] is trainer.params]
        done = [r for r in self.fits if r["after"] is trainer.params]
        if held or done:
            saved = copy_tree(trainer.params)
            for r in held:
                r["params"] = saved
            for r in done:
                r["after"] = saved

    def fit_wrapper(self, i: int, trainer, fit):
        """Tenant ``i``'s ``fit_resident``, keeping the window's first fit
        of each kind in each class."""
        def wrapped(**kw):
            self._protect(i, trainer)
            call = self.fit_count[i] = self.fit_count[i] + 1
            scratch = (self.phase0[i] + call) % self.every == 0
            kind = "scratch" if scratch else "tune"
            c = i % self.classes
            if not self.armed or any(
                    r["kind"] == kind and r["tenant"] % self.classes == c
                    for r in self.fits):
                return fit(**kw)
            cache = trainer.cache
            rec = {"tenant": i, "kind": kind,
                   "program_scratch": bool(kw.get("from_scratch", False)),
                   "call": call, "seed": int(trainer.seed),
                   "buffers": copy_tree(cache.buffers),
                   "pos": int(cache.pos), "count": int(cache.count),
                   "slot_ok": np.array(cache.slot_ok)}
            if not scratch:                 # a fine-tune starts from these
                rec["params"] = copy_tree(trainer.params)
                rec["opt"] = copy_tree(trainer.opt)
            loss = fit(**kw)
            rec["after"] = trainer.params
            self.fits.append(rec)
            return loss
        return wrapped

    def new_run(self, i: int) -> None:
        if i in self.sim_tenants:
            self.steps[i].append([])

    def offer_step(self, i: int, req, res) -> None:
        if i in self.sim_tenants:
            self.steps[i][-1].append(
                (int(req.comp_idx), int(req.start_scaleout),
                 int(req.end_scaleout), float(req.clock),
                 bool(req.inject_failures),
                 [float(st.runtime) for st in res.component.stages]))

    # ----------------------------------------------------- after the window
    def fit_cases(self, cfg: Dict) -> List[Dict]:
        """The kept fits as the reference's inputs and the program's
        results (``checks/fits.py``'s cases)."""
        fit = cfg["fit"]
        out = []
        for rec in self.fits:
            buffers = _host(rec["buffers"])
            case = {"tenant": rec["tenant"], "kind": rec["kind"],
                    "program_scratch": rec["program_scratch"],
                    "key": fit_key(rec["seed"], rec["call"]),
                    "got": _host(rec["after"])}
            if rec["kind"] == "scratch":
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
        of every kept request, and each request's pick gap, the reference
        answering with the parameters the request was made with; with
        ``operands`` set, the control (a forward with the same parameters
        and its products' operands rounded to that type) stands in for the
        program's answers.  Returns the numbers compared (means) and the
        worst of each, for the record."""
        if not self.decisions:
            nan = float("nan")
            return {"totals_dev_mean": nan, "pick_gap_mean": nan}, {}
        params = {}
        for rec, _, _ in self.decisions:
            params.setdefault(id(rec["params"]), rec["params"])
        host = dict(zip(params, jax.device_get(list(params.values()))))
        devs, gaps = [], []
        for r, s_prog, tot_prog in self.decisions:
            p = host[id(r["params"])]
            g = request_graphs(r)
            ref = r["elapsed"] + _totals(p, g)
            cands = r["candidates"]
            if operands is None:
                got = np.array([tot_prog[s] for s in cands])
                chosen = s_prog
            else:
                got = r["elapsed"] + _totals(p, g, operands)
                chosen = enel_ref.pick(cands, list(got), r["target"])
            devs.append(np.abs(got - ref) / r["target"])
            gaps.append(enel_ref.pick_gap(chosen, cands, list(ref),
                                          r["target"]))
        devs = np.concatenate(devs)
        return ({"totals_dev_mean": float(devs.mean()),
                 "pick_gap_mean": float(np.mean(gaps))},
                {"totals_dev_max": float(devs.max()),
                 "pick_gap_max": float(max(gaps)),
                 "decisions": len(gaps)})

    def sim_replay(self) -> float:
        """Largest relative deviation of the sampled tenants' stage
        runtimes from a numpy ``ClusterSim`` replay of the same steps from
        the slot state they started from."""
        from repro.dataflow.simulator import ClusterSim
        from repro.sim.engine import NumpySimBackend, SimStepRequest
        worst = 0.0
        for j, runs in self.steps.items():
            exp = self.exps[j]
            sim = ClusterSim(seed=exp.seed, scenario=exp.scenario)
            sim.load_state_dict(self.slot_state0[j])
            npb = NumpySimBackend()
            slot = npb.adopt(sim, exp.job)
            for steps in runs:
                npb.begin_run(slot)
                for k, a, z, clock, inject, got in steps:
                    res = npb.step([SimStepRequest(slot, k, a, z, clock,
                                                   inject)])[0]
                    if len(res.component.stages) != len(got):
                        return float("inf")
                    for st, rt in zip(res.component.stages, got):
                        ref = float(np.float32(st.runtime))
                        worst = max(worst, abs(rt - ref) / ref)
        return worst


def window_fit_numbers(cases: List[Dict], control: bool = False
                       ) -> Tuple[Dict, Dict]:
    """``fit_numbers`` over the window's kept fits; a kind no run of the
    window ended in reads 0 (nothing to compare), and the record counts the
    fits of each kind compared."""
    out, worst = fit_numbers(cases, control)
    for kind in KINDS:
        n = sum(c["kind"] == kind for c in cases)
        worst[kind + "_fits"] = n
        if n == 0:
            out[kind + "_change_gap"] = 0.0
    return out, worst
