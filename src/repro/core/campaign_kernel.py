"""Whole-campaign-on-device: sim step + decision sweep + resident fit fused
into ONE ``lax.scan`` over campaign steps.

The live fleet path (``FleetCampaign.adaptive_campaign``) interleaves host
python between every device dispatch: one sim-step jit per component round,
one sweep jit per decision round, one Adam jit per run, plus host graph
building, ring appends and bookkeeping in between.  This module compiles the
ENTIRE campaign — R runs x C components of J concurrent jobs — into a single
scanned jit per bucket-ladder rung:

* step ``t`` maps to (run ``t // C``, component ``t % C``);
* (a) one :func:`~repro.sim.engine._step_kernel_impl` component step against
  pre-drawn per-run input blocks (:meth:`BatchedClusterSim.
  campaign_run_blocks` consumes the SAME host RNG stream as the stepped
  path, so the noise/straggler/kill draws are bit-identical);
* (b) the observed component's ring row is built on device from frozen
  context tables and appended to the resident training ring as a pure carry
  update (:func:`~repro.core.graph.ring_append`);
* (c) on decision boundaries, the bucketed candidate sweep + on-device
  compliant pick runs via the SAME :func:`~repro.core.service.
  sweep_eval_one` ops the fleet service dispatches, with the
  :func:`~repro.core.fallback.fallback_pick` guardrail and the
  non-finite reduce folded into the scan (pure ops, no host round-trip);
* (d) at each run boundary, the paper's retrain cadence runs K resident
  Adam steps (:func:`~repro.core.training._adam_run_resident_impl`) from
  the ring under ``lax.cond`` — scratch reinit every ``retrain_every``-th
  run, fine-tune otherwise — and ``nan_fit`` chaos poisons params in-scan.

The host materializes traces ONCE at campaign end.  ``run_stepped`` drives
the identical step body through a python loop (one jit call per step) — the
parity contract ``run_fused == run_stepped`` is bit-exact and CI-tested.

Documented deviations from the LIVE host path (``adaptive_campaign``) —
the fused campaign is a faithful but not bit-identical twin:

* node contexts are FROZEN at plan time (``frozen_context_tables``:
  ``drop_versions=False``, ``attempt=0``) — the live encoder consumes RNG
  per observation for software-version dropout and bumps the attempt
  counter on failures;
* the candidate grid is the fixed ``range(lo, hi+1, stride) | {hi}`` —
  the live grid also splices in the current scale-out when off-stride;
* historical H-summary tables are frozen at plan time — the live
  ``hist_summaries`` grow intra-campaign, so live H nodes drift as runs
  accumulate;
* P-summary context/metrics are f32 device means (live: numpy means cast
  to f32 — identical op order for <= 5 stages, but not guaranteed bitwise);
* the per-run fit fires at the LAST component index of the longest job for
  every job, and fine-tune batches are padded to one uniform
  ``pow2_bucket(c_max)`` row count (live: per-job ``pow2_bucket(n_j)``,
  which changes the per-step dropout RNG shapes for shorter jobs);
* only ``nan_fit`` chaos is supported in-scan (``nan_graphs_every`` /
  ``cache_corrupt_every`` mutate host caches mid-run); the service-layer
  retry/breaker/shed envelope does not exist here — the in-scan guardrail
  is the isfinite reduce + fallback clamp.

None of these affect the fused==stepped contract, which shares every table
and every op; ``tests/test_fused_campaign.py`` additionally grounds the
fused kernel against ``BatchedClusterSim.run_full`` by replaying the fused
z-schedule (bit-exact stage runtimes/clocks).
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fallback import fallback_pick
from repro.core.graph import (CAND_LADDER, COMP_LADDER, EDGE_LADDER,
                              LEVEL_LADDER, N_METRICS, CTX_DIM, ladder_bucket,
                              historical_summaries_batch, pow2_bucket,
                              propagation_depth, ring_append)
from repro.core.model import init_enel, record_trace
from repro.core.service import sweep_eval_one
from repro.core.training import _adam_run_resident_impl, _round_steps

# engine <-> dataflow import cycle: initialize the dataflow package first so
# repro.sim.engine's simulator import finds it loaded (same order the fleet
# entry points use)
import repro.dataflow  # noqa: F401  (import-order side effect only)
from repro.sim.engine import (_nc, _step_kernel_impl, _O_CLK, _O_FAILED,
                              _O_MET, _O_RT, BatchedClusterSim)

N_ROW = 8          # ring-row / sweep node slots (stages + P + H, bucketed)


class PlanStatic(NamedTuple):
    """Hashable static config of one fused campaign (jit static argnum 0).

    One compile per distinct PlanStatic — the compile count of a campaign
    is bounded by the bucket-ladder rungs these fields can take, asserted
    in CI via ``model.TRACE_COUNTS["fused_campaign"]``.
    """
    c_max: int           # component steps per run (longest job)
    s_max: int           # stage rows per component step (engine S)
    lo: int              # scale-out grid origin (SCALEOUT_RANGE[0])
    tune_rows: int       # fine-tune batch rows: pow2_bucket(c_max)
    scratch_steps: int   # _round_steps(steps)
    tune_steps: int      # _round_steps(fine_tune_steps)
    retrain_every: int
    levels: int          # bucketed propagation depth for the sweep
    telemetry: bool = False  # in-scan obs block (ENEL_OBS; default at
    #                          build_plan). False compiles the exact
    #                          pre-observability program: same carry, same
    #                          ys, same jaxpr — the ENEL_OBS=0 bit-exactness
    #                          and zero-extra-traces guarantees.


class CampaignPlan:
    """Everything one fused campaign needs: static shapes, device tables,
    the initial carry, and the host-side materialization tables."""

    def __init__(self, static: PlanStatic, dev: Dict[str, Any],
                 init: Dict[str, Any], host: Dict[str, Any]):
        self.static = static
        self.dev = dev
        self.init = init
        self.host = host

    @property
    def n_jobs(self) -> int:
        return int(self.dev["inject"].shape[0])

    @property
    def n_runs(self) -> int:
        return int(self.dev["blocks"].shape[0])

    @property
    def n_steps(self) -> int:
        return self.n_runs * self.static.c_max


# =========================================================================
# the fused step body: ONE component step of the whole fleet
# =========================================================================

def _step(st: PlanStatic, dev, carry, t):
    """(carry, t) -> (carry', ys): component ``t % c_max`` of run
    ``t // c_max`` for every job — sim step, ring append, decision sweep
    (on decision boundaries) and the per-run fit (on run boundaries), all
    as pure ops so ``lax.scan`` fuses the whole campaign."""
    record_trace("fused_campaign")
    f32 = jnp.float32
    r = t // st.c_max
    k = t - r * st.c_max
    J = dev["inject"].shape[0]
    ji = jnp.arange(J)
    nsg = dev["obs_ctx"].shape[3]

    def zi(s):
        return jnp.clip(s.astype(jnp.int32) - st.lo, 0, nsg - 1)

    at_start = k == 0
    clock = jnp.where(at_start, f32(0.0), carry["clock"])
    s_prev = jnp.where(at_start, dev["s0"], carry["s_prev"])
    s_cur = jnp.where(at_start, dev["s0"], carry["s_cur"])
    a, z = s_prev, s_cur
    comp_ok = dev["comp_valid"][k]                       # (J,)

    # ---------------------------------------------- (a) fleet sim step
    with jax.named_scope("enel.sim"):
        # device twin of tables.overhead_f32 (anti-FMA guarded like the
        # engine)
        d = jnp.abs(z - a)
        ov = jnp.where(a == z, f32(0.0), f32(4.0) + _nc(f32(0.35) * d))
        ctrl = jnp.stack([clock, carry["interf"], a, z, dev["inject"],
                          dev["n_stage_f"][k], ov, dev["cursor_f"][k]],
                         axis=-1)
        state, outs = _step_kernel_impl(
            dev["blocks"][r], ctrl, st.s_max, dev["kills"][r], dev["burst"],
            dev["preempt"], dev["iscale2"], dev["mem_tab"], dev["shuf_tab"])
        clock = state[:, 0]                              # pass-through when
        interf = state[:, 1]                             # comp invalid (n=0)

    # ------------------------------------- (b) observed ring row, on device
    with jax.named_scope("enel.ring"):
        g = dev["cls"]
        h = dev["hcls"]
        rmask = dev["row_mask"][g, k]                        # (J, N_ROW)
        rsum = dev["row_summ"][g, k]
        radj = dev["row_adj"][g, k]                      # (J, N_ROW, N_ROW)
        rsi = dev["row_stage_idx"][g, k]                     # (J, N_ROW) i32
        rst = dev["row_is_stage"][g, k]
        rs0 = rst & (rsi == 0)
        rp = dev["row_is_p"][g, k]
        rh = dev["row_is_h"][g, k]

        pm, pa, pz = carry["p_met"], carry["p_a"], carry["p_z"]
        km1 = jnp.maximum(k - 1, 0)
        ctx_k = dev["obs_ctx"][g, k]                         # (J, S, NS, CTX)
        ctx_kz = ctx_k[ji[:, None], rsi, zi(z)[:, None]]     # (J, N_ROW, CTX)
        p_ctx_old = dev["p_ctx"][g, km1, zi(pz)]             # (J, CTX)
        h_ctx = dev["hob_ctx"][h, k, zi(z)]                  # (J, CTX)
        h_met = dev["hob_met"][h, k, zi(z)]                  # (J, N_METRICS)
        h_val = dev["hob_val"][h, k, zi(z)]                  # (J,)
        h_a = dev["hob_start"][h, k, zi(z)]
        h_b = dev["hob_end"][h, k, zi(z)]

        met_js = jnp.swapaxes(outs[:, :, _O_MET], 0, 1)      # (J, S, 5)
        rt_js = jnp.swapaxes(outs[:, :, _O_RT], 0, 1)        # (J, S)
        row_met = met_js[ji[:, None], rsi]                   # (J, N_ROW, 5)
        row_rt = rt_js[ji[:, None], rsi]                     # (J, N_ROW)

        a2, z2 = a[:, None], z[:, None]
        rescale0 = rs0 & (a2 != z2)
        w3 = lambda m: m[..., None]
        row = {
            "context": (jnp.where(w3(rst), ctx_kz, 0.0)
                        + jnp.where(w3(rp), p_ctx_old[:, None, :], 0.0)
                        + jnp.where(w3(rh), h_ctx[:, None, :], 0.0)),
            "metrics": (jnp.where(w3(rst), row_met, 0.0)
                        + jnp.where(w3(rp), pm[:, None, :], 0.0)
                        + jnp.where(w3(rh), h_met[:, None, :], 0.0)),
            "metrics_valid": rst | rp | (rh & h_val[:, None]),
            "a_raw": jnp.where(rs0, a2, jnp.where(rst, z2, jnp.where(
                rp, pa[:, None], jnp.where(rh, h_a[:, None], 1.0)))),
            "z_raw": jnp.where(rst, z2, jnp.where(
                rp, pz[:, None], jnp.where(rh, h_b[:, None], 1.0))),
            "r": jnp.where(rescale0, f32(0.8), f32(1.0)),
            "runtime": jnp.where(rst, row_rt, 0.0),
            "runtime_valid": rst,
            "overhead": jnp.where(rescale0, ov[:, None], 0.0),
            "overhead_valid": rescale0,
            "adj": radj,
            "mask": rmask,
            "is_summary": rsum,
        }

        ring = carry["ring"]
        cap = ring["slot_ok"].shape[1]

        def _append(bufs, row_j, pos_j, ok_j, slot_ok_j):
            old = jax.tree_util.tree_map(lambda b: b[pos_j], bufs)
            sel = jax.tree_util.tree_map(
                lambda nv, ovv: jnp.where(ok_j, nv.astype(ovv.dtype), ovv),
                row_j, old)
            bufs = ring_append(bufs, sel, pos_j)
            slot_ok_j = slot_ok_j.at[pos_j].set(
                jnp.where(ok_j, True, slot_ok_j[pos_j]))
            return bufs, slot_ok_j

        buffers, slot_ok = jax.vmap(_append)(
            ring["buffers"], row, ring["pos"], comp_ok, ring["slot_ok"])
        inc = comp_ok.astype(jnp.int32)
        pos = (ring["pos"] + inc) % cap
        count = jnp.minimum(ring["count"] + inc, cap)

        # fresh P(k) summary (current_summary for this boundary's decision)
        nst = dev["n_stage_f"][k].astype(jnp.int32)
        sv = jnp.arange(st.s_max)[None, :] < nst[:, None]    # (J, S)
        pm_new = (jnp.sum(jnp.where(sv[..., None], met_js, 0.0), axis=1)
                  / jnp.maximum(nst, 1)[:, None].astype(f32))
        pm = jnp.where(comp_ok[:, None], pm_new, pm)
        pa = jnp.where(comp_ok, a, pa)
        pz = jnp.where(comp_ok, z, pz)

    # ------------------------------------ (c) decision sweep + guardrails
    decide = dev["decide_tab"][k]                        # (J,)
    cand = dev["cand"]
    cand_valid = dev["cand_valid"]
    n_cand = cand.shape[0]

    def _decide_one(p_j, g_j, h_j, s_j, el_j, tg_j, nj_j, pm_j, pa_j, pz_j):
        stg = dev["sw_is_stage"][g_j]                    # (K, N)
        sidx = dev["sw_stage_idx"][g_j]
        isp = dev["sw_is_p"][g_j]
        ish = dev["sw_is_h"][g_j]
        comp_of = dev["sw_comp"]                         # (K,) = ki + 1
        vk = (comp_of > k) & (comp_of < nj_j)            # remaining comps
        isn = comp_of == (k + 1)
        mask_j = dev["sw_mask0"][g_j] & vk[:, None] \
            & (~isp | isn[:, None])
        zis = jnp.clip(s_j.astype(jnp.int32) - st.lo, 0, nsg - 1)
        ctx_z = dev["obs_ctx"][g_j, :, :, zis]           # (C_max, S, CTX)
        cc = jnp.clip(comp_of, 0, dev["obs_ctx"].shape[1] - 1)
        ctx_st = ctx_z[cc[:, None], sidx]                # (K, N, CTX)
        pzi = jnp.clip(pz_j.astype(jnp.int32) - st.lo, 0, nsg - 1)
        pctx = dev["p_ctx"][g_j, k, pzi]                 # (CTX,)
        base = {
            "context": (jnp.where(stg[..., None], ctx_st, 0.0)
                        + jnp.where(isp[..., None],
                                    pctx[None, None, :], 0.0)),
            "metrics": jnp.where(isp[..., None], pm_j[None, None, :], 0.0),
            "adj": dev["sw_adj"][g_j],
            "mask": mask_j,
            "is_summary": dev["sw_summ"][g_j],
        }
        zsel = jnp.broadcast_to(cand[:, None], (n_cand, stg.shape[0]))
        asel = jnp.where(isn[None, :], s_j, zsel)
        st0 = stg & (sidx == 0)
        a3, z3 = asel[:, :, None], zsel[:, :, None]
        h_a3 = dev["hsw_start"][h_j][..., None]          # (C, K, 1)
        h_b3 = dev["hsw_end"][h_j][..., None]
        hv3 = dev["hsw_val"][h_j][..., None]
        deltas = {
            "a_raw": jnp.where(st0[None], a3, jnp.where(
                stg[None], z3, jnp.where(isp[None], pa_j, jnp.where(
                    ish[None], h_a3, 1.0)))).astype(f32),
            "z_raw": jnp.where(stg[None], z3, jnp.where(
                isp[None], pz_j, jnp.where(ish[None], h_b3, 1.0))
            ).astype(f32),
            "r": jnp.where(stg[None] & (a3 != z3), f32(0.8), f32(1.0)),
            "metrics_valid": (isp[None] | (ish[None] & hv3))
            & mask_j[None],
            "h_context": dev["hsw_ctx"][h_j],            # (C, K, CTX)
            "h_metrics": dev["hsw_met"][h_j],
        }
        ed = dev["sw_edge_dst"][g_j]                     # (K, E)
        es = dev["sw_edge_src"][g_j]
        ev = (dev["sw_edge_val"][g_j]
              & jnp.take_along_axis(mask_j, ed, axis=1)
              & jnp.take_along_axis(mask_j, es, axis=1))
        idx, totals, _, ok = sweep_eval_one(
            p_j, base, dev["sw_oh"][g_j], deltas, ed, es, ev, cand,
            cand_valid, el_j, tg_j, st.levels)
        fb = fallback_pick(cand, cand_valid, totals, s_j, el_j, tg_j)
        return cand[jnp.where(ok, idx, fb)], ok

    def _run_sweep(_):
        return jax.vmap(_decide_one)(
            carry["params"], g, h, s_cur, clock, dev["target"],
            dev["n_comp"], pm, pa, pz)

    def _no_sweep(_):
        return s_cur, jnp.ones(J, bool)

    with jax.named_scope("enel.sweep"):
        s_new, dec_ok = jax.lax.cond(dev["any_decide"][k], _run_sweep,
                                     _no_sweep, None)
    fb_used = decide & ~dec_ok
    nonfin = decide & ~jnp.isfinite(s_new)
    s_next = jnp.where(decide, s_new, s_cur)
    # belt-and-braces: a non-finite decision must NEVER leave the scan
    s_next = jnp.where(jnp.isfinite(s_next), s_next, s_cur)

    # --------------------------------------- (d) per-run resident fit
    params, opt, fcalls = carry["params"], carry["opt"], carry["fit_calls"]
    is_last = k == st.c_max - 1

    def _run_adam(p, o, batch, w, steps):
        keys = jax.vmap(jax.random.fold_in)(dev["base_key"], fcalls)

        def one(pj, oj, bj, wj, kj, lr_j):
            return _adam_run_resident_impl(
                pj, oj, bj, wj, kj, lr_j, dev["dropout_p"], steps)

        return jax.vmap(one)(p, o, batch, w, keys, dev["lr"])

    def _fit_scratch(_):
        p0 = dev["init_params"]
        o0 = (jax.tree_util.tree_map(jnp.zeros_like, p0),
              jax.tree_util.tree_map(jnp.zeros_like, p0),
              jnp.zeros(J, jnp.int32))
        w = ((jnp.arange(cap)[None, :] < count[:, None])
             & slot_ok).astype(f32)
        return _run_adam(p0, o0, buffers, w, st.scratch_steps)

    def _fit_tune(_):
        rows = jnp.arange(st.tune_rows)[None, :]
        idx = (pos[:, None] - dev["n_comp"][:, None] + rows) % cap
        live = rows < dev["n_comp"][:, None]
        idx = jnp.where(live, idx, 0)
        batch = jax.tree_util.tree_map(
            lambda b: b[ji[:, None], idx], buffers)
        w = (live & slot_ok[ji[:, None], idx]).astype(f32)
        return _run_adam(params, opt, batch, w, st.tune_steps)

    def _do_fit(_):
        return jax.lax.cond(dev["scratch_at"][r], _fit_scratch, _fit_tune,
                            None)

    def _no_fit(_):
        return params, opt, jnp.zeros(J, f32), jnp.zeros(J, jnp.int32)

    with jax.named_scope("enel.fit"):
        params, opt, fit_loss, fit_skip = jax.lax.cond(is_last, _do_fit,
                                                       _no_fit, None)
    fcalls = jnp.where(is_last, fcalls + 1, fcalls)

    # nan_fit chaos fires right after the fit, exactly like the live hook
    pmask = dev["poison_at"][r] & is_last
    params = jax.tree_util.tree_map(
        lambda p: jnp.where(pmask.reshape((-1,) + (1,) * (p.ndim - 1)),
                            jnp.nan, p), params)

    new_carry = {
        "clock": clock, "interf": interf,
        "s_prev": s_cur, "s_cur": s_next,
        "p_met": pm, "p_a": pa, "p_z": pz,
        "ring": {"buffers": buffers, "pos": pos, "count": count,
                 "slot_ok": slot_ok},
        "params": params, "opt": opt, "fit_calls": fcalls,
        "fallbacks": carry["fallbacks"] + fb_used.astype(jnp.int32),
        "nonfinite": carry["nonfinite"] + nonfin.astype(jnp.int32),
    }
    ys = {
        "clock": clock, "interf": interf, "a": a, "z": z, "s_next": s_next,
        "decided": decide, "dec_ok": dec_ok, "fallback": fb_used,
        "nonfinite": nonfin, "fit_loss": fit_loss, "fit_skipped": fit_skip,
        "rt": outs[:, :, _O_RT], "failed": outs[:, :, _O_FAILED],
        "stage_clk": outs[:, :, _O_CLK],
    }

    if st.telemetry:
        # ---------------------------- in-scan flight-recorder telemetry:
        # a compact per-step block widening the carry (decision-gap step
        # deltas as the pick-latency proxy, per-run fallback/non-finite/
        # fit-skip counts, per-run compliance margin) materialized into ys
        # at run boundaries and replayed into the recorder at write-back
        # (see ``replay_spans``).  Pure observation: nothing below feeds
        # back into the decision or training ops above.
        i32 = jnp.int32
        tel = carry["tel"]
        gap_valid = decide & (tel["last_dec_t"] >= 0)
        gap = jnp.where(gap_valid, t - tel["last_dec_t"], 0).astype(i32)
        last_dec_t = jnp.where(decide, t, tel["last_dec_t"]).astype(i32)
        run_fb = tel["run_fallbacks"] + fb_used.astype(i32)
        run_nf = tel["run_nonfinite"] + nonfin.astype(i32)
        run_fs = tel["run_fit_skip"] + fit_skip
        zero = jnp.zeros_like(run_fb)
        new_carry["tel"] = {
            "last_dec_t": last_dec_t,
            "run_fallbacks": jnp.where(is_last, zero, run_fb),
            "run_nonfinite": jnp.where(is_last, zero, run_nf),
            "run_fit_skip": jnp.where(is_last, zero, run_fs),
            "gap_sum": tel["gap_sum"] + gap,
            "gap_n": tel["gap_n"] + gap_valid.astype(i32),
        }
        ys.update(
            tel_dec_gap=gap,
            tel_margin=jnp.where(is_last, dev["target"] - clock, f32(0.0)),
            tel_run_fallbacks=jnp.where(is_last, run_fb, zero),
            tel_run_nonfinite=jnp.where(is_last, run_nf, zero),
            tel_run_fit_skip=jnp.where(is_last, run_fs, zero),
        )
    return new_carry, ys


def _scan_impl(st, dev, carry, ts):
    return jax.lax.scan(lambda c, t: _step(st, dev, c, t), carry, ts)


_SCAN_JIT = jax.jit(_scan_impl, static_argnums=(0,))
_STEP_JIT = jax.jit(_step, static_argnums=(0,))


# =========================================================================
# drivers
# =========================================================================

def init_carry(plan: CampaignPlan):
    return jax.tree_util.tree_map(jnp.asarray, plan.init)


def run_fused(plan: CampaignPlan, carry=None, start: int = 0,
              stop: Optional[int] = None):
    """Scan steps [start, stop) in ONE dispatch -> (final carry, ys)."""
    if carry is None:
        carry = init_carry(plan)
    if stop is None:
        stop = plan.n_steps
    ts = jnp.arange(start, stop, dtype=jnp.int32)
    return _SCAN_JIT(plan.static, plan.dev, carry, ts)


def run_stepped(plan: CampaignPlan, carry=None, start: int = 0,
                stop: Optional[int] = None):
    """Python loop over the SAME jitted step body (parity comparator /
    incremental driver); returns ys stacked exactly like the scan's."""
    if carry is None:
        carry = init_carry(plan)
    if stop is None:
        stop = plan.n_steps
    ys_steps = []
    for t in range(start, stop):
        carry, y = _STEP_JIT(plan.static, plan.dev, carry, jnp.int32(t))
        ys_steps.append(y)
    ys = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ys_steps)
    return carry, ys


def carry_to_host(carry) -> Dict[str, Any]:
    """Picklable numpy copy of a scan carry (mid-campaign checkpoint)."""
    return jax.tree_util.tree_map(np.asarray, carry)


def carry_from_host(carry) -> Dict[str, Any]:
    return jax.tree_util.tree_map(jnp.asarray, carry)


def replay_spans(plan: CampaignPlan, ys, start: int = 0,
                 recorder=None) -> int:
    """Replay a fused-campaign ys block into the flight recorder.

    A pure function of ``(plan, ys)``: the span stream depends only on the
    materialized scan outputs, so ``run_fused`` and ``run_stepped`` of the
    same plan replay to IDENTICAL ``(kind, attrs)`` streams (parity-tested
    in ``tests/test_obs.py``).  Timestamps are the *logical* step index
    (not wall time).  Returns the number of spans emitted.

    Span kinds mirror the live stepped path where an in-scan analogue
    exists: ``decision.pick`` per decided job (with the step-delta pick
    latency proxy), ``decision.fallback`` for guardrail-clamped picks,
    ``fit`` at run boundaries and ``run.end`` with the per-run compliance
    margin + fallback/non-finite/fit-skip counts from the in-scan
    telemetry block (plans built with ``telemetry=False`` have no tel
    arrays, so only the base decision/fit spans replay).
    """
    from repro import obs as _obs
    if recorder is None:
        recorder = _obs.recorder()
    if not _obs.enabled():
        return 0
    h = plan.host
    ysn = {k: np.asarray(v) for k, v in ys.items()}
    c_max = plan.static.c_max
    names = h["job_names"]
    scratch_at = h.get("scratch_at")
    n = 0
    for i in range(ysn["decided"].shape[0]):
        t = start + i
        r, k = divmod(t, c_max)
        decided = ysn["decided"][i]
        for j, name in enumerate(names):
            if decided[j]:
                attrs = dict(driver="fused", job=name, run=r, comp=k,
                             scaleout=int(ysn["s_next"][i, j]),
                             fallback=bool(ysn["fallback"][i, j]))
                if "tel_dec_gap" in ysn:
                    attrs["gap_steps"] = int(ysn["tel_dec_gap"][i, j])
                recorder.emit("decision.pick", _ts=float(t), **attrs)
                n += 1
                if attrs["fallback"]:
                    recorder.emit(
                        "decision.fallback", _ts=float(t), driver="fused",
                        job=name, run=r, comp=k, cause="guardrail",
                        nonfinite=bool(ysn["nonfinite"][i, j]))
                    n += 1
        if k == c_max - 1:                      # run boundary: fit + run.end
            scratch = bool(scratch_at[r]) if scratch_at is not None and \
                r < len(scratch_at) else False
            for j, name in enumerate(names):
                recorder.emit(
                    "fit", _ts=float(t), driver="fused", job=name, run=r,
                    mode="scratch" if scratch else "tune",
                    skipped=int(ysn["fit_skipped"][i, j]),
                    loss=round(float(ysn["fit_loss"][i, j]), 6))
                n += 1
                if "tel_margin" in ysn:
                    recorder.emit(
                        "run.end", _ts=float(t), driver="fused", job=name,
                        run=r, clock=round(float(ysn["clock"][i, j]), 4),
                        margin=round(float(ysn["tel_margin"][i, j]), 4),
                        fallbacks=int(ysn["tel_run_fallbacks"][i, j]),
                        nonfinite=int(ysn["tel_run_nonfinite"][i, j]),
                        fit_skipped=int(ysn["tel_run_fit_skip"][i, j]))
                    n += 1
    return n


# =========================================================================
# plan construction (host side, once per campaign)
# =========================================================================

def _class_tables(exp, c_max: int, s_max: int, k_pad: int,
                  e_pad: int) -> Dict[str, np.ndarray]:
    """Structural tables shared by every experiment of one job class:
    frozen observation contexts, ring-row node layout and the sweep's
    candidate-invariant graph structure (fixed slot layout: stages 0..n-1,
    P at n — masked unless next component — and H at n+1; masked slots
    contribute exact zeros in the sparse sweep, so the fixed layout is
    functionally identical to the live path's compaction)."""
    from repro.dataflow.runner import frozen_context_tables
    job = exp.job
    ctx, n_stages = frozen_context_tables(exp.encoder, job)
    n_comp, s_loc, ns = ctx.shape[0], ctx.shape[1], ctx.shape[2]
    obs = np.zeros((c_max, s_max, ns, CTX_DIM), np.float32)
    obs[:n_comp, :s_loc] = ctx
    nst = np.zeros(c_max, np.int32)
    nst[:n_comp] = n_stages
    p_ctx = np.zeros((c_max, ns, CTX_DIM), np.float32)
    for c in range(n_comp):
        p_ctx[c] = ctx[c, :n_stages[c]].mean(axis=0)

    row_mask = np.zeros((c_max, N_ROW), bool)
    row_summ = np.zeros((c_max, N_ROW), bool)
    row_st = np.zeros((c_max, N_ROW), bool)
    row_p = np.zeros((c_max, N_ROW), bool)
    row_h = np.zeros((c_max, N_ROW), bool)
    row_si = np.zeros((c_max, N_ROW), np.int32)
    row_adj = np.zeros((c_max, N_ROW, N_ROW), bool)
    for c in range(n_comp):
        n = int(n_stages[c])
        row_mask[c, :n] = True
        row_st[c, :n] = True
        row_si[c, :n] = np.arange(n)
        for i in range(n - 1):
            row_adj[c, i + 1, i] = True
        if c > 0:                     # P(k-1) and H(k-1) predecessor slots
            row_mask[c, n:n + 2] = True
            row_summ[c, n:n + 2] = True
            row_p[c, n] = True
            row_h[c, n + 1] = True
            row_adj[c, 0, n] = True
            row_adj[c, 0, n + 1] = True

    sw_mask0 = np.zeros((k_pad, N_ROW), bool)
    sw_summ = np.zeros((k_pad, N_ROW), bool)
    sw_st = np.zeros((k_pad, N_ROW), bool)
    sw_p = np.zeros((k_pad, N_ROW), bool)
    sw_h = np.zeros((k_pad, N_ROW), bool)
    sw_si = np.zeros((k_pad, N_ROW), np.int32)
    sw_oh = np.zeros((k_pad, N_ROW), np.float32)
    sw_adj = np.zeros((k_pad, N_ROW, N_ROW), bool)
    sw_ed = np.zeros((k_pad, e_pad), np.int32)
    sw_es = np.zeros((k_pad, e_pad), np.int32)
    sw_ev = np.zeros((k_pad, e_pad), bool)
    depth = 1
    for ki in range(k_pad):
        c = ki + 1
        if c >= n_comp:
            continue
        n = int(n_stages[c])
        assert n + 2 <= N_ROW, "sweep slots overflow the node bucket"
        sw_mask0[ki, :n + 2] = True
        sw_st[ki, :n] = True
        sw_si[ki, :n] = np.arange(n)
        sw_summ[ki, n:n + 2] = True
        sw_p[ki, n] = True
        sw_h[ki, n + 1] = True
        sw_oh[ki, n + 1] = 1.0
        adj = np.zeros((N_ROW, N_ROW), bool)
        for i in range(n - 1):
            adj[i + 1, i] = True
        adj[0, n] = True
        adj[0, n + 1] = True
        sw_adj[ki] = adj
        pairs = np.argwhere(adj)               # (m, 2): [dst, src], the
        m = len(pairs)                         # live sweep_edge_list order
        assert m <= e_pad, "edge bucket overflow"
        sw_ed[ki, :m] = pairs[:, 0]
        sw_es[ki, :m] = pairs[:, 1]
        sw_ev[ki, :m] = True
        depth = max(depth, propagation_depth(adj, sw_mask0[ki]))
    return {
        "obs_ctx": obs, "p_ctx": p_ctx, "n_stage": nst,
        "row_mask": row_mask, "row_summ": row_summ, "row_is_stage": row_st,
        "row_is_p": row_p, "row_is_h": row_h, "row_stage_idx": row_si,
        "row_adj": row_adj,
        "sw_mask0": sw_mask0, "sw_summ": sw_summ, "sw_is_stage": sw_st,
        "sw_is_p": sw_p, "sw_is_h": sw_h, "sw_stage_idx": sw_si,
        "sw_oh": sw_oh, "sw_adj": sw_adj, "sw_edge_dst": sw_ed,
        "sw_edge_src": sw_es, "sw_edge_val": sw_ev,
        "depth": np.int32(depth),
    }


def _hist_tables(exp, c_max: int, k_pad: int, grid: np.ndarray,
                 cand: np.ndarray) -> Dict[str, np.ndarray]:
    """Frozen historical-summary tables: per component k, the H(k-1) node
    attributes at every grid scale-out (ring rows) and at every candidate
    (sweep deltas).  Matches the live ranking exactly at plan time; the
    live history keeps growing afterwards (documented deviation)."""
    beta = exp.enel.beta
    ns, c_pad = len(grid), len(cand)
    n_comp = exp.job.n_components
    hob_ctx = np.zeros((c_max, ns, CTX_DIM), np.float32)
    hob_met = np.zeros((c_max, ns, N_METRICS), np.float32)
    hob_val = np.zeros((c_max, ns), bool)
    hob_start = np.ones((c_max, ns), np.float32)
    hob_end = np.ones((c_max, ns), np.float32)
    for k in range(1, n_comp):
        hl = exp.enel.hist_summaries.get(k - 1, [])
        if not hl:
            raise ValueError(
                f"no history for component {k - 1} of {exp.job.name} — "
                "run profile() before building a fused campaign plan")
        hb = historical_summaries_batch(hl, grid, beta)
        hob_ctx[k] = hb["context"]
        hob_met[k] = hb["metrics"]
        hob_val[k] = hb["metrics_valid"]
        hob_start[k] = np.maximum(hb["start"], 1e-6)
        hob_end[k] = np.maximum(hb["end"], 1e-6)
    hsw_ctx = np.zeros((c_pad, k_pad, CTX_DIM), np.float32)
    hsw_met = np.zeros((c_pad, k_pad, N_METRICS), np.float32)
    hsw_val = np.zeros((c_pad, k_pad), bool)
    hsw_start = np.ones((c_pad, k_pad), np.float32)
    hsw_end = np.ones((c_pad, k_pad), np.float32)
    for ki in range(k_pad):
        c = ki + 1
        if c >= n_comp:
            continue
        hl = exp.enel.hist_summaries.get(c - 1, [])
        if not hl:
            raise ValueError(
                f"no history for component {c - 1} of {exp.job.name} — "
                "run profile() before building a fused campaign plan")
        hb = historical_summaries_batch(hl, cand, beta)
        hsw_ctx[:, ki] = hb["context"]
        hsw_met[:, ki] = hb["metrics"]
        hsw_val[:, ki] = hb["metrics_valid"]
        hsw_start[:, ki] = np.maximum(hb["start"], 1e-6)
        hsw_end[:, ki] = np.maximum(hb["end"], 1e-6)
    return {"hob_ctx": hob_ctx, "hob_met": hob_met, "hob_val": hob_val,
            "hob_start": hob_start, "hob_end": hob_end,
            "hsw_ctx": hsw_ctx, "hsw_met": hsw_met, "hsw_val": hsw_val,
            "hsw_start": hsw_start, "hsw_end": hsw_end}


def build_plan(experiments, n_runs: int, *, inject_failures: bool = False,
               retrain_every: int = 5, steps: int = 160,
               fine_tune_steps: int = 60,
               metric_dropout: float = 0.5,
               telemetry: Optional[bool] = None) -> CampaignPlan:
    """Compile a fused whole-campaign plan for ``n_runs`` adaptive runs of
    a profiled fleet sharing one :class:`BatchedClusterSim`.

    Consumes the backend's RNG streams exactly as ``n_runs`` stepped runs
    would (via :meth:`campaign_run_blocks`), so a fused campaign and a
    stepped campaign from the same seed state see identical draws.  Raises
    on configurations the in-scan path cannot honour (unprofiled jobs,
    host-side chaos families, capacity caps, non-uniform trainer cadence).
    """
    exps = list(experiments)
    if not exps:
        raise ValueError("empty fleet")
    backend = exps[0].backend
    if not isinstance(backend, BatchedClusterSim):
        raise TypeError("fused campaigns need the batched sim engine "
                        "(FleetCampaign(..., engine='batched'))")
    for i, e in enumerate(exps):
        if e.backend is not backend:
            raise ValueError("all experiments must share ONE backend")
        if e.sim_slot != i:
            raise ValueError("experiment order must match sim slots")
        if e.target is None:
            raise ValueError(f"{e.job.name}: profile() first")
        cache = e.trainer.cache
        if cache is None or cache.count == 0:
            raise ValueError(f"{e.job.name}: empty training ring")
        if cache.max_nodes != N_ROW:
            raise ValueError(f"ring rows have {cache.max_nodes} node "
                             f"slots, fused kernel needs {N_ROW}")
        if e.scale_cap is not None:
            raise ValueError("capacity caps are a host-path feature")
        if e.chaos is not None and (e.chaos.spec.nan_graphs_every
                                    or e.chaos.spec.cache_corrupt_every):
            raise ValueError("only nan_fit chaos runs in-scan; "
                             "nan_graphs/cache_corrupt mutate host caches")
    J = len(exps)
    lo, hi = exps[0].enel.range
    stride = exps[0].enel.candidate_stride
    cap = exps[0].trainer.cache.capacity
    runs_seen0 = exps[0].trainer.runs_seen
    for e in exps:
        if e.enel.range != (lo, hi) or \
                e.enel.candidate_stride != stride:
            raise ValueError("candidate grids must be uniform")
        if e.trainer.cache.capacity != cap:
            raise ValueError("ring capacities must be uniform")
        if e.trainer.runs_seen != runs_seen0:
            raise ValueError("trainer cadence must be uniform "
                             "(equal runs_seen)")

    grid_c = sorted(set(range(lo, hi + 1, stride)) | {hi})
    c_real = len(grid_c)
    c_pad = ladder_bucket(c_real, CAND_LADDER)
    cand = np.full(c_pad, grid_c[-1], np.float32)
    cand[:c_real] = grid_c
    cand_valid = np.zeros(c_pad, bool)
    cand_valid[:c_real] = True
    grid_all = np.arange(lo, hi + 1, dtype=np.float32)

    const = backend.fused_sim_constants()
    s_max = int(const["s_max"])
    c_max = max(e.job.n_components for e in exps)
    k_pad = ladder_bucket(max(c_max - 1, 1), COMP_LADDER)
    e_pad = ladder_bucket(s_max + 1, EDGE_LADDER)

    # ---- structural tables, deduplicated per job class
    cls_of: Dict[tuple, int] = {}
    classes: List[Dict[str, np.ndarray]] = []
    cls = np.zeros(J, np.int32)
    for i, e in enumerate(exps):
        key = (e.job.name, e.seed, e.job.n_components,
               tuple(len(e.job.stages(c))
                     for c in range(e.job.n_components)))
        if key not in cls_of:
            cls_of[key] = len(classes)
            classes.append(_class_tables(e, c_max, s_max, k_pad, e_pad))
        cls[i] = cls_of[key]
    depth = max(int(c["depth"]) for c in classes)
    levels = ladder_bucket(depth, LEVEL_LADDER)

    # ---- frozen history tables, deduplicated per (job, seed, progress)
    h_of: Dict[tuple, int] = {}
    hists: List[Dict[str, np.ndarray]] = []
    hcls = np.zeros(J, np.int32)
    for i, e in enumerate(exps):
        key = (e.job.name, e.seed, e._run_idx, e.trainer.runs_seen,
               tuple(len(e.enel.hist_summaries.get(c, []))
                     for c in range(e.job.n_components)))
        if key not in h_of:
            h_of[key] = len(hists)
            hists.append(_hist_tables(e, c_max, k_pad, grid_all, cand))
        hcls[i] = h_of[key]

    # ---- per-job schedule tables
    n_comp = np.array([e.job.n_components for e in exps], np.int32)
    comp_valid = np.zeros((c_max, J), bool)
    decide_tab = np.zeros((c_max, J), bool)
    n_stage_f = np.zeros((c_max, J), np.float32)
    cursor_f = np.zeros((c_max, J), np.float32)
    for i, e in enumerate(exps):
        nc = e.job.n_components
        comp_valid[:nc, i] = True
        for k in range(nc):
            decide_tab[k, i] = (k < nc - 1
                                and k % e.decision_interval == 0)
        tab = backend._slots[i].tables
        n_stage_f[:nc, i] = tab.n_stages
        cursor_f[:nc, i] = tab.comp_start
        cursor_f[nc:, i] = tab.total_stages
    any_decide = decide_tab.any(axis=1)

    # ---- fixed s0 (exact under method="enel": Ellis never refits during
    # adaptive runs, so its recommendation is constant across the campaign)
    s0 = np.zeros(J, np.float32)
    predicted = []
    for i, e in enumerate(exps):
        rec, p_hat = e.ellis.recommend(
            next_comp=0, n_components=e.job.n_components, elapsed=0.0,
            current_scaleout=lo, target_runtime=e.target)
        s0[i] = rec
        predicted.append(p_hat)
    inject = np.array(
        [float(bool(inject_failures) or e.scenario.inject_failures)
         for e in exps], np.float32)
    target = np.array([e.target for e in exps], np.float32)

    # ---- fit cadence / chaos schedules
    scratch_at = np.array(
        [((runs_seen0 + r + 1) % retrain_every) == 0
         for r in range(n_runs)], bool)
    poison_at = np.zeros((n_runs, J), bool)
    for i, e in enumerate(exps):
        if e.chaos is not None and e.chaos.spec.nan_fit_every:
            for r in range(n_runs):
                poison_at[r, i] = e.chaos._fires(
                    e.chaos.spec.nan_fit_every, e._run_idx + r + 1)

    # ---- learned state (stacked along the job axis)
    stack = lambda trees: jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *trees)
    params0 = stack([e.trainer.params for e in exps])
    opt0 = stack([e.trainer.opt for e in exps])
    fit_calls = np.array([e.trainer._fit_calls for e in exps], np.int32)
    base_key = np.stack(
        [np.asarray(jax.random.PRNGKey(e.seed ^ 0x5eed)) for e in exps])
    init_params = stack(
        [init_enel(jax.random.PRNGKey(e.seed)) for e in exps])
    lr = np.array([e.trainer.lr for e in exps], np.float32)

    snaps = [e.trainer.cache.snapshot() for e in exps]
    ring0 = {
        "buffers": {kk: np.stack([s["buffers"][kk] for s in snaps])
                    for kk in snaps[0]["buffers"]},
        "pos": np.array([s["pos"] for s in snaps], np.int32),
        "count": np.array([s["count"] for s in snaps], np.int32),
        "slot_ok": np.stack([s["slot_ok"] for s in snaps]),
    }
    interf0 = np.array(
        [backend.slot_state(i)["interf"] for i in range(J)], np.float32)

    # LAST: consume the backend RNG streams for the whole campaign
    blocks, kills = backend.campaign_run_blocks(n_runs)

    gather = lambda key_: jnp.asarray(
        np.stack([c[key_] for c in classes]))
    hgather = lambda key_: jnp.asarray(
        np.stack([hh[key_] for hh in hists]))
    dev = {
        "blocks": jnp.asarray(blocks), "kills": jnp.asarray(kills),
        "burst": const["burst"], "preempt": const["preempt"],
        "iscale2": const["iscale2"], "mem_tab": const["mem_tab"],
        "shuf_tab": const["shuf_tab"],
        "cand": jnp.asarray(cand), "cand_valid": jnp.asarray(cand_valid),
        "inject": jnp.asarray(inject), "target": jnp.asarray(target),
        "s0": jnp.asarray(s0), "n_comp": jnp.asarray(n_comp),
        "comp_valid": jnp.asarray(comp_valid),
        "decide_tab": jnp.asarray(decide_tab),
        "any_decide": jnp.asarray(any_decide),
        "n_stage_f": jnp.asarray(n_stage_f),
        "cursor_f": jnp.asarray(cursor_f),
        "cls": jnp.asarray(cls), "hcls": jnp.asarray(hcls),
        "sw_comp": jnp.arange(1, k_pad + 1, dtype=jnp.int32),
        "obs_ctx": gather("obs_ctx"), "p_ctx": gather("p_ctx"),
        "row_mask": gather("row_mask"), "row_summ": gather("row_summ"),
        "row_is_stage": gather("row_is_stage"),
        "row_is_p": gather("row_is_p"), "row_is_h": gather("row_is_h"),
        "row_stage_idx": gather("row_stage_idx"),
        "row_adj": gather("row_adj"),
        "sw_mask0": gather("sw_mask0"), "sw_summ": gather("sw_summ"),
        "sw_is_stage": gather("sw_is_stage"),
        "sw_is_p": gather("sw_is_p"), "sw_is_h": gather("sw_is_h"),
        "sw_stage_idx": gather("sw_stage_idx"), "sw_oh": gather("sw_oh"),
        "sw_adj": gather("sw_adj"),
        "sw_edge_dst": gather("sw_edge_dst"),
        "sw_edge_src": gather("sw_edge_src"),
        "sw_edge_val": gather("sw_edge_val"),
        "hob_ctx": hgather("hob_ctx"), "hob_met": hgather("hob_met"),
        "hob_val": hgather("hob_val"),
        "hob_start": hgather("hob_start"), "hob_end": hgather("hob_end"),
        "hsw_ctx": hgather("hsw_ctx"), "hsw_met": hgather("hsw_met"),
        "hsw_val": hgather("hsw_val"),
        "hsw_start": hgather("hsw_start"), "hsw_end": hgather("hsw_end"),
        "init_params": init_params, "base_key": jnp.asarray(base_key),
        "lr": jnp.asarray(lr),
        "dropout_p": jnp.float32(metric_dropout),
        "scratch_at": jnp.asarray(scratch_at),
        "poison_at": jnp.asarray(poison_at),
    }
    init = {
        "clock": np.zeros(J, np.float32), "interf": interf0,
        "s_prev": s0.copy(), "s_cur": s0.copy(),
        "p_met": np.zeros((J, N_METRICS), np.float32),
        "p_a": np.ones(J, np.float32), "p_z": np.ones(J, np.float32),
        "ring": ring0,
        "params": params0, "opt": opt0,
        "fit_calls": fit_calls,
        "fallbacks": np.zeros(J, np.int32),
        "nonfinite": np.zeros(J, np.int32),
    }
    if telemetry is None:
        from repro import obs as _obs
        telemetry = _obs.enabled()
    if telemetry:
        init["tel"] = {
            "last_dec_t": np.full(J, -1, np.int32),
            "run_fallbacks": np.zeros(J, np.int32),
            "run_nonfinite": np.zeros(J, np.int32),
            "run_fit_skip": np.zeros(J, np.int32),
            "gap_sum": np.zeros(J, np.int32),
            "gap_n": np.zeros(J, np.int32),
        }
    static = PlanStatic(
        c_max=c_max, s_max=s_max, lo=lo, tune_rows=pow2_bucket(c_max),
        scratch_steps=_round_steps(steps),
        tune_steps=_round_steps(fine_tune_steps),
        retrain_every=retrain_every, levels=levels,
        telemetry=bool(telemetry))
    host = {
        "predicted": predicted, "targets": target.copy(),
        "n_comp": n_comp.copy(), "decide_tab": decide_tab.copy(),
        "comp_valid": comp_valid.copy(),
        "n_stage": n_stage_f.astype(np.int32),
        "s0": s0.astype(np.int32),
        "job_names": [e.job.name for e in exps],
        "run_idx0": [e._run_idx for e in exps],
        "n_runs": int(n_runs),
        "scratch_at": scratch_at.copy(),
    }
    return CampaignPlan(static, dev, init, host)
