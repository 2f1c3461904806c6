"""Controller flight recorder + metrics registry (ISSUE 9).

Layers under test:

* registry units: fixed-bucket histogram quantiles, merge-restore
  semantics, Prometheus/JSONL exporters;
* recorder units: bounded ring, gating, span lookup and causal links;
* attribute-API compatibility: the service/trainer/cache counters moved
  into the registry behind their original attributes;
* S6 regression: a service checkpoint taken while the breaker is OPEN
  restores breaker state AND the registry's metric labels identically;
* neutrality: with observability disabled a campaign's decisions are
  bit-exact vs the enabled twin and the timed reruns add zero jit traces;
* fused == stepped span parity: replaying the two drivers' (bit-exact)
  telemetry outputs yields identical span streams;
* host spans: nesting and ``parent``, self time, phase spans kept out of
  the ring, the gate, and the two latency histograms they replaced;
* the open-loop serving loop's span, histograms and stack-memo counters
  add up over a schedule, and turning observability off changes no
  decision.
"""
import json
import math
import time

import numpy as np
import pytest

import repro.core.campaign_kernel as ck
from repro import obs
from repro.core import model as enel_model
from repro.core.service import CircuitBreaker, DecisionService
from repro.dataflow import FleetCampaign, JobExperiment
from repro.obs.metrics import (DEFAULT_LATENCY_BUCKETS, HistogramSeries,
                               MetricsRegistry)
from repro.obs.recorder import FlightRecorder


# ---------------------------------------------------------------- registry

def test_histogram_quantiles_without_samples():
    h = HistogramSeries(buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.6, 3.0, 3.5, 7.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 6 and abs(s["sum"] - 17.1) < 1e-9
    assert s["min"] == 0.5 and s["max"] == 7.0
    # quantiles interpolate inside the owning bucket, clamped to [min,max]
    assert 1.0 <= s["p50"] <= 4.0
    assert s["p95"] <= 7.0 and s["p99"] <= 7.0
    h.observe(float("nan"))             # non-finite observations are dropped
    assert h.count == 6
    empty = HistogramSeries(buckets=DEFAULT_LATENCY_BUCKETS)
    assert math.isnan(empty.quantile(0.5))


def test_registry_merge_restore():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help").labels(svc="a")
    c.inc(3)
    snap = reg.snapshot()
    c.inc(2)                                     # diverge after snapshot
    reg.counter("t_total").labels(svc="b").inc(7)  # series born later
    reg.gauge("t_new").labels().set(1.0)           # metric born later
    reg.restore(snap)
    assert reg.counter("t_total").labels(svc="a").value == 3  # rewound
    assert reg.counter("t_total").labels(svc="b").value == 7  # untouched
    assert reg.gauge("t_new").labels().value == 1.0           # untouched
    with pytest.raises(ValueError):
        reg.gauge("t_total")                     # kind collision is loud


def test_prometheus_text_exporter():
    reg = MetricsRegistry()
    reg.counter("x_total", "things").labels(job="a").inc(2)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0)).labels(svc="s")
    h.observe(0.05)
    h.observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE x_total counter" in text
    assert 'x_total{job="a"} 2' in text
    assert 'lat_seconds_bucket{le="0.1",svc="s"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf",svc="s"} 2' in text
    assert 'lat_seconds_count{svc="s"} 2' in text


# ---------------------------------------------------------------- recorder

def test_recorder_ring_gating_and_jsonl(tmp_path):
    gate = {"on": True}
    rec = FlightRecorder(capacity=4, gate=lambda: gate["on"])
    seqs = [rec.emit("k", i=i) for i in range(6)]
    assert len(rec) == 4 and rec.dropped == 2
    assert rec.find(seqs[0]) is None             # evicted
    assert rec.find(seqs[-1])["attrs"]["i"] == 5
    gate["on"] = False
    assert rec.emit("k", i=99) == -1 and len(rec) == 4
    gate["on"] = True
    path = tmp_path / "spans.jsonl"
    text = rec.to_jsonl(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 4 and lines[-1]["attrs"]["i"] == 5
    assert text.count("\n") == 4
    st = rec.state()
    rec2 = FlightRecorder(capacity=4)
    rec2.load(st)
    assert rec2.stream() == rec.stream()


# ------------------------------------------------- attribute-API counters

def test_service_counters_attribute_api():
    svc = DecisionService(obs_name="t_api")
    svc.decisions += 5
    svc.retries += 2
    assert svc.decisions == 5 and svc.retries == 2
    st = svc.stats()
    assert st["decisions"] == 5 and st["retries"] == 2
    assert st["breaker_state"] == "closed"
    rows = obs.registry().rows(prefix="enel_service_decisions_total")
    assert any(r["labels"] == {"service": "t_api"} and r["value"] == 5
               for r in rows)


def test_breaker_mid_open_checkpoint_restores_state_and_labels():
    """S6: checkpoint while the breaker is OPEN -> restore into a fresh
    service with the same obs label; breaker state, counters AND registry
    series (same labels) all match the moment of the snapshot."""
    svc = DecisionService(obs_name="t_s6")
    for _ in range(svc.breaker.threshold):
        svc.breaker.record(False)
    svc.dispatch_failures += 4
    assert svc.breaker.state == CircuitBreaker.OPEN
    snap = svc.snapshot_state()
    trips0 = svc.breaker.trips

    twin = DecisionService(obs_name="t_s6")      # fresh, label-identical
    assert twin.breaker.state == CircuitBreaker.CLOSED
    twin.restore_state(snap)
    assert twin.breaker.state == CircuitBreaker.OPEN
    assert twin.breaker.trips == trips0
    assert twin.dispatch_failures == 4
    # the one-hot state gauge tracks the restored state under the SAME label
    gauge = obs.registry().get("enel_breaker_state")
    assert gauge.labels(service="t_s6", state="open").value == 1.0
    assert gauge.labels(service="t_s6", state="closed").value == 0.0
    rows = obs.registry().rows(prefix="enel_breaker_trips_total")
    assert any(r["labels"] == {"service": "t_s6"} and r["value"] == trips0
               for r in rows)


def test_obs_snapshot_roundtrips_registry_and_recorder():
    obs.observe("t_rt_seconds", 0.2, phase="x")
    seq = obs.emit("t.span", a=1)
    snap = obs.snapshot()
    assert isinstance(json.dumps(snap, default=str), str)  # pickle/json safe
    obs.observe("t_rt_seconds", 0.9, phase="x")
    obs.restore(snap)
    h = obs.registry().get("t_rt_seconds").labels(phase="x")
    assert h.count == 1                          # rewound to snapshot
    if seq >= 0:
        assert obs.recorder().find(seq) is not None


# ------------------------------------------------------ campaign neutrality

def _twin_campaign(n_profile=2):
    exps = [JobExperiment(k, seed=50 + i, candidate_stride=4)
            for i, k in enumerate(("lr", "kmeans", "gbt"))]
    camp = FleetCampaign(exps, DecisionService(seed=3), engine="batched")
    camp.profile(n_profile)
    return camp


def _decision_trace(all_stats):
    return [(round(s.runtime, 6), tuple(s.scaleouts), round(s.violation, 6),
             s.fallback_decisions, s.n_rescales)
            for run in all_stats for s in run]


@pytest.mark.slow
def test_disabled_obs_is_bit_exact_and_trace_neutral():
    """ENEL_OBS=0 contract on a 3-job stepped campaign: identical decision
    trace, and the disabled run adds exactly as many jit traces as an
    enabled twin on the warmed caches (i.e. zero extra)."""
    with obs.obs_enabled(True):
        stats_on, _ = _twin_campaign().adaptive_campaign(2, "enel", False)
    before = dict(enel_model.TRACE_COUNTS)
    with obs.obs_enabled(False):
        stats_off, _ = _twin_campaign().adaptive_campaign(2, "enel", False)
    delta_off = {k: v - before.get(k, 0)
                 for k, v in enel_model.TRACE_COUNTS.items()
                 if v - before.get(k, 0)}
    before = dict(enel_model.TRACE_COUNTS)
    with obs.obs_enabled(True):
        stats_on2, _ = _twin_campaign().adaptive_campaign(2, "enel", False)
    delta_on = {k: v - before.get(k, 0)
                for k, v in enel_model.TRACE_COUNTS.items()
                if v - before.get(k, 0)}
    assert _decision_trace(stats_off) == _decision_trace(stats_on)
    assert _decision_trace(stats_on2) == _decision_trace(stats_on)
    assert delta_off == delta_on        # disabling adds/removes no compiles


@pytest.mark.slow
def test_fused_telemetry_off_bit_exact():
    """The telemetry=False plan compiles the pre-observability jaxpr: same
    decisions/clocks as the telemetry=True twin, no tel_* outputs, and
    reruns add zero traces."""
    import jax
    p1 = ck.build_plan(_twin_campaign().experiments, 2, telemetry=True)
    p0 = ck.build_plan(_twin_campaign().experiments, 2, telemetry=False)
    _, ys1 = ck.run_fused(p1)
    _, ys0 = ck.run_fused(p0)
    jax.block_until_ready((ys1, ys0))
    assert any(k.startswith("tel_") for k in ys1)
    assert not any(k.startswith("tel_") for k in ys0)
    np.testing.assert_array_equal(np.asarray(ys1["z"]), np.asarray(ys0["z"]))
    np.testing.assert_array_equal(np.asarray(ys1["decided"]),
                                  np.asarray(ys0["decided"]))
    np.testing.assert_array_equal(np.asarray(ys1["clock"]),
                                  np.asarray(ys0["clock"]))
    t0 = enel_model.trace_count("fused_campaign")
    jax.block_until_ready(ck.run_fused(p0)[1])
    jax.block_until_ready(ck.run_fused(p1)[1])
    assert enel_model.trace_count("fused_campaign") == t0


@pytest.mark.slow
def test_fused_vs_stepped_span_parity():
    """Replaying the fused and stepped drivers' telemetry yields identical
    (kind, attrs) span streams — the drivers are bit-exact, so the flight
    recorder must be too."""
    camp = _twin_campaign()
    plan = ck.build_plan(camp.experiments, 2, telemetry=True)
    _, ys_f = ck.run_fused(plan)
    _, ys_s = ck.run_stepped(plan)
    rec = obs.recorder()
    rec.clear()
    n_f = ck.replay_spans(plan, ys_f)
    stream_f = rec.stream()
    rec.clear()
    n_s = ck.replay_spans(plan, ys_s)
    stream_s = rec.stream()
    rec.clear()
    assert n_f == n_s and n_f > 0
    assert stream_f == stream_s
    kinds = {k for k, _ in stream_f}
    assert {"decision.pick", "fit", "run.end"} <= kinds


def test_fallback_spans_link_to_cause():
    """Every decision.fallback span names its cause and links to the
    causing span (guardrail trip / dispatch fault / breaker transition)."""
    rec = obs.recorder()
    rec.clear()
    svc = DecisionService(obs_name="t_cause", max_retries=0)
    calls = {"n": 0}

    def chaos():
        calls["n"] += 1
        from repro.core.service import DispatchTimeout
        raise DispatchTimeout("injected")

    svc.fault_injector = chaos
    exp = JobExperiment("kmeans", seed=2, candidate_stride=4)
    exp.profile(2)
    from repro.dataflow.runner import _future_nodes, _to_graph
    builder = lambda ci, a, z, pr: _to_graph(
        _future_nodes(exp.encoder, exp.job, ci, a, z), pr, ci)
    req = exp.enel.prepare_request(
        graph_builder=builder, next_comp=1,
        n_components=exp.job.n_components, elapsed=10.0,
        current_scaleout=8, target_runtime=exp.target)
    svc.decide([req])
    falls = rec.events("decision.fallback")
    assert falls, "injected dispatch failure must produce fallback spans"
    for ev in falls:
        at = ev["attrs"]
        assert at["cause"] in ("guardrail", "breaker_open",
                               "retries_exhausted", "shed")
        if at["cause_seq"] >= 0:
            cause = rec.find(at["cause_seq"])
            assert cause is not None and cause["seq"] < ev["seq"]


# ------------------------------------------------------------- host spans

def _kmeans_request(exp=None):
    """A profiled kmeans tenant (``exp``, or a fresh one) and one request."""
    if exp is None:
        exp = JobExperiment("kmeans", seed=2, candidate_stride=4)
        exp.profile(2)
    from repro.dataflow.runner import _future_nodes, _to_graph
    builder = lambda ci, a, z, pr: _to_graph(
        _future_nodes(exp.encoder, exp.job, ci, a, z), pr, ci)
    req = exp.enel.prepare_request(
        graph_builder=builder, next_comp=1,
        n_components=exp.job.n_components, elapsed=10.0,
        current_scaleout=8, target_runtime=exp.target)
    return exp, req


def test_span_nesting_parent_and_self_time():
    rec = obs.recorder()
    rec.clear()
    with obs.obs_enabled(True):
        with obs.span("t.outer", _ring=True, a=1) as outer:
            with obs.span("t.phase"):            # profiler + histogram only
                inner_ev = obs.emit("t.event", x=2)
            with obs.span("t.inner", _ring=True) as inner:
                time.sleep(0.01)
                inner.set(hits=3)
            time.sleep(0.02)
        after = obs.emit("t.after")
    spans = {e["kind"]: e for e in rec.events() if "end" in e}
    assert set(spans) == {"t.outer", "t.inner"}     # t.phase stays out
    o, i = spans["t.outer"], spans["t.inner"]
    assert o["seq"] == outer.seq and i["seq"] == inner.seq
    assert o["parent"] == -1 and i["parent"] == o["seq"]
    assert rec.find(inner_ev)["parent"] == o["seq"]  # phase spans skipped
    assert rec.find(after)["parent"] == -1
    assert i["attrs"] == {"hits": 3} and o["attrs"] == {"a": 1}
    assert o["start"] <= i["start"] <= i["end"] <= o["end"]
    # self time: the outer span's own 20 ms sleep, its child's excluded
    self_s = (o["end"] - o["start"]) - (i["end"] - i["start"])
    assert 0.02 <= self_s < 0.2 and i["end"] - i["start"] >= 0.01
    hist = obs.registry().get("enel_span_seconds")
    for kind in ("t.outer", "t.inner", "t.phase"):
        assert hist.labels(span=kind).count >= 1


def test_disabled_obs_records_no_span():
    rec = obs.recorder()
    rec.clear()
    hist = obs.registry().histogram("enel_span_seconds")
    with obs.obs_enabled(False):
        with obs.span("t.off", _ring=True) as sp:
            assert obs.emit("t.off.event") == -1
            sp.set(n=1)
    assert sp.seq == -1 and len(rec) == 0
    assert hist.labels(span="t.off").count == 0
    assert obs.current_span() == -1


def test_disabled_obs_decide_is_bit_exact_and_trace_neutral():
    """ENEL_OBS=0 on the request path: twin tenants get the same pick and
    totals with and without observability, the disabled one leaves no
    recorder entry or span sample, and it adds no jit trace beyond what
    an enabled twin adds on the same warmed caches."""
    def decide_twin():
        counts0 = dict(enel_model.TRACE_COUNTS)
        _, req = _kmeans_request()
        res = DecisionService().decide([req])[0]
        delta = {k: v - counts0.get(k, 0)
                 for k, v in enel_model.TRACE_COUNTS.items()
                 if v != counts0.get(k, 0)}
        return (res.scaleout, res.predicted, res.totals), delta

    with obs.obs_enabled(True):
        decide_twin()                   # warm every shape
        on, delta_on = decide_twin()
    rec = obs.recorder()
    rec.clear()
    hist = obs.registry().histogram("enel_span_seconds")
    n0 = hist.labels(span="enel.decide").count
    with obs.obs_enabled(False):
        off, delta_off = decide_twin()
    assert off == on and delta_off == delta_on
    assert len(rec) == 0 and hist.labels(span="enel.decide").count == n0


def test_decision_latency_histogram_times_each_request():
    """``enel_decision_latency_seconds`` runs from a request's prep to the
    end of the decide() that answered it, not a share of decide()."""
    exp, req = _kmeans_request()
    _, req2 = _kmeans_request(exp)
    assert req2.rid > req.rid >= 0 and req.prepared_at is not None
    svc = DecisionService(obs_name="t_latency")
    svc.decide([req2])                  # warm the shapes
    time.sleep(0.05)
    t0 = time.perf_counter()
    with obs.obs_enabled(True):
        res = svc.decide([req])
    wall = time.perf_counter() - t0
    h = obs.registry().get("enel_decision_latency_seconds").labels(
        service="t_latency")
    assert h.count == 2
    assert h.vmax >= 0.05 + wall * 0.9          # waited before decide()
    assert res[0].service_seconds <= wall       # the share keeps its meaning


def test_fit_span_replaces_fit_seconds_histogram():
    exp = JobExperiment("lr", seed=4, candidate_stride=4)
    rec = obs.recorder()
    with obs.obs_enabled(True):
        rec.clear()
        exp.profile(2)                  # ends in a resident scratch fit
    assert obs.registry().get("enel_fit_seconds") is None
    fits = [e for e in rec.events("enel.fit")]
    assert fits and fits[-1]["attrs"]["mode"] == "scratch"
    assert fits[-1]["attrs"]["steps"] == 128
    ev = rec.events("fit")[-1]
    assert ev["parent"] == fits[-1]["seq"] and "seconds" not in ev["attrs"]
    assert obs.registry().get("enel_span_seconds").labels(
        span="enel.fit").count >= 1


def test_fallback_cause_resolves_after_a_live_unit():
    """A live-style unit of requests (three tenants, lockstep rounds, one
    adaptive run each) with the first dispatch failing: the ring keeps
    layer spans only, so the fallback's cause is still in it afterwards,
    and the fallback names its request and its enclosing decide() span."""
    from repro.core.service import DispatchTimeout
    rec = obs.recorder()
    with obs.obs_enabled(True):
        rec.clear()
        exps = [JobExperiment(k, seed=60 + i, candidate_stride=4)
                for i, k in enumerate(("lr", "kmeans", "gbt"))]
        svc = DecisionService(obs_name="t_unit", max_retries=0)
        camp = FleetCampaign(exps, svc, engine="batched")
        camp.profile(2)
        state = {"n": 0}

        def first_fails():
            state["n"] += 1
            if state["n"] == 1:
                raise DispatchTimeout("injected")

        svc.fault_injector = first_fails
        camp.adaptive_round()
    kinds = set(rec.span_counts())
    assert not kinds & {"enel.prep", "enel.prep.build", "enel.prep.adopt",
                        "enel.decide.stack", "enel.decide.launch",
                        "enel.decide.fetch", "enel.resume"}
    assert {"enel.round", "enel.sim_step", "enel.decide",
            "enel.fit"} <= kinds
    falls = rec.events("decision.fallback")
    assert falls and rec.dropped == 0
    for ev in falls:
        at = ev["attrs"]
        assert at["cause"] == "retries_exhausted" and at["rid"] >= 0
        cause = rec.find(at["cause_seq"])
        assert cause is not None and cause["kind"] == "dispatch.fault"
        assert rec.find(ev["parent"])["kind"] == "enel.decide"


class _FakeClock:
    """Time moves only when the serving loop sleeps."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds


def _open_schedule():
    """Three tenants, calm arrivals through one whole run (11 decisions),
    then a burst with each due twice."""
    calm = [(0.3 * d + 0.05 * i, i) for d in range(11) for i in range(3)]
    return calm + [(4.0, i) for i in range(3)] * 2


def test_serve_counters_add_up_and_obs_off_is_bit_exact():
    """Over a fake-clock schedule the ``enel.serve`` counters, the queue
    histograms and the stack-memo counters account for every arrival and
    dispatch; with ENEL_OBS=0 a twin fleet decides bit for bit alike."""
    sched = _open_schedule()
    rec = obs.recorder()
    rec.clear()
    reg = obs.registry()
    with obs.obs_enabled(True):
        camp = _twin_campaign()
        svc = camp.service
        lab = {"service": svc.obs_name}
        d0, lookups0, hits0 = svc.dispatches, svc.memo_lookups, svc.memo_hits
        arrivals = camp.serve_arrivals(sched, clock=_FakeClock())
        on = [[(s.runtime, tuple(s.scaleouts)) for s in camp.open_stats[i]]
              for i in range(3)]
    serves = [e["attrs"] for e in rec.events() if e["kind"] == "enel.serve"]
    assert sum(a["taken"] for a in serves) == len(sched) == len(arrivals)
    assert sum(a["groups"] for a in serves) == svc.dispatches - d0
    assert sum(a["sims"] for a in serves) > 0
    assert all(a["taken"] <= a["queued"] for a in serves)
    wait = reg.get("enel_queue_wait_seconds").labels(**lab)
    batch = reg.get("enel_decide_batch_requests").labels(**lab)
    assert wait.count == len(sched) and batch.sum == len(sched)
    assert batch.count == sum(a["taken"] > 0 for a in serves)
    assert wait.sum == pytest.approx(sum(a.taken - a.due for a in arrivals))
    lookups = svc.memo_lookups - lookups0
    assert lookups == 8 * (svc.dispatches - d0)     # 8 memoised fields
    assert 0 <= svc.memo_hits - hits0 <= lookups
    with obs.obs_enabled(False):
        twin = _twin_campaign()
        twin.serve_arrivals(sched, clock=_FakeClock())
        off = [[(s.runtime, tuple(s.scaleouts)) for s in twin.open_stats[i]]
               for i in range(3)]
    assert off == on and any(on)
