"""The open-arrivals cell at a size the CPU holds (8 tenants, the cell's own
widths, cadence and burst shape, short units).

* a unit's schedule holds exactly ``round(unit_seconds * mean_rate)``
  arrivals, 10% of its time in the burst, which takes its share of them
  on average; set-up's starting points are spread evenly over the decision
  points;
* a run's comparisons pass for the program and the float8 control (the
  plain reference with its products' operands rounded to float8) fails a
  limit;
* with the fit broken underneath (its state returned unchanged, a
  fine-tune alone left unchanged, half of the batch left out), a window
  that holds a scratch retrain and a fine-tune fails a fit's change gap;
* a result planted on the wrong tenant reads non-zero on
  ``misrouted_or_duplicate`` and the run comes out not correct.
"""
import time

import jax
import numpy as np
import pytest

import harness
import run
from checks import faults

BENCH = harness.load_benchmark()
CELL = harness.find_cell(BENCH, "arrivals-1k")
SMALL = {"config": {"tenants": 8},
         "traffic": {"unit_seconds": 1.0, "mean_rate": 6.0,
                     "warmup_seconds": 0.5}}


def _files():
    files = harness.cell_files(BENCH, CELL)
    for part, extra in SMALL.items():
        files[part] = dict(files[part], **extra)
    return files


def test_schedule_count_and_burst_share():
    mix = harness.cell_files(BENCH, CELL)["traffic"]
    drv_mod = harness.driver_module(mix["driver"])
    rng = np.random.default_rng(11)
    secs, burst = mix["unit_seconds"], mix["burst_seconds"]
    assert burst / secs == pytest.approx(0.1)
    n = int(round(secs * mix["mean_rate"]))
    in_burst = []
    for _ in range(200):
        t, start = drv_mod.burst_schedule(rng, n, secs, burst,
                                          mix["burst_factor"])
        assert len(t) == n and np.all(np.diff(t) >= 0)
        assert 0 <= t.min() and t.max() < secs and start + burst <= secs
        in_burst.append(np.sum((t >= start) & (t < start + burst)))
    # a 3x rate over 10% of the time takes 3 * 0.5 / (3 * 0.5 + 4.5) = 25%
    # of the arrivals on average (standard error 0.34% over 200 units of
    # 82), and the count is drawn, not fixed
    assert np.mean(in_burst) / n == pytest.approx(0.25, abs=0.015)
    assert len(set(in_burst)) > 1
    pos = drv_mod.balanced(rng, 256, 11)
    assert np.bincount(pos).min() == 256 // 11 and pos.max() == 10
    assert np.bincount(pos).max() == 256 // 11 + 1
    # fewer draws than values: each value as likely as any other
    firsts = [drv_mod.balanced(rng, 1, 5)[0] for _ in range(200)]
    assert set(firsts) == set(range(5))


@pytest.fixture(scope="module")
def small():
    """One set-up of the small cell, shared by the tests that run windows
    on it."""
    files = _files()
    drv = harness.driver_module(files["traffic"]["driver"]).Driver(
        files["config"], files["traffic"], 2 ** 31 + 7,
        harness.Spans(False))
    drv.setup()
    return drv, files


def test_unit_schedule_and_control_fails_a_limit(small):
    drv, files = small
    sched = drv.schedule(5.0, 6.0)
    assert len(sched) == 30 and {i for _, i in sched} <= set(range(8))
    drv.start_window()
    assert drv.unit() == 6 and drv.unit() == 6
    limits = files["limits"]
    prog = drv.check()
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert prog["unanswered"] == 0 and prog["misrouted_or_duplicate"] == 0
    assert drv.record["decisions"]["decisions"] == 12
    ctrl = drv.control()
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl


# the open-loop cell runs the fit the live cell does: the same faults
@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged",
                                   "tune_unchanged"])
def test_planted_fit_fault_is_not_correct(small, fault, monkeypatch):
    drv, files = small
    mod, attr, broken = faults.target("live_lockstep", fault)
    monkeypatch.setattr(mod, attr, broken)
    drv.start_window()
    for _ in range(60):     # until runs of both kinds have ended
        if {r["kind"] for r in drv.capture.fits} == {"scratch", "tune"}:
            break
        drv._unit(1.0, 40.0)
    drv.capture.decisions.clear()   # the test above checks those
    nums = drv.check()
    assert drv.record["worst_leaf"]["scratch_fits"] >= 1
    assert drv.record["worst_leaf"]["tune_fits"] >= 1
    gaps = {k: nums[k] for k in ("scratch_change_gap", "tune_change_gap")}
    assert any(v > files["limits"][k] for k, v in gaps.items()), gaps


def test_planted_misroute_is_not_correct(monkeypatch):
    """Results handed back in the wrong order reach the wrong tenants."""
    from repro.core.service import DecisionService
    decide = DecisionService.decide

    def rotated(self, reqs):
        out = decide(self, reqs)
        return out[1:] + out[:1]
    monkeypatch.setattr(DecisionService, "decide", rotated)
    over = {"config": SMALL["config"],
            "traffic": dict(SMALL["traffic"], mean_rate=24.0)}
    out = run.execute(BENCH, CELL, 2 ** 31 + 5, 1.5, False,
                      time.perf_counter(), jax.devices()[:1],
                      overrides=over)
    assert out["checks"]["misrouted_or_duplicate"]["value"] > 0
    assert not out["correct"]
