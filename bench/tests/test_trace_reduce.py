"""The trace reducer on hand-made event lists and on a recorded v5e trace."""
import json
import os

import pytest

from trace_reduce import reduce_events


def test_hand_made_events():
    ev = {"devices": {"/device:TPU:0": [["op_a", 100, 50], ["op_b", 120, 60],
                                        ["op_a", 300, 100],
                                        ["op_c", 1200, 10]]},
          "host": [["bench.window", 0, 1000], ["bench.unit", 0, 500],
                   ["bench.materialize", 200, 90]]}
    out = reduce_events(ev)
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 180] and [300, 400]; op_c lies past the window
    assert out["busy_s"] == pytest.approx(180e-9)
    assert dict((k, v) for k, v in out["device_ops"]) == pytest.approx(
        {"op_a": 150e-9, "op_b": 60e-9})
    # gaps: [0,100] under bench.unit, [180,300] under the innermost span
    # bench.materialize, [400,1000] under the window alone
    assert dict((k, v) for k, v in out["idle_gaps"]) == pytest.approx(
        {"bench.unit": 100e-9, "bench.materialize": 120e-9,
         "bench.window": 600e-9})
    assert [k for k, _ in out["idle_gaps"]][0] == "bench.window"


def test_averages_over_devices():
    ev = {"devices": {"/device:TPU:0": [["x", 0, 100]],
                      "/device:TPU:1": [["x", 0, 50]]},
          "host": [["bench.window", 0, 100]]}
    out = reduce_events(ev)
    assert out["busy_s"] == pytest.approx(75e-9)
    assert out["n_devices"] == 2


def test_no_device_op_in_window_raises():
    ev = {"devices": {"/device:TPU:0": [["x", 2000, 100]]},
          "host": [["bench.window", 0, 1000]]}
    with pytest.raises(ValueError, match="no device operation"):
        reduce_events(ev)


def test_recorded_tpu_trace():
    """What ``load_events`` read from a v5e trace of 20 steps, each one
    jitted 2048x2048 matmul fusion (about 91.5 us) then a 5 ms host sleep
    under ``bench.materialize``.  The device's clock runs about 1 ms behind
    the host's, so the first step's fusion starts before the window."""
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "tpu_trace.json")
    with open(path) as f:
        ev = json.load(f)
    out = reduce_events(ev)
    assert out["n_devices"] == 1
    assert out["window_s"] == pytest.approx(0.131604698)
    fusions = [d for n, s, d in ev["devices"]["/device:TPU:0"]
               if n == "%fusion"]
    assert len(fusions) == 20
    assert out["device_ops"][0][0] == "%fusion"
    assert out["device_ops"][0][1] == pytest.approx(sum(fusions[1:]) * 1e-9)
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle + out["busy_s"] == pytest.approx(out["window_s"])
    assert out["idle_gaps"][0][0] == "bench.materialize"
