"""The program-span reduction on hand-made events and on a recorded v5e
trace; the reducer's own keys do not depend on the program's events."""
import json
import os

import pytest

from program_trace import hlo_scopes, layer_metrics, op_scope, reduce_program
from trace_reduce import reduce_events

LINE = "/host:CPU/0"


def _ev():
    """Window [0, 1000].  One decide call [100, 500] holding a stack phase
    [110, 210] and a fetch phase [300, 480]; device busy [250, 350]; a prep
    [600, 700] with a build child [610, 650] on another line; the device
    ops under scope ``enel.fit`` nest: [800, 900] holds [820, 840]."""
    return {
        "devices": {"/device:TPU:0": [["%while", 250, 100],
                                      ["%while", 800, 100],
                                      ["%fusion", 820, 20]]},
        "host": [["bench.window", 0, 1000]],
        "program": [
            ["enel.decide", 100, 400, LINE, {"requests": 3}],
            ["enel.decide.stack", 110, 100, LINE,
             {"hits": 2, "misses": 1, "bytes": 64}],
            ["enel.decide.fetch", 300, 180, LINE, {}],
            ["enel.prep", 600, 100, "/host:CPU/1", {"rid": 7}],
            ["enel.prep.build", 610, 40, "/host:CPU/1", {}],
        ],
        "scopes": {"/device:TPU:0": [["enel.fit", 800, 100],
                                     ["enel.fit", 820, 20]]},
    }


def test_self_time_subtracts_children_on_the_same_line():
    red = reduce_program(_ev())
    assert red["span_s"]["enel.decide"] == pytest.approx(400e-9)
    assert red["self_s"]["enel.decide"] == pytest.approx(120e-9)
    assert red["self_s"]["enel.decide.stack"] == pytest.approx(100e-9)
    assert red["self_s"]["enel.prep"] == pytest.approx(60e-9)
    child = red["child_self_ms"]
    assert child["enel.decide>enel.decide.stack"] == pytest.approx([1e-4])
    assert child["enel.decide>enel.decide.fetch"] == pytest.approx([1.8e-4])
    assert child["enel.prep>enel.prep.build"] == pytest.approx([4e-5])
    assert red["span_args"]["enel.decide.stack"] == {
        "hits": 2, "misses": 1, "bytes": 64}


def test_self_time_clipped_to_the_window():
    ev = _ev()
    ev["program"] = [["enel.round", -100, 300, LINE, {}],
                     ["enel.resume", -50, 100, LINE, {}],
                     ["enel.resume", 100, 50, LINE, {}]]
    red = reduce_program(ev)
    assert red["span_s"]["enel.round"] == pytest.approx(200e-9)
    assert red["self_s"]["enel.round"] == pytest.approx(100e-9)
    assert red["child_self_ms"]["enel.round>enel.resume"] == pytest.approx(
        [1e-4])


def test_idle_gaps_charged_to_the_innermost_program_span():
    red = reduce_program(_ev())
    gaps = dict(red["program_gaps"])
    # idle: [0,250] [350,800] [900,1000]
    assert gaps["enel.decide.stack"] == pytest.approx(100e-9)
    assert gaps["enel.decide"] == pytest.approx(10e-9 + 40e-9 + 20e-9)
    assert gaps["enel.decide.fetch"] == pytest.approx(130e-9)
    assert gaps["enel.prep.build"] == pytest.approx(40e-9)
    assert gaps["enel.prep"] == pytest.approx(60e-9)
    assert gaps["-"] == pytest.approx(100e-9 + 100e-9 + 100e-9 + 100e-9)
    assert red["idle_named"] == pytest.approx(400 / 800)


def test_nested_scoped_ops_count_once():
    red = reduce_program(_ev())
    assert red["scope_device_s"] == pytest.approx({"enel.fit": 100e-9})
    two = _ev()
    two["devices"]["/device:TPU:1"] = [["%while", 800, 50]]
    two["scopes"]["/device:TPU:1"] = [["enel.fit", 800, 50]]
    assert reduce_program(two)["scope_device_s"]["enel.fit"] == \
        pytest.approx(75e-9)


def test_op_scope_is_the_innermost_enel_part():
    assert op_scope("jit(f)/while/body/enel.fit/cond/dot") == "enel.fit"
    assert op_scope("jit(f)/enel.sweep/enel.fit/add") == "enel.fit"
    assert op_scope("jit(f)/while/body/add") is None


def _msg(*fields):
    """Serialize (field number, bytes or str) pairs as length-delimited
    protobuf fields."""
    out = b""
    for num, value in fields:
        value = value.encode() if isinstance(value, str) else value
        size, head = len(value), b""
        while True:
            head += bytes([(size & 0x7F) | (0x80 if size > 0x7F else 0)])
            size >>= 7
            if not size:
                break
        out += bytes([num << 3 | 2]) + head + value
    return out


def test_hlo_scopes_read_from_the_metadata_plane():
    """XSpace -> /host:metadata plane -> event metadata (name, stat) ->
    HloProto -> module -> computations -> instructions and op_name."""
    def ins(name, stack=None):
        meta = [(7, _msg((2, stack)))] if stack else []
        return _msg((1, name), *meta)

    body = _msg((1, "region_0"), (2, ins("fusion.3", "jit(f)/while/body/"
                                         "enel.fit/cond/dot")),
                (2, ins("dot.1", "jit(f)/enel.sim/enel.ring/dot")),
                (2, ins("add.2", "jit(f)/while/body/add")),
                (2, ins("param.0")))
    proto = _msg((1, _msg((1, "jit_f"), (3, body))))
    meta = _msg((2, "jit_f(42)"), (5, _msg((6, proto))))
    space = _msg((1, _msg((2, "/host:CPU"))),
                 (1, _msg((2, "/host:metadata"), (4, _msg((2, meta))))))
    assert hlo_scopes(space) == {"jit_f(42)": {"%fusion.3": "enel.fit",
                                               "%dot.1": "enel.ring"}}


def test_layer_metrics():
    m = layer_metrics(reduce_program(_ev()), units=2)
    assert m["decide_stack_ms.live"] == pytest.approx(1e-4)
    assert m["decide_launch_ms.live"] is None
    assert m["prep_build_ms.live"] == pytest.approx(4e-5)
    assert m["stack_memo_hit.live"] == pytest.approx(200 / 3)
    assert m["idle_named.live"] == pytest.approx(50.0)
    assert m["fit_device_s.fused"] == pytest.approx(50e-9)
    assert m["sim_device_s.fused"] is None


def _fixture():
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "tpu_trace_spans.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("load", [_ev, _fixture], ids=["hand", "tpu"])
def test_program_keys_leave_reducer_outputs_unchanged(load):
    ev = load()
    bare = {k: v for k, v in ev.items() if k not in ("program", "scopes")}
    assert reduce_events(ev) == reduce_events(bare)


def test_recorded_tpu_trace():
    """What ``load_events`` plus ``program_trace.collect`` read from a v5e
    trace: a jitted ``lax.scan`` of 3 steps, each an ``enel.sim`` matmul
    and tanh of 1024x1024 arrays then, under ``enel.fit``, a ``lax.cond``
    holding a second matmul on even steps, dispatched 3 times under
    ``bench.window``; each time inside an ``enel.round`` span holding
    ``enel.decide.launch`` (the dispatch), ``enel.decide.fetch`` (the
    fetch of one result; args ``hits`` and ``bytes`` set on close) and
    ``enel.resume`` (a 3 ms host sleep).  A v5e op event has no name stack;
    the scopes come from the HLO protos in the trace's metadata plane, and
    the conditional's ops nest inside it."""
    ev = _fixture()
    red = reduce_program(ev)
    base = reduce_events(ev)
    kinds = {p[0] for p in ev["program"]}
    assert kinds == {"enel.round", "enel.decide.launch", "enel.decide.fetch",
                     "enel.resume"}
    assert len(red["child_self_ms"]["enel.round>enel.resume"]) == 3
    assert min(red["child_self_ms"]["enel.round>enel.resume"]) > 3.0
    assert red["span_args"]["enel.decide.fetch"] == {"hits": 3,
                                                     "bytes": 3 * 4096}
    scopes = red["scope_device_s"]
    assert set(scopes) == {"enel.sim", "enel.fit"}
    assert scopes["enel.fit"] > scopes["enel.sim"] > 0
    fit_ops = sum(d for s, _, d in ev["scopes"]["/device:TPU:0"]
                  if s == "enel.fit")
    assert scopes["enel.fit"] < 0.6 * fit_ops * 1e-9     # nested: once
    assert sum(scopes.values()) <= base["busy_s"] * (1 + 1e-9)
    # the device idles while the host sleeps under enel.resume
    gaps = dict(red["program_gaps"])
    assert gaps["enel.resume"] > 3 * 3e-3 * 0.9
    assert 0.9 < red["idle_named"] <= 1
