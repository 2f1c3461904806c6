"""Every cell, configuration, mix, limit and metric of BENCHMARK.json is
found by its name, and the file keeps the benchmark's contract."""
import os
import re

import pytest

import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_found_by_name(cell):
    files = harness.cell_files(BENCH, cell)
    assert files["config"]["name"] == cell["config"]
    drv = harness.driver_module(files["traffic"]["driver"])
    assert hasattr(drv, "Driver")
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in harness.cell_metrics(BENCH, cell["name"], kind):
            assert callable(harness.metric_reader(m["name"]).read)
    names = {m["name"] for m in harness.cell_metrics(BENCH, cell["name"],
                                                     "end_to_end")}
    assert "setup_s" in names and len(names) >= 2
    assert harness.cell_metrics(BENCH, cell["name"], "per_layer")
    assert files["limits"]["window_traces"] == 0


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        e2e = {m["name"] for m in BENCH["end_to_end"]}
        assert metric["moves"] in e2e and metric["layer"]
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics",
                                       metric["name"] + ".py"))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert cfg["file"].startswith(BENCH["paths"][0] + "/")
    data = harness.load_json(harness.ROOT, cfg["file"])
    for key in cfg["reduced"]:
        assert data[key] != data["published"][key]
    assert {"hidden", "edge_dim", "ctx_dim"}.isdisjoint(cfg["reduced"])


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    seen = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(seen) == len(set(seen))
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) < 65536
