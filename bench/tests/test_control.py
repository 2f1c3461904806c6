"""The comparison that decides ``correct`` fails what it has to fail.

At a size the CPU holds (8 tenants, the cells' own widths, runs and
cadence): the control (the plain reference with its products' operands
rounded to float8) put in the program's place fails
a limit of each cell, and a whole run with the timed path broken
underneath comes out not correct, once for each fault the cells can have:
a fit that returns its state unchanged, a fine-tune alone that does, half
of the batch left out of the fit (the mean taken over the rest), and a
decision altered where it is produced.  The cells run on one chip, so no
exchange between chips exists to leave out.
"""
import time

import jax
import pytest

import harness
import run
from checks import faults

BENCH = harness.load_benchmark()
SMALL = {"config": {"tenants": 8}}
SMALL_LIVE = {"config": {"tenants": 8}}
CELLS = [("fused-256", SMALL), ("live-32", SMALL_LIVE)]


def _execute(cell, overrides, seed=2 ** 31 + 5):
    return run.execute(BENCH, harness.find_cell(BENCH, cell), seed, 0.5,
                       False, time.perf_counter(), jax.devices()[:1],
                       overrides=overrides)


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell,overrides", CELLS)
def test_control_fails_a_limit(cell, overrides):
    files = harness.cell_files(BENCH, harness.find_cell(BENCH, cell))
    for part, extra in overrides.items():
        files[part] = dict(files[part], **extra)
    drv = harness.driver_module(files["traffic"]["driver"]).Driver(
        files["config"], files["traffic"], 2 ** 31 + 7,
        harness.Spans(False))
    drv.setup()
    drv.start_window()
    drv.unit()
    limits = files["limits"]
    prog = drv.check()
    assert all(v <= limits[k] for k, v in prog.items()), prog
    ctrl = drv.control()
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl


# ------------------------------------------------------------ planted faults
@pytest.mark.parametrize("cell,overrides", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.FAULTS["fused_campaign"]))
def test_planted_fault_is_not_correct(cell, overrides, fault, monkeypatch,
                                      fresh_jit):
    files = harness.cell_files(BENCH, harness.find_cell(BENCH, cell))
    mod, attr, broken = faults.target(files["traffic"]["driver"], fault)
    monkeypatch.setattr(mod, attr, broken)
    out = _execute(cell, overrides)
    failed = [k for k, c in out["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    assert not out["correct"] and failed, out["checks"]
