"""Shared pieces of the benchmark: file lookup by name, spans, statistics.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``  the deployment (sizes, seeds, cadence);
* ``bench/traffic/<traffic>.json`` the mix; its ``driver`` names the
  general generator under ``bench/drivers/`` that reads it;
* ``bench/limits/<workload>.json`` the limits of the numbers ``correct``
  compares for that cell;
* ``bench/metrics/<metric>.py``    one reader per metric, ``read(run)``
  returning a number or ``None`` where the run has nothing to read.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import time
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict:
    return load_json(root, "BENCHMARK.json")


def find_cell(bench: Dict, workload: str) -> Dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def cell_files(bench: Dict, cell: Dict) -> Dict:
    """The config, mix and limits of one cell, each loaded by name."""
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    return {"config": load_json(ROOT, cfg_entry["file"]),
            "traffic": load_json(BENCH_DIR, "traffic",
                                 cell["traffic"] + ".json"),
            "limits": load_json(BENCH_DIR, "limits", cell["name"] + ".json")}


def cell_metrics(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def driver_module(name: str):
    return load_module(os.path.join(BENCH_DIR, "drivers", name + ".py"),
                       "bench_driver_" + name)


def start_program():
    """Put the program's ``src/`` on the path, keep JAX's compile cache at
    one fixed path inside the checkout (every program, however quick to
    compile), and return JAX's devices; ``None`` without the program."""
    import sys
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return None
    sys.path.insert(0, src)
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)     # JAX writes no entry without it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.devices()


def seeds(seed: int, n: int) -> List[int]:
    """``n`` non-negative 31-bit seeds drawn from the run's ``--seed``."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) >> 1 for s in state]


class Spans:
    """Host-clock spans the benchmark opens around calls into a layer.
    With ``trace`` on each span is also a ``TraceAnnotation`` named
    ``bench.<name>``, so the trace reducer can charge idle gaps to it."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.durations: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)

    def wrap(self, name: str, fn):
        def timed(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return timed


def quantile(values, q: float) -> Optional[float]:
    """Linear-interpolated quantile, ``None`` for no values."""
    if len(values) == 0:
        return None
    return float(np.quantile(np.asarray(values, np.float64), q))


class RunData:
    """What one run measured; the metric readers read from it."""

    def __init__(self, cell: str):
        self.cell = cell
        self.setup_s = 0.0
        self.window_s = 0.0
        self.units = 0
        self.decisions = 0
        self.latencies_s: List[float] = []
        self.spans: Dict[str, List[float]] = {}
        self.trace: Optional[Dict] = None
        self.flops_per_unit: Optional[float] = None
        self.peak: Optional[Dict] = None
