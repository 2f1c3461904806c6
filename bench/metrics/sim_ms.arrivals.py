"""Median host time of the benchmark's span around each call of the shared
batched simulator's step in the open-loop window (one call steps every
tenant waiting on a sim step, between dispatches), in ms."""
from harness import quantile


def read(run):
    v = quantile(run.spans.get("sim", []), 0.5)
    return None if v is None else v * 1e3
