"""Median host time of the benchmark's span around each lockstep call of the
shared batched simulator's step, in ms."""
from harness import quantile


def read(run):
    v = quantile(run.spans.get("sim", []), 0.5)
    return None if v is None else v * 1e3
