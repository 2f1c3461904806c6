"""Median host time of the benchmark's span around each open-loop
exp.enel.prepare_request (host graph build, template diff, request
padding), in ms."""
from harness import quantile


def read(run):
    v = quantile(run.spans.get("prep", []), 0.5)
    return None if v is None else v * 1e3
