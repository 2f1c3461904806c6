"""Median host time of the benchmark's span around each
EnelTrainer.fit_resident of a run that ends in the open-loop window (ended
after its loss is fetched), in ms."""
from harness import quantile


def read(run):
    v = quantile(run.spans.get("fit", []), 0.5)
    return None if v is None else v * 1e3
