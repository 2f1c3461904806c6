"""Median, in ms, of the window's queue waits: from each arrival's
scheduled time to the start of the DecisionService.decide that took its
request (the benchmark's host clock around each call); request preparation
lies inside it."""
from harness import quantile


def read(run):
    v = quantile(run.spans.get("queue_wait", []), 0.5)
    return None if v is None else v * 1e3
