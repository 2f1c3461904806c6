"""Median host time of the benchmark's span around each open-loop
DecisionService.decide (stacking, sweep dispatch, pick and guardrail, one
fetch per group), in ms."""
from harness import quantile


def read(run):
    v = quantile(run.spans.get("dispatch", []), 0.5)
    return None if v is None else v * 1e3
