"""50th percentile of the window's decision latencies, in ms: from the
shared backend's return of a job's component result to the end of the
generator step that applies the job's decision."""
from harness import quantile


def read(run):
    v = quantile(run.latencies_s, 0.50)
    return None if v is None else v * 1e3
