"""Decisions completed in the window over the window's wall time."""


def read(run):
    return run.decisions / run.window_s if run.window_s > 0 else None
