"""Seconds from the process's start to the window: imports, device start,
profiling, plan build, warm-up and any compilation."""


def read(run):
    return run.setup_s
