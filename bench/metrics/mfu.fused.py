"""Model FLOPs of the window's fused campaigns (``bench/flops.py``) over
the window's wall time and the chip's bf16 peak, in %."""


def read(run):
    if run.trace is None or not run.flops_per_unit or run.peak is None:
        return None
    rate = run.flops_per_unit * run.units / run.window_s
    return 100.0 * rate / run.peak["bf16_flops_per_s"]
