"""Device milliseconds of the carried counting kernels (A1 and A2 state
kernels) in the traced window, per window mined in it."""

from measure import KERNELS
from trace_reduce import kernel_seconds


def read(run):
    if run.trace is None:
        return None
    mined = len(run.span_durations("session.mine_window"))
    secs = sum(kernel_seconds(run.trace, run.trace_lo, run.trace_hi, KERNELS).values())
    if not mined or secs <= 0:
        return None
    return secs / mined * 1e3
