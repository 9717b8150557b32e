"""Share of the traced window in which no op ran on the device, in %."""

from trace_reduce import busy_seconds, device_ops


def read(run):
    if run.trace is None or not device_ops(run.trace):
        return None
    busy = busy_seconds(run.trace, run.trace_lo, run.trace_hi)
    return 100.0 * (1.0 - busy / run.seconds)
