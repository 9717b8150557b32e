"""Milliseconds of building new counters (``stream.counter_init`` spans:
state init, the kernel brick layout and its uploads, and any compile or
cache load of them) per window mined."""

from window_spans import per_window_ms


def read(run):
    return per_window_ms(run, "stream.counter_init")
