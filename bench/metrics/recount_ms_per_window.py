"""Milliseconds of the exact recount of episodes whose bounded lists
overflowed (``stream.recount`` spans, in counts and in the counters' base
advance) per window mined."""

from window_spans import per_window_ms


def read(run):
    return per_window_ms(run, "stream.recount")
