"""Milliseconds new counters spend replaying the retained window history
(``stream.replay`` spans, whose launches, read-backs and recounts nest
inside) per window mined."""

from window_spans import per_window_ms


def read(run):
    return per_window_ms(run, "stream.replay")
