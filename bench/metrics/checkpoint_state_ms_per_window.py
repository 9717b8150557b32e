"""Milliseconds of gathering a session's state for a checkpoint
(``ckpt.state`` spans: the durable checkpoint and the retry snapshot
before each window; counter read-backs nest inside) per window mined."""

from window_spans import per_window_ms


def read(run):
    return per_window_ms(run, "ckpt.state")
