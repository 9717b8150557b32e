"""Milliseconds of writing checkpoints to disk (``ckpt.write`` spans: one
file per leaf with fsync, the manifest, the rename) per window mined."""

from window_spans import per_window_ms


def read(run):
    return per_window_ms(run, "ckpt.write")
