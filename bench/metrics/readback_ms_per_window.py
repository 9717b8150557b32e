"""Milliseconds of device-to-host reads of counter state
(``stream.readback`` spans; they end when the device has finished the
launches queued before them, so the host's wait on the device is here)
per window mined. Read-backs inside ``stream.replay``, ``ckpt.state`` and
``stream.checkpoint`` are counted here too."""

from window_spans import per_window_ms


def read(run):
    return per_window_ms(run, "stream.readback")
