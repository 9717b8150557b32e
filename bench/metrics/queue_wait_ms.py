"""Mean time a window waits between its ``wire.ingest`` and the start of
its ``session.mine_window``, over the windows mined in the window: mostly
the mining of the windows ahead of it in the same scheduler step."""

from window_spans import queue_wait_ms


def read(run):
    return queue_wait_ms(run)
