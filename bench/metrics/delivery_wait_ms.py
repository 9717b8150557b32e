"""Mean time a mined window's delta waits between the end of its
``session.mine_window`` and the first ``wire.deliver`` that hands it out,
over the windows mined in the window. The serving loop holds the server
lock that ``POLL`` waits on through the rest of the scheduler step (the
step's other windows) and the checkpoint after it, so this reads the
serving loop, not the wire."""

from window_spans import delivery_wait_ms


def read(run):
    return delivery_wait_ms(run)
