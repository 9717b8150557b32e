"""Mean server-side handling of one ``EVENT_BATCH`` (``wire.ingest``
spans that ended inside the measured window): the dedup and order checks
and the queueing of the window, timed from when the server's lock is
taken, so a wait behind a scheduler step is not in it. The frame's read
and decode come before the span and are not in it either."""


def read(run):
    d = run.span_durations("wire.ingest")
    return sum(d) / len(d) * 1e3 if d else None
