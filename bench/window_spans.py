"""The program's spans inside a window's life, as the per-window readers
in ``metrics/`` see them.

Every reader here reads only ``Run.spans`` (names and arguments). The
per-window readers each belong to a family of spans that one part of the
program records together: the streaming miner's (``STREAMING``) or the
checkpoint's (``DURABILITY``). Where the run holds no span of a reader's
family, the program predates them and the reader returns None. Where it
holds one and none of the asked name ended in the window (no new counter,
no exact recount), the time is 0. The two waits join on the ``window``
that ``wire.ingest`` and ``wire.deliver`` carry, and return None where
nothing joins.
"""

from __future__ import annotations

MINE = "session.mine_window"
STREAMING = ("mine.candidates", "stream.counter_init", "stream.replay",
             "stream.readback", "stream.recount")
DURABILITY = ("ckpt.state", "ckpt.write")


def _ended(run, name: str) -> list:
    """Spans ``name`` that ended inside the measured window: the rule of
    ``measure.Run.span_durations``, keeping the events."""
    if run.spans is None:
        return []
    end = run.end
    return [e for e in run.spans
            if e.name == name and run.start <= e.t0 + e.dur <= end]


def per_window_ms(run, name: str) -> float | None:
    """Milliseconds of ``name`` spans that ended in the window, per
    ``session.mine_window`` span that ended in it."""
    family = STREAMING if name in STREAMING else DURABILITY
    mined = len(run.span_durations(MINE))
    if not mined or not any(e.name in family for e in run.spans):
        return None
    return sum(run.span_durations(name)) / mined * 1e3


def _key(e):
    return (e.args or {}).get("session"), (e.args or {}).get("window")


def queue_wait_ms(run) -> float | None:
    """Mean, over windows mined in the window, of the time from the end of
    the window's ``wire.ingest`` to the start of its mining, joined on
    (session, window)."""
    ingested = {}
    for e in run.spans or []:
        if e.name == "wire.ingest" and _key(e)[1] is not None:
            ingested[_key(e)] = e.t0 + e.dur
    waits = [m.t0 - ingested[_key(m)] for m in _ended(run, MINE)
             if _key(m) in ingested]
    return sum(waits) / len(waits) * 1e3 if waits else None


def delivery_wait_ms(run) -> float | None:
    """Mean, over windows mined in the window, of the time from the end of
    the window's mining to the first ``wire.deliver`` that hands out its
    delta."""
    first = {}
    for e in run.spans or []:
        if e.name != "wire.deliver":
            continue
        sid = (e.args or {}).get("session")
        for w in (e.args or {}).get("windows", ()):
            if (sid, w) not in first or e.t0 < first[(sid, w)]:
                first[(sid, w)] = e.t0
    waits = [first[_key(m)] - (m.t0 + m.dur) for m in _ended(run, MINE)
             if _key(m) in first]
    return sum(waits) / len(waits) * 1e3 if waits else None
