"""Nested host-side spans with ring-buffer storage and trace exporters.

``TRACER.span("stream.prepare", session="array-0")`` is a context
manager: two ``perf_counter`` reads, a thread-local stack push/pop, and
one deque append on exit — O(1), allocation-light, exception-safe (the
span closes in ``__exit__`` whatever the body raises), and **never**
syncs the device (device-side time is visible as the host wall time of
the dispatch call, which on accelerator backends is a lower bound; use
``obs.jaxprof.capture_step`` for the real device timeline). With
``enabled`` off a span costs one attribute check. Arguments known only at
the end of the work are added with ``note``, which does nothing when the
span is off.

Span names are dotted ``layer.phase`` strings. A window's life, each
span nested under the one that causes it (``*``: zero width)::

    wire.ingest (session, seq, window)
      service.ingest
    schedule.step
      schedule.snapshot / schedule.stage
        ckpt.state (leaves)               retry snapshot in prepare
          stream.readback
      session.mine_window (session, window)
        mine.candidates (level, m)        one per level
        stream.counter_init (kind, m)     a new counter's state
        stream.replay (windows)           new counter over the history
          stream.prepare / stream.launch / stream.readback / ...
        stream.prepare
        batch.barrier_wait / batch.pad_fuse / batch.device_launch /
        batch.self_launch / stream.launch
        stream.commit
        stream.readback (m)               device -> host counter state
        stream.recount (episodes, events) exact recount of flagged episodes
        stream.checkpoint
          stream.readback
          stream.recount
    service.checkpoint
      ckpt.state
        stream.readback
      ckpt.write (leaves, bytes)
    wire.deliver* (session, windows)      first hand-out of deltas

(``schedule.stage`` is the pipelined scheduler's double-buffered host
prepare for the *next* step, running on a session thread while other
lanes hold the device; ``batch.self_launch`` is a lane's own standalone
dispatch when the batcher's fusion gate declines fusion.) The
``session``/``window`` pair names one window in every layer: the
session-local index ``MiningSession.enqueue`` assigns.

The ring keeps the newest ``capacity`` spans; ``dropped`` counts the
ones it evicted since the last ``clear``.

Exports: ``export_jsonl`` (one span per line, absolute timestamps) and
``export_chrome`` (Chrome trace-event JSON — open in Perfetto or
``chrome://tracing``).
"""

from __future__ import annotations

import json
import threading
import time
from collections import namedtuple

SpanEvent = namedtuple("SpanEvent", "name tid t0 dur depth args")


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_depth", "_active")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        tr = self._tracer
        self._active = tr.enabled
        if self._active:
            stack = tr._stack()
            self._depth = len(stack)
            stack.append(self._name)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._active:
            t1 = time.perf_counter()
            tr = self._tracer
            tr._stack().pop()
            if len(tr._events) == tr.capacity:
                with tr._drop_lock:  # taken only once the ring is full
                    tr.dropped += 1
            tr._events.append(SpanEvent(
                self._name, threading.get_ident(), self._t0,
                t1 - self._t0, self._depth, self._args))
        return False

    def note(self, **args) -> None:
        """Add arguments known only once the work is done (a no-op when
        the span is off)."""
        if self._active:
            if self._args is None:
                self._args = {}
            self._args.update(args)


class Tracer:
    """Ring buffer of completed spans, shared process-wide."""

    def __init__(self, capacity: int = 65536):
        self.enabled = True
        self.capacity = capacity
        from collections import deque
        self._events = deque(maxlen=capacity)
        self.dropped = 0  # spans the full ring evicted since clear()
        self._drop_lock = threading.Lock()
        self._local = threading.local()
        # export origin: perf_counter epoch pinned to wall time once
        self._origin = time.perf_counter()
        self._wall0 = time.time()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args or None)

    def current(self) -> str | None:
        """Innermost open span name on this thread (or None)."""
        st = self._stack()
        return st[-1] if st else None

    def events(self) -> list[SpanEvent]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.dropped = 0

    # ---------------------------------------------------------- exports

    def export_jsonl(self, path) -> int:
        """One span per line: {name, ts (unix s), dur_s, tid, depth,
        args}. Returns the number of spans written."""
        events = self.events()
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps({
                    "name": e.name,
                    "ts": self._wall0 + (e.t0 - self._origin),
                    "dur_s": e.dur,
                    "tid": e.tid,
                    "depth": e.depth,
                    "args": e.args or {},
                }) + "\n")
        return len(events)

    def export_chrome(self, path) -> int:
        """Chrome trace-event JSON (Perfetto-loadable): complete ("X")
        events, ts/dur in microseconds, one renamed row per thread.
        Returns the number of spans written."""
        events = self.events()
        tids: dict[int, int] = {}
        rows = []
        for e in events:
            tid = tids.setdefault(e.tid, len(tids))
            rows.append({
                "name": e.name,
                "cat": e.name.split(".", 1)[0],
                "ph": "X",
                "ts": (e.t0 - self._origin) * 1e6,
                "dur": e.dur * 1e6,
                "pid": 0,
                "tid": tid,
                "args": e.args or {},
            })
        meta = [{"name": "thread_name", "ph": "M", "pid": 0, "tid": small,
                 "args": {"name": f"worker-{small}" if small else "main"}}
                for small in sorted(tids.values())]
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + rows,
                       "displayTimeUnit": "ms"}, f)
        return len(rows)


TRACER = Tracer()


def span(name: str, **args) -> _Span:
    """Module-level shorthand for ``TRACER.span``."""
    return TRACER.span(name, **args)
