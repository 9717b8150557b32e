"""Load loops that drive the served miner over the wire, one
``MiningClient`` per array.

Two loops, chosen by the traffic file's ``loop`` key:

* ``open`` -- independent arrays. Window j of array a is due when its last
  tick has passed on the array's clock: ``start + offset_a + (j + 1 -
  first) * window_s / clock_factor``, with ``offset_a`` drawn from the
  seed within one window period. It is sent when due, whatever the server
  is doing; latency runs from the due time to the delta's arrival, so a
  stalled server shows as latency and the sender's own lateness is
  recorded apart (``Sent.sent`` against ``Sent.due``).
* ``replay`` -- a lab re-mining recorded sessions: from ``start``, each
  array sends its next window as soon as fewer than ``outstanding`` of its
  windows await their deltas. Latency is then a closed-loop service time,
  from the send.

Each array has one thread that alone uses its client (a ``MiningClient``
is not thread-safe). Every RPC is timed (``rpcs``). The loops derive from
the fleet driver ``repro.launch.wire_load``, which submits every window at
once and times nothing per window.
"""

from __future__ import annotations

import dataclasses
import threading
import time


@dataclasses.dataclass
class Sent:
    """One window's life on the client side (``time.perf_counter`` s)."""

    array: int
    idx: int
    n_events: int
    due: float
    sent: float = 0.0
    arrived: float | None = None
    delta: dict | None = None


@dataclasses.dataclass
class ArrayLoad:
    """What one array's thread did."""

    array: int
    sent: list[Sent] = dataclasses.field(default_factory=list)
    rpcs: list[tuple[float, float]] = dataclasses.field(default_factory=list)
    extra: list[dict] = dataclasses.field(default_factory=list)
    error: str | None = None


class _Driver:
    def __init__(self, array: int, client, windows, first: int, poll_s: float):
        self.client = client
        self.windows = windows  # idx -> EventStream, for idx >= first
        self.next = first
        self.poll_s = poll_s
        self.out = ArrayLoad(array)
        self.by_idx: dict[int, Sent] = {}

    def _timed(self, fn, *a, **kw):
        t0 = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            self.out.rpcs.append((t0, time.perf_counter()))

    def send(self, due: float) -> None:
        w = self.windows(self.next)
        rec = Sent(self.out.array, self.next, int(w.types.shape[0]), due,
                   time.perf_counter())
        self._timed(self.client.submit, w)
        self.out.sent.append(rec)
        self.by_idx[rec.idx] = rec
        self.next += 1

    def poll(self) -> int:
        got = self._timed(self.client.poll)
        now = time.perf_counter()
        for d in got:
            rec = self.by_idx.get(d["window_idx"])
            if rec is None or rec.arrived is not None:
                self.out.extra.append(d)
                continue
            rec.arrived, rec.delta = now, d
        return len(got)

    @property
    def waiting(self) -> int:
        return sum(1 for r in self.out.sent if r.arrived is None)


def _open_loop(drv: _Driver, start: float, offset: float, period: float,
               stop: float, grace: float) -> None:
    k = 0
    while True:
        due = start + offset + (k + 1) * period
        if due >= stop:
            break
        now = time.perf_counter()
        if now >= due:
            drv.send(due)
            k += 1
            continue
        if drv.waiting:
            drv.poll()
        time.sleep(max(0.0, min(due - time.perf_counter(), drv.poll_s)))
    _drain(drv, stop + grace)


def _replay_loop(drv: _Driver, outstanding: int, start: float, stop: float,
                 grace: float) -> None:
    time.sleep(max(0.0, start - time.perf_counter()))
    while time.perf_counter() < stop:
        if drv.waiting < outstanding:
            drv.send(time.perf_counter())
            continue
        if not drv.poll():
            time.sleep(drv.poll_s)
    _drain(drv, stop + grace)


def _drain(drv: _Driver, deadline: float) -> None:
    while drv.waiting and time.perf_counter() < deadline:
        if not drv.poll():
            time.sleep(drv.poll_s)


def run_load(traffic: dict, clients, windows, first: int, offsets, start: float,
             seconds: float, grace: float) -> list[ArrayLoad]:
    """Drive every array's client from its own thread from ``start`` for
    ``seconds``, then wait up to ``grace`` seconds for every delta of a
    window sent in that time. ``windows[a](j)`` is array a's window j."""
    stop = start + seconds
    drivers = [_Driver(a, c, windows[a], first, float(traffic["poll_ms"]) / 1e3)
               for a, c in enumerate(clients)]
    if traffic["loop"] == "open":
        period = traffic["window_ms"] / 1e3 / float(traffic["clock_factor"])
        targets = [(_open_loop, (d, start, offsets[a] * period, period, stop, grace))
                   for a, d in enumerate(drivers)]
    elif traffic["loop"] == "replay":
        targets = [(_replay_loop, (d, int(traffic["outstanding"]), start, stop, grace))
                   for d in drivers]
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")

    def guard(fn, drv, args):
        try:
            fn(*args)
        except Exception as e:  # noqa: BLE001 -- reported as not correct
            drv.out.error = repr(e)

    threads = [threading.Thread(target=guard, args=(fn, args[0], args),
                                name=f"load-{i}", daemon=True)
               for i, (fn, args) in enumerate(targets)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + grace + 120.0)
    for d, t in zip(drivers, threads):
        if t.is_alive():
            d.out.error = d.out.error or "load thread did not finish"
    return [d.out for d in drivers]


def backlog(loads: list[ArrayLoad], at: float) -> int:
    """Windows due by ``at`` whose delta had not arrived by then."""
    return sum(1 for ld in loads for r in ld.sent
               if r.due <= at and (r.arrived is None or r.arrived > at))
