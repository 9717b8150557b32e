"""The load loops against a fake server: the open loop keeps its schedule
while the server stalls, so the stall shows as latency; the replay loop
never has more than its quota of windows waiting."""

import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import load  # noqa: E402
import measure  # noqa: E402
import streams  # noqa: E402


class FakeClient:
    """Mines each window ``service_s`` after the previous one finished (a
    single server thread); from ``stall_at`` it stops for ``stall_s``.
    Submits never block, as on a server whose lock is free."""

    def __init__(self, service_s, stall_at=None, stall_s=0.0):
        self.service_s, self.stall_at, self.stall_s = service_s, stall_at, stall_s
        self.lock = threading.Lock()
        self.free_at = 0.0
        self.done = []  # (ready time, idx)
        self.n = 0
        self.max_waiting = 0

    def submit(self, window):
        with self.lock:
            now = time.perf_counter()
            begin = max(now, self.free_at)
            if self.stall_at is not None and begin >= self.stall_at:
                begin = max(begin, self.stall_at + self.stall_s)
                self.stall_at = None
            self.free_at = begin + self.service_s
            self.done.append((self.free_at, self.n))
            self.n += 1
            self.max_waiting = max(self.max_waiting,
                                   sum(1 for t, _ in self.done if t > now))

    def poll(self):
        with self.lock:
            now = time.perf_counter()
            ready = [i for t, i in self.done if t <= now]
            self.done = [(t, i) for t, i in self.done if t > now]
        return [{"window_idx": i + 2, "n_events": 10, "episodes": []} for i in ready]


class Window:
    types = np.zeros(10, np.int32)


def run(traffic, clients, seconds, offsets=None):
    start = time.perf_counter() + 0.05
    loads = load.run_load(traffic, clients, [lambda j: Window()] * len(clients), 2,
                          offsets if offsets is not None else [0.0] * len(clients),
                          start, seconds, grace=3.0)
    return measure.Run(setup_s=1.0, start=start, stop=start + seconds,
                       gave_up=time.perf_counter(), loop=traffic["loop"],
                       loads=loads)


OPEN = {"loop": "open", "window_ms": 100, "clock_factor": 1.0, "poll_ms": 5}


def test_open_loop_keeps_its_schedule():
    offsets = streams.array_rng(9, 2).uniform(0, 1, 2)
    r = run(OPEN, [FakeClient(0.01), FakeClient(0.01)], 1.0, offsets)
    for a, ld in enumerate(r.loads):
        dues = [s.due - r.start for s in ld.sent]
        want = [offsets[a] * 0.1 + (k + 1) * 0.1 for k in range(len(dues))]
        assert np.allclose(dues, want) and len(dues) in (9, 10)
        assert [s.idx for s in ld.sent] == list(range(2, 2 + len(dues)))
    lat = measure.latency_s(r)
    assert all(s.arrived is not None for s in r.records())
    assert 0.009 < measure.percentile(lat, 50) < 0.05


def test_a_stalled_server_shows_as_latency_not_as_a_late_sender():
    stall_s = 0.6
    steady = run(OPEN, [FakeClient(0.02)], 1.5)
    stalled = run(OPEN, [FakeClient(0.02, time.perf_counter() + 0.5, stall_s)], 1.5)
    # the sender kept its schedule: the stall made it no later than the
    # steady run's sender on the same busy machine. One that waited for
    # the server would fall most of the stall behind.
    lag = {name: max(s.sent - s.due for s in r.records())
           for name, r in (("steady", steady), ("stalled", stalled))}
    assert lag["stalled"] < lag["steady"] + stall_s / 4, lag
    assert len(stalled.due()) == len(steady.due())
    assert (measure.percentile(measure.latency_s(stalled), 90)
            > measure.percentile(measure.latency_s(steady), 90) + 0.3)


def test_replay_keeps_at_most_its_quota_waiting():
    fake = FakeClient(0.03)
    r = run({"loop": "replay", "outstanding": 2, "poll_ms": 2}, [fake], 0.6)
    assert fake.max_waiting <= 2
    assert min(s.sent for s in r.records()) >= r.start  # nothing sent early
    assert 10 <= len(r.delivered()) <= 21
    assert all(s.arrived is not None for s in r.records())


def test_the_rate_runs_until_the_last_delta_owed_has_arrived():
    """The sending stops after the run's seconds; the window closes when
    the last delta of what was sent arrives, so no window is cut off."""
    import spec

    r = run({"loop": "replay", "outstanding": 2, "poll_ms": 2}, [FakeClient(0.07)],
            0.5)
    assert all(s.arrived is not None for s in r.due())
    assert r.end == max(s.arrived for s in r.due()) > r.stop
    rate = spec.reader("events_per_s")(r)
    assert rate == 10 * len(r.due()) / (r.end - r.start)
