"""What one run measured, as the metric readers see it.

``Run`` holds the load loops' records (host clock), the program's spans of
the measured window (traced runs only), and the profiler trace's rows with
the window's bounds on the trace clock (traced runs only). The helpers
below are the arithmetic the readers share.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# The program's carried counting kernels: every served window runs them.
KERNELS = ("a1_count_state_kernel", "a2_count_state_kernel")


@dataclasses.dataclass
class Run:
    """The measured window runs from ``start`` until the last delta of the
    work sent in it has arrived (``end``): the loops send for the run's
    ``--seconds``, up to ``stop``, and then wait for every delta they are
    owed. A rate over that window counts all the work and all its time,
    without cutting the last windows in half or dropping them."""

    setup_s: float
    start: float  # measured window, time.perf_counter seconds
    stop: float  # the loops send no window from here on
    gave_up: float  # when the run stopped waiting for late deltas
    loop: str  # "open" or "replay"
    loads: list  # load.ArrayLoad, one per array
    spans: list | None = None  # repro.obs SpanEvent in the window
    trace: list | None = None  # trace_reduce rows
    trace_lo: float = 0.0  # the window on the trace clock, ns
    trace_hi: float = 0.0
    compile_s: float = 0.0  # backend compile seconds inside the window

    @property
    def end(self) -> float:
        """When the last delta of a window due in the window arrived; the
        time the run gave up waiting where one never came."""
        due = self.due()
        if any(r.arrived is None for r in due):
            return self.gave_up
        return max((r.arrived for r in due), default=self.stop)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def records(self):
        return [r for ld in self.loads for r in ld.sent]

    def due(self):
        """Windows due inside the sending time (open loop: by schedule;
        replay: sent in it)."""
        return [r for r in self.records() if self.start <= r.due < self.stop]

    def delivered(self):
        """Windows due inside the sending time whose delta arrived."""
        return [r for r in self.due() if r.arrived is not None]

    def span_durations(self, name: str) -> list[float]:
        """Durations (s) of the program's spans ``name`` that ended inside
        the window."""
        if self.spans is None:
            return []
        end = self.end
        return [e.dur for e in self.spans
                if e.name == name and self.start <= e.t0 + e.dur <= end]


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear between order statistics), or None."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def latency_s(run: Run) -> list[float]:
    """Latency of every window due in the window: arrival minus due time.
    A window whose delta never came counts at the time the run gave up on
    it (the run is then not correct)."""
    out = []
    for r in run.due():
        out.append((r.arrived if r.arrived is not None else run.gave_up) - r.due)
    return out
