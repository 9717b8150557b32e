"""The readers of the program's spans inside a window's life, on runs
whose spans are built by hand: each gives the value worked out here, and
None where the program records none of its spans."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import measure  # noqa: E402
import spec  # noqa: E402
import window_spans  # noqa: E402
from repro.obs.trace import SpanEvent  # noqa: E402

START, STOP = 100.0, 110.0  # no load records: the window ends at STOP


def ev(name, t0, dur, **args):
    return SpanEvent(name, 1, t0, dur, 0, args or None)


def run_of(spans):
    return measure.Run(setup_s=1.0, start=START, stop=STOP, gave_up=STOP,
                       loop="replay", loads=[], spans=spans)


def spans():
    """Windows 5 and 6 of session "a" mined in the window, window 4 before
    it; one span of each kind that ended before the window opened."""
    return [
        ev("session.mine_window", 97.0, 2.0, session="a", window=4),
        ev("mine.candidates", 97.5, 0.5, level=2, m=80),
        ev("ckpt.write", 99.4, 0.5, leaves=9, bytes=100),
        ev("wire.ingest", 100.0, 0.001, session="a", seq=7, window=5),
        ev("wire.ingest", 100.5, 0.001, session="a", seq=8, window=6),
        ev("wire.ingest", 100.6, 0.001, session="a", seq=8),  # a duplicate
        ev("mine.candidates", 101.1, 0.1, level=2, m=80),
        ev("stream.counter_init", 101.2, 0.05, kind="a1", m=3),
        ev("stream.replay", 101.3, 0.2, windows=2),
        ev("stream.readback", 101.35, 0.01, m=3),
        ev("stream.recount", 101.6, 0.08, episodes=2, events=900),
        ev("session.mine_window", 101.001, 2.0, session="a", window=5),
        ev("wire.deliver", 103.251, 0.0, session="a", windows=[5]),
        ev("ckpt.state", 103.3, 0.06, leaves=40),
        ev("ckpt.write", 103.4, 0.5, leaves=40, bytes=5000),
        ev("mine.candidates", 103.6, 0.3, level=3, m=400),
        ev("stream.readback", 104.0, 0.03, m=3),
        ev("ckpt.state", 104.1, 0.02, leaves=40),
        ev("session.mine_window", 103.501, 2.0, session="a", window=6),
        ev("wire.deliver", 106.001, 0.0, session="a", windows=[6]),
        ev("wire.deliver", 106.5, 0.0, session="b", windows=[6]),
    ]


@pytest.mark.parametrize("name,want", [
    ("candidates_ms_per_window", (0.1 + 0.3) / 2 * 1e3),
    ("counter_setup_ms_per_window", 0.05 / 2 * 1e3),
    ("replay_ms_per_window", 0.2 / 2 * 1e3),
    ("readback_ms_per_window", (0.01 + 0.03) / 2 * 1e3),
    ("recount_ms_per_window", 0.08 / 2 * 1e3),
    ("checkpoint_state_ms_per_window", (0.06 + 0.02) / 2 * 1e3),
    ("checkpoint_write_ms_per_window", 0.5 / 2 * 1e3),
    # windows 5 and 6: mined 1.0 s and 3.0 s after their ingest ended
    ("queue_wait_ms", (1.0 + 3.0) / 2 * 1e3),
    # handed out 0.25 s and 0.5 s after their mining ended
    ("delivery_wait_ms", (0.25 + 0.5) / 2 * 1e3),
])
def test_window_span_reader(name, want):
    read = spec.reader(name)
    assert read(run_of(spans())) == pytest.approx(want, rel=1e-9)
    # an untraced run, and a traced run that mined nothing
    assert read(run_of(None)) is None
    assert read(run_of([])) is None
    # a program without these spans: its windows carry no index at ingest,
    # no delivery is recorded, and no counter state is read back
    older = [e for e in spans() if e.name in ("session.mine_window", "wire.ingest")]
    older = [e._replace(args={k: v for k, v in e.args.items() if k != "window"})
             if e.name == "wire.ingest" else e for e in older]
    assert read(run_of(older)) is None


PER_WINDOW = {  # reader: the span it sums, and the span's family
    "candidates_ms_per_window": ("mine.candidates", window_spans.STREAMING),
    "counter_setup_ms_per_window": ("stream.counter_init", window_spans.STREAMING),
    "replay_ms_per_window": ("stream.replay", window_spans.STREAMING),
    "readback_ms_per_window": ("stream.readback", window_spans.STREAMING),
    "recount_ms_per_window": ("stream.recount", window_spans.STREAMING),
    "checkpoint_state_ms_per_window": ("ckpt.state", window_spans.DURABILITY),
    "checkpoint_write_ms_per_window": ("ckpt.write", window_spans.DURABILITY),
}


@pytest.mark.parametrize("name", sorted(PER_WINDOW))
def test_work_absent_from_an_instrumented_run_reads_zero(name):
    """A window in which no counter was built, replayed, recounted, read
    back or checkpointed reads 0 where the program records the other spans
    of the reader's family."""
    own = PER_WINDOW[name][0]
    assert spec.reader(name)(run_of([e for e in spans() if e.name != own])) == 0.0


@pytest.mark.parametrize("name", sorted(PER_WINDOW))
def test_reader_depends_only_on_its_own_family(name):
    """Taking away every span of the other family, or of the other spans of
    its own family, leaves a reader's value; taking away its whole family
    makes it None."""
    own, family = PER_WINDOW[name]
    read = spec.reader(name)
    want = read(run_of(spans()))
    assert read(run_of([e for e in spans()
                        if e.name == own or e.name not in
                        window_spans.STREAMING + window_spans.DURABILITY])) == want
    assert read(run_of([e for e in spans() if e.name not in family])) is None
