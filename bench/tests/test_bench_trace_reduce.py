"""Trace reduction on traces whose busy share, kernel sums and idle gaps
are known."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"
MS = 1e6  # ns


def rows():
    # device ops on [0, 100) ms: busy 10-30 (two overlapping ops), 50-60,
    # 90-110 (clipped at 100); a module line and a host line that never count
    return [
        [DEV, "XLA Ops", "a1_count_state_kernel.3", 10 * MS, 15 * MS],
        [DEV, "XLA Ops", "copy.1", 20 * MS, 10 * MS],
        [DEV, "XLA Ops", "a2_count_state_kernel", 50 * MS, 10 * MS],
        [DEV, "XLA Ops", "a1_count_state_kernel.3", 90 * MS, 20 * MS],
        [DEV, "XLA Modules", "jit_a1_count_state_kernel", 0, 100 * MS],
        ["/host:CPU", "python", tr.SYNC, 5 * MS, 0.0],
    ]


def test_busy_kernels_and_top_ops():
    r = rows()
    assert tr.busy_seconds(r, 0, 100 * MS) == pytest.approx(0.040)
    k = tr.kernel_seconds(r, 0, 100 * MS, ("a1_count_state_kernel",
                                          "a2_count_state_kernel"))
    assert k == pytest.approx({"a1_count_state_kernel": 0.025,
                               "a2_count_state_kernel": 0.010})
    assert tr.top(tr.op_seconds(r, 0, 100 * MS), 2) == [
        ["a1_count_state_kernel.3", pytest.approx(0.025)],
        ["copy.1", pytest.approx(0.010)]]


def test_busy_averages_over_devices():
    r = rows() + [["/device:TPU:1", "XLA Ops", "x", 0, 20 * MS]]
    assert tr.busy_seconds(r, 0, 100 * MS) == pytest.approx(0.030)


def test_idle_gaps_by_innermost_host_span():
    # idle stretches: 0-10, 30-50, 60-90 (60 ms); host spans on the trace
    # clock: a step over 0-80 with a mine span 35-45 inside it
    spans = [("schedule.step", 0, 80 * MS, 0), ("session.mine_window", 35 * MS,
                                                45 * MS, 1)]
    got = dict(tr.idle_by_host(rows(), spans, 0, 100 * MS))
    assert got == pytest.approx({"schedule.step": 0.040,
                                 "session.mine_window": 0.010,
                                 "host_idle": 0.010})


def test_sync_offset():
    assert tr.sync_offset_ns(rows(), 2.0) == pytest.approx(5 * MS - 2e9)


def test_recorded_chip_trace():
    """A slice of a traced run on a TPU v5 lite: the reduction's busy time
    equals the union computed here by sweeping every op edge."""
    path = Path(__file__).parent / "data" / "trace_rows_v5e.json"
    r = json.loads(path.read_text())
    ops = [(t, t + d) for p, line, _, t, d in r
           if line == tr.OPS_LINE and tr.DEVICE_PLANE.match(p)]
    lo, hi = min(a for a, _ in ops), max(b for _, b in ops)
    edges = sorted({x for ab in ops for x in ab})
    want = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for s, e in ops)) / 1e9
    assert tr.busy_seconds(r, lo, hi) == pytest.approx(want, rel=1e-9)
    assert 0 < want < (hi - lo) / 1e9
    k = tr.kernel_seconds(r, lo, hi, ("a1_count_state_kernel",))
    assert k["a1_count_state_kernel"] > 0
