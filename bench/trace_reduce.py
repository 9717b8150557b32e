"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps.

A trace is read once (``read_xplane``) into plain rows
``[plane, line, name, start_ns, dur_ns]``; everything else works on those
rows, so the tests can feed it a small recorded trace. Device rows are the
ops of the accelerator planes (``/device:TPU:<n>``) on the ``XLA Ops``
line: the device is busy while any op runs, and idle otherwise.

Host spans (the program's ``repro.obs`` spans, kept on ``perf_counter``)
are put on the trace's clock by one marker annotation, ``bench.sync``,
that the harness opens at a known ``perf_counter`` time while the trace
runs.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
SYNC = "bench.sync"


def read_xplane(path) -> list[list]:
    """Rows ``[plane, line, name, start_ns, dur_ns]`` of every event in an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name, float(ev.start_ns),
                             float(ev.duration_ns)])
    return rows


def op_name(text: str) -> str:
    """An op's HLO name from its trace name, which may be the whole HLO
    instruction (``%a1_count_state_kernel.1 = (...) custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def device_ops(rows) -> dict[str, list[tuple]]:
    """Per device plane, its ops as ``(name, start_ns, end_ns)``."""
    out: dict[str, list[tuple]] = {}
    for plane, line, name, t, d in rows:
        if line == OPS_LINE and DEVICE_PLANE.match(plane):
            out.setdefault(plane, []).append((op_name(name), t, t + d))
    return out


def _union(intervals, lo: float, hi: float) -> list[tuple]:
    """Sorted disjoint union of ``intervals`` clipped to [lo, hi]."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_seconds(rows, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] (ns) in which some op ran, averaged over the
    device planes that ran any op."""
    ops = device_ops(rows)
    if not ops:
        return 0.0
    total = sum(sum(b - a for a, b in _union([(s, e) for _, s, e in evs], lo, hi))
                for evs in ops.values())
    return total / len(ops) / 1e9


def op_seconds(rows, lo: float, hi: float) -> dict[str, float]:
    """Device seconds by op name in [lo, hi], summed over devices."""
    out: dict[str, float] = {}
    for evs in device_ops(rows).values():
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[name] = out.get(name, 0.0) + d / 1e9
    return out


def kernel_seconds(rows, lo: float, hi: float, kernels) -> dict[str, float]:
    """Device seconds of each kernel in ``kernels``: the ops whose name
    contains the kernel's name."""
    per_op = op_seconds(rows, lo, hi)
    return {k: sum(v for name, v in per_op.items() if k in name) for k in kernels}


def top(items: dict[str, float], k: int = 10) -> list[list]:
    return [[n, v] for n, v in sorted(items.items(), key=lambda x: -x[1])[:k]]


def sync_offset_ns(rows, perf_s: float) -> float | None:
    """Trace clock minus ``perf_counter`` clock, in ns, from the marker
    opened at ``perf_s``."""
    for _, _, name, t, _ in rows:
        if name == SYNC:
            return t - perf_s * 1e9
    return None


def idle_by_host(rows, spans, lo: float, hi: float, k: int = 10) -> list[list]:
    """Idle device time in [lo, hi], by what the host was doing: each idle
    stretch of the first device is split over the innermost host span open
    at each point (``spans``: ``(name, start_ns, end_ns, depth)`` on the
    trace clock; the latest started wins among equals), and ``host_idle``
    where none was open. Top ``k`` by seconds."""
    ops = device_ops(rows)
    if not ops:
        return []
    busy = _union([(s, e) for _, s, e in ops[sorted(ops)[0]]], lo, hi)
    edges = []  # (time, order, kind, payload): ends sort before starts
    t = lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            edges += [(t, 1, "gap", 1), (a, 0, "gap", -1)]
        t = max(t, b)
    for i, (name, s0, s1, depth) in enumerate(spans):
        if s1 > lo and s0 < hi:
            edges += [(max(s0, lo), 1, "span", i), (min(s1, hi), 0, "span", ~i)]
    edges.sort(key=lambda e: (e[0], e[1]))
    out: dict[str, float] = {}
    active: dict[int, tuple] = {}
    in_gap, prev = 0, lo
    for x, _, kind, val in edges:
        if in_gap and x > prev:
            if active:
                i = max(active, key=lambda j: active[j])
                name = spans[i][0]
            else:
                name = "host_idle"
            out[name] = out.get(name, 0.0) + (x - prev) / 1e9
        prev = x
        if kind == "gap":
            in_gap += val
        elif val >= 0:
            active[val] = (spans[val][3], spans[val][1])
        else:
            active.pop(~val, None)
    return top(out, k)
