"""Service scaling: sessions × ingest-rate sweep, aggregate sustained
events/sec.

The companion accelerator paper (arXiv:0905.2203) frames the mining
engines as a shared accelerator service; the figure of merit at fleet
scale is aggregate sustained events/sec across tenants, not one stream's
latency. This benchmark admits S concurrent synthetic electrode-array
sessions (three rate/window classes, so same-class tenants share shape
buckets), pushes every session's partition windows through the
ingest → schedule → batched-mine → poll loop, and reports:

* aggregate sustained events/sec (all sessions' events over the wall
  time of the drain loop — the number that must beat the fleet's summed
  acquisition rates for the chip-on-chip claim);
* per-class p50/p99 window latency;
* batcher fusion counters (requests fused into vmapped device batches),
  with an unbatched run at the largest S for comparison.

Measured columns are steady state: before the timed sweep, one untimed
warmup fleet runs at the largest S in each mode so every (kind,
shape-bucket, lane-bucket) jit compile is paid outside the measurement.
Without it the comparison is compile-order, not architecture — the mode
that happens to run first pays every cold compile and the later one
inherits the warm caches. ``--cold`` skips the warmup to measure
first-contact behavior (expect the batched column to trail there: fused
lane-bucket compiles are extra work the serial baseline never does).
The fusion win this benchmark exists to track — one dispatch per bucket
instead of S — needs host parallelism or an accelerator to show; on a
single-core host the scheduler's adaptive lane cap keeps the batched
path near-serial and the columns converge.

Usage:
  PYTHONPATH=src python benchmarks/service_scale.py [--smoke]
      [--sessions 2 4 8] [--seconds 8] [--cold]
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

try:  # package mode (python -m benchmarks.run)
    from .common import Report
except ImportError:  # direct script mode
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from common import Report

from repro.data import partition_windows, sym26  # noqa: E402
from repro.obs import TRACER, span  # noqa: E402
from repro.service import (MiningService, SchedulerPolicy,  # noqa: E402
                           SessionConfig)

CLASSES = (  # (rate_hz, window_ms): three tenant shapes
    (15.0, 2000), (25.0, 2000), (40.0, 4000))


def _feeds(num_sessions: int, seconds: int):
    feeds = []
    for i in range(num_sessions):
        rate, window_ms = CLASSES[i % len(CLASSES)]
        stream, _ = sym26(seconds=seconds, rate_hz=rate, seed=100 + i)
        cfg = SessionConfig(intervals=((5, 10),), theta=3, max_level=3,
                            window_ms=window_ms, history_limit=8)
        wins = list(partition_windows(stream, window_ms))
        feeds.append((f"array-{i}", cfg, wins, len(stream)))
    return feeds


def _run_fleet(num_sessions: int, seconds: int, batching: bool):
    feeds = _feeds(num_sessions, seconds)
    svc = MiningService(
        policy=SchedulerPolicy(max_sessions=num_sessions,
                               max_pending_windows=64),
        batching=batching)
    for sid, cfg, wins, _ in feeds:
        svc.create_session(sid, cfg)
    # an obs span times the drain loop (bench.fleet is the wall clock)
    TRACER.clear()
    with span("bench.fleet", sessions=num_sessions, batched=batching):
        for sid, _, wins, _ in feeds:
            for j, w in enumerate(wins):
                svc.ingest(sid, w, final=j == len(wins) - 1)
        svc.pump()
    wall = next(e.dur for e in reversed(TRACER.events())
                if e.name == "bench.fleet")
    total_events = sum(n for _, _, _, n in feeds)
    total_windows = sum(len(wins) for _, _, wins, _ in feeds)
    stats = svc.stats()
    return {
        "wall_s": wall,
        "events": total_events,
        "windows": total_windows,
        "agg_ev_per_s": total_events / wall if wall > 0 else 0.0,
        "p50_latency_s": stats["aggregate"]["p50_latency_s"],
        "p99_latency_s": stats["aggregate"]["p99_latency_s"],
        "fused": (stats["batcher"]["fused_requests"] if batching else 0),
        "batches": (stats["batcher"]["batches"] if batching else 0),
        "flush_groups": (stats["batcher"]["flush_groups"]
                         if batching else 0),
        "gate": (stats["batcher"]["fusion_gate"] if batching else {}),
    }


def run(sessions=(2, 4, 8), seconds: int = 8, trace_out: str | None = None,
        cold: bool = False):
    rep = Report("service_scale")
    if not cold:
        # steady-state measurement: pay every jit compile (standalone
        # and fused lane buckets) before the timed sweep, both modes
        s = max(sessions)
        print(f"[service-bench] warmup: {s}-session fleet per mode "
              f"(untimed, populates jit caches)")
        _run_fleet(s, seconds, batching=True)
        _run_fleet(s, seconds, batching=False)
    for s in sessions:
        r = _run_fleet(s, seconds, batching=True)
        rep.add(f"batched/s{s}", r["wall_s"],
                sessions=s, events=r["events"], windows=r["windows"],
                agg_ev_per_s=round(r["agg_ev_per_s"]),
                p99_ms=round(r["p99_latency_s"] * 1e3, 1),
                fused=r["fused"], batches=r["batches"],
                flush_groups=r["flush_groups"],
                gate_fuse=r["gate"].get("fuse", 0),
                gate_standalone=r["gate"].get("standalone", 0))
        print(f"[service-bench] {s:2d} sessions (batched): "
              f"{r['agg_ev_per_s']:,.0f} ev/s aggregate over "
              f"{r['windows']} windows, p99 {r['p99_latency_s']*1e3:.0f} ms,"
              f" {r['fused']} scans fused into {r['batches']} batches"
              f" over {r['flush_groups']} group flushes (gate {r['gate']})")
        if trace_out:
            # trace of the LAST batched fleet size survives (per-run clear)
            n = TRACER.export_chrome(trace_out)
            print(f"[service-bench] wrote {n} spans to {trace_out}")
    s = max(sessions)
    r = _run_fleet(s, seconds, batching=False)
    rep.add(f"unbatched/s{s}", r["wall_s"],
            sessions=s, events=r["events"], windows=r["windows"],
            agg_ev_per_s=round(r["agg_ev_per_s"]),
            p99_ms=round(r["p99_latency_s"] * 1e3, 1),
            flush_groups=0, gate_fuse=0, gate_standalone=0)
    print(f"[service-bench] {s:2d} sessions (unbatched baseline): "
          f"{r['agg_ev_per_s']:,.0f} ev/s aggregate")
    rep.save()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: short streams, 8-session cap")
    ap.add_argument("--sessions", type=int, nargs="+",
                    default=None)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the largest batched fleet's span trace "
                         "as Chrome trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--cold", action="store_true",
                    help="skip the per-mode warmup fleet: measure "
                         "first-contact (compile-bound) behavior")
    args = ap.parse_args()
    if args.smoke:
        sessions = tuple(args.sessions or (2, 8))
        seconds = args.seconds or 6
    else:
        sessions = tuple(args.sessions or (2, 4, 8, 16))
        seconds = args.seconds or 12
    run(sessions=sessions, seconds=seconds, trace_out=args.trace_out,
        cold=args.cold)


if __name__ == "__main__":
    main()
