#!/usr/bin/env python3
"""Chip smoke run of the wire-served episode-mining path.

  python chip_smoke.py              one TPU: phases ``serve`` and ``engines``
  python chip_smoke.py --chips 4    four TPUs: phase ``sharded`` only
  python chip_smoke.py --rehearse [--chips 4]
                                    the same phases at a tiny size on the
                                    CPU, kernels in interpret mode (four
                                    forced host devices with --chips 4);
                                    never prints ``"ok": true``

Run it from the root of a checkout. Without a TPU (and without
``--rehearse``) it exits 1 before any phase and prints no result.

``serve`` builds the daemon's service (``MiningDaemon``: a ``WireServer``
over a ``MiningService``, exactly what ``mine_serve --listen`` builds),
serves it from threads of this process, and streams five arrays through
``MiningClient``: four Sym26 recordings (26 neurons, 20 Hz) and one
59-electrode culture, four 10 s windows each, every window an
``EVENT_BATCH`` and every delta a ``POLL``. ``engines`` runs the one-shot
engines (PTPE, the segmented kernel, the two-pass cull) on one stream.
``sharded`` runs the mesh-sharded MapConcatenate three ways (one-shot,
streaming, the batcher's fused two-lane launch) over the host's four chips.

Every count is compared with ``==`` against the XLA scan engine in this
process and, for the planted chains and a seeded sample, against the
sequential oracles (``core/ref.py``). The run also fails on any
kernel→XLA fallback, a kernel kind that never launched, a scheduler
watchdog retry, or more than two compiles of one kernel after the first
served window. The last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DATA_DIR = ROOT / ".chip_smoke"  # wire checkpoints of the serve phase
INTERVAL = (5, 10)  # (tlo, thi] ms of every episode edge
PLANTED = ((0, 1, 2), (7, 8, 9, 10))  # sym26's chains, capped at level 4
ENGINE_KINDS = ("a1", "a1_state", "a2_state", "a1_mapc", "a2_mapc")


@dataclasses.dataclass(frozen=True)
class Size:
    # length of the served streams: the exact recount of bounded-list
    # overflows runs on the host in Python and, until a session's first
    # checkpoint, replays from the stream's start every window, so a
    # served window costs more the later it comes (PERF.md, §5)
    serve_seconds: int
    seconds: int  # length of the engines and sharded phases' streams
    window_ms: int
    rate_hz: float  # background firing rate of every neuron
    theta_sym26: int  # per-window θ, chosen for >= min_level3 candidates
    theta_mea59: int
    # level-3 candidates every served window after the first must count
    # (candidates are seeded on two windows' support, so window 0 has few)
    min_level3: int
    episodes: int  # M of the engines / sharded batches
    oracle_sample: int


FULL = Size(serve_seconds=40, seconds=60, window_ms=10_000, rate_hz=20.0,
            theta_sym26=44, theta_mea59=52, min_level3=1024, episodes=1024,
            oracle_sample=64)
REHEARSAL = Size(serve_seconds=12, seconds=12, window_ms=3_000, rate_hz=5.0,
                 theta_sym26=4, theta_mea59=4, min_level3=1, episodes=128,
                 oracle_sample=16)


class Failures(list):
    def check(self, ok, what: str) -> bool:
        if not ok:
            self.append(what)
            print(f"[chip_smoke] FAIL {what}", file=sys.stderr, flush=True)
        return bool(ok)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def recompiles() -> dict:
    from repro.obs import REGISTRY

    return {labels["kernel"]: m.value
            for labels, m in REGISTRY.family_items("recompiles")}


def episode_batch(etypes):
    import numpy as np

    from repro.core import EpisodeBatch

    et = np.asarray(etypes, np.int32)
    m, n = et.shape
    return EpisodeBatch(et, np.full((m, n - 1), INTERVAL[0], np.int32),
                        np.full((m, n - 1), INTERVAL[1], np.int32))


def candidates(rng, m: int, n: int, num_types: int, planted=()):
    """``m`` seeded random n-node episodes, the planted chains first."""
    import numpy as np

    rows = [list(c) for c in planted if len(c) == n]
    rnd = rng.integers(0, num_types, size=(m - len(rows), n))
    return episode_batch(np.concatenate([np.asarray(rows, np.int32)
                                         .reshape(-1, n), rnd]))


# ------------------------------------------------------------------ serve


def phase_serve(size: Size, fail: Failures, rng) -> dict:
    import numpy as np

    from repro.core import EventStream, count_a1_sequential
    from repro.data import partition_windows, sym26
    from repro.obs import REGISTRY
    from repro.service import SessionConfig
    from repro.service.client import MiningClient
    from repro.service.daemon import DaemonConfig, MiningDaemon
    from repro.service.session import MiningSession
    from repro.service.wire import delta_payload

    arrays = {f"sym26-{i}": (sym26(seconds=size.serve_seconds,
                                   rate_hz=size.rate_hz, seed=i)[0],
                             size.theta_sym26) for i in range(4)}
    arrays["mea59"] = (sym26(seconds=size.serve_seconds,
                             rate_hz=size.rate_hz, num_types=59, seed=4)[0],
                       size.theta_mea59)
    configs = {sid: SessionConfig(intervals=(INTERVAL,), theta=theta,
                                  max_level=4, window_ms=size.window_ms)
               for sid, (_, theta) in arrays.items()}
    windows = {sid: list(partition_windows(stream, size.window_ms))
               for sid, (stream, _) in arrays.items()}
    n_windows = max(len(w) for w in windows.values())

    shutil.rmtree(DATA_DIR, ignore_errors=True)
    daemon = MiningDaemon(DaemonConfig(address="127.0.0.1:0",
                                       data_dir=str(DATA_DIR),
                                       max_sessions=len(arrays)))
    address = daemon.server.start()
    # one pump step mines every session's window under the server lock,
    # so a reply may wait a whole step: generous per-RPC timeouts
    clients = {sid: MiningClient(address, sid, configs[sid], deadline_s=900,
                                 rpc_timeout_s=300)
               for sid in arrays}
    served = {sid: {} for sid in arrays}

    def send(j):
        for sid, c in clients.items():
            if j < len(windows[sid]):
                c.submit(windows[sid][j], final=j == len(windows[sid]) - 1)

    def collect():  # POLL until every submitted window's delta is in
        for sid, c in clients.items():
            for d in c.drain():
                served[sid][d["window_idx"]] = d

    def kernel_compiles(now, before):  # the Pallas entry points
        return {k: v - before.get(k, 0) for k, v in now.items()
                if v > before.get(k, 0) and k.endswith("_kernel")}

    per_window = []  # kernel compiles while each window after the first
    try:               # was mined (every session's window j drained)
        for j in range(n_windows):
            before, t0 = recompiles(), time.perf_counter()
            send(j)
            collect()
            compiled = kernel_compiles(recompiles(), before)
            if j:
                per_window.append(compiled)
            print(f"[chip_smoke] serve window {j}: "
                  f"{time.perf_counter() - t0:.1f} s, compiled {compiled}",
                  flush=True)
        retries = REGISTRY.counter("scheduler_watchdog_retries_total").value
        for c in clients.values():
            c.close_session()
    finally:
        daemon.server.shutdown(drain=True)
        shutil.rmtree(DATA_DIR, ignore_errors=True)

    fail.check(retries == 0, f"serve: {retries} scheduler watchdog retries")
    # the carried kernels launch one fixed shape per level, so only a
    # level first reached after window 0 may still compile
    compiles = {}
    for w in per_window:
        for k, v in w.items():
            compiles[k] = compiles.get(k, 0) + v
    for k, v in compiles.items():
        fail.check(v <= 2, f"serve: {k} compiled {v} times after the "
                           "first window (> 2)")

    # the XLA scan engine, in this process, on the same windows
    for sid, wins in windows.items():
        ref = MiningSession(f"ref-{sid}",
                            dataclasses.replace(configs[sid],
                                                use_kernel=False))
        for j, w in enumerate(wins):
            ref.enqueue(w, final=j == len(wins) - 1)
        while ref.queue_depth:
            p = ref.prepare()
            ref.commit(p, ref.execute(p))
        for d in ref.poll():
            want = delta_payload(d)
            got = served[sid].get(d.window_idx)
            fail.check(got is not None and got["episodes"] == want["episodes"],
                       f"serve: {sid} window {d.window_idx} differs from "
                       "the XLA scan engine")
            l3 = [s.num_candidates for s in d.result.stats if s.level == 3]
            fail.check(d.window_idx == 0 or (l3 and l3[0] >= size.min_level3),
                       f"serve: {sid} window {d.window_idx} counted {l3} "
                       f"level-3 candidates (< {size.min_level3})")
        fail.check(len(served[sid]) == len(wins),
                   f"serve: {sid} got {len(served[sid])}/{len(wins)} deltas")

    # the sequential oracle, per served delta. The miner attributes an
    # occurrence to the window in which its counters consume its last
    # event, and they hold back each window's trailing tie group, so
    # window p's delta is A1 over the events before window p's last
    # timestamp minus the same for window p - 1 (the final window
    # consumes everything). Checked for the planted chains in every
    # window and for a seeded sample of every served multi-node episode.
    picks, entries = set(), []
    for sid, by_window in served.items():
        for j, d in by_window.items():
            for et, c in d["episodes"]:
                if len(et) < 2:
                    continue
                entries.append((sid, j, tuple(et), c))
                if tuple(et) in PLANTED:
                    picks.add(entries[-1])
        # the short chain (~50 occurrences a window) clears every θ here;
        # the long one is checked where it does
        fail.check(sum(e[0] == sid and e[2] == PLANTED[0] for e in picks)
                   == len(windows[sid]), f"serve: {sid} did not report "
                   f"the planted chain {PLANTED[0]} in every window")
    for i in rng.choice(len(entries), min(size.oracle_sample, len(entries)),
                        replace=False):
        picks.add(entries[i])
    groups = {}
    for sid, j, et, c in picks:
        groups.setdefault((sid, len(et)), []).append((j, et, c))
    bad = 0
    for (sid, _), items in groups.items():
        stream = arrays[sid][0]
        eps = sorted({et for _, et, _ in items})
        batch = episode_batch(eps)
        cum = []  # oracle counts at each window's attribution frontier
        for j, w in enumerate(windows[sid]):
            k = (len(stream.times) if j == len(windows[sid]) - 1
                 else int(np.searchsorted(stream.times, w.times[-1])))
            cum.append(count_a1_sequential(
                EventStream(stream.types[:k], stream.times[:k],
                            stream.num_types), batch))
        for j, et, c in items:
            i = eps.index(et)
            want = cum[j][i] - (cum[j - 1][i] if j else 0)
            bad += int(want != c)
    fail.check(bad == 0, f"serve: {bad}/{len(picks)} oracle-sampled deltas "
                         "differ")
    return {"sessions": len(arrays), "windows": sum(map(len, windows.values())),
            "events": sum(len(s) for s, _ in arrays.values()),
            "oracle_checked": len(picks), "compiles_after_first": compiles}


# ---------------------------------------------------------------- engines


def phase_engines(size: Size, fail: Failures, rng) -> dict:
    import numpy as np

    from repro.core import (count_a1_sequential, count_a2_sequential,
                            count_dispatch, count_two_pass)
    from repro.data import sym26

    stream = sym26(seconds=size.seconds, seed=0)[0]
    checked = 0
    for n, m in ((3, size.episodes), (5, 128)):
        eps = candidates(rng, m, n, 26, PLANTED + ((7, 8, 9, 10, 11),))
        ref = count_dispatch(stream, eps, engine="ptpe", use_kernel=False)
        for engine in ("ptpe", "mapconcat_kernel"):
            got = count_dispatch(stream, eps, engine=engine)
            fail.check(np.array_equal(got, ref),
                       f"engines: {engine} N={n} differs from the XLA scan")
        theta = max(int(np.median(ref)), 1)
        tp = count_two_pass(stream, eps, theta, engine="mapconcat_kernel")
        tref = count_two_pass(stream, eps, theta, use_kernel=False,
                              engine="ptpe")
        for field in ("counts", "a2_counts", "survived", "frequent"):
            fail.check(np.array_equal(getattr(tp, field), getattr(tref, field)),
                       f"engines: two-pass {field} N={n} differs from the "
                       "XLA scan")
        idx = np.unique(np.concatenate([
            [0], rng.choice(m, min(size.oracle_sample, m), replace=False)]))
        sub = eps.select(idx)
        fail.check(np.array_equal(ref[idx], count_a1_sequential(stream, sub)),
                   f"engines: A1 counts N={n} differ from the oracle")
        fail.check(np.array_equal(tp.a2_counts[idx],
                                  count_a2_sequential(stream, sub.relaxed())),
                   f"engines: A2 counts N={n} differ from the oracle")
        checked += len(idx)
    return {"events": len(stream), "oracle_checked": checked}


# ---------------------------------------------------------------- sharded


def phase_sharded(size: Size, fail: Failures, rng) -> dict:
    import threading

    import numpy as np

    from repro.core import StreamingCounter, count_a1_sequential, count_dispatch
    from repro.core.mapconcat import make_segments, shard_device_count
    from repro.data import partition_windows, sym26
    from repro.kernels import ops as kops
    from repro.service.batcher import CrossSessionBatcher

    d = shard_device_count()
    fail.check(d == 4, f"sharded: shard_device_count() = {d}, want 4")
    stream = sym26(seconds=size.seconds, seed=0)[0]
    eps = candidates(rng, size.episodes, 3, 26, PLANTED)
    idx = np.unique(np.concatenate([
        [0], rng.choice(eps.M, min(size.oracle_sample, eps.M),
                        replace=False)]))
    oracle = count_a1_sequential(stream, eps.select(idx))
    shard0 = kops.KERNEL_CALLS["a1_mapc_shard"]

    # one-shot
    single = count_dispatch(stream, eps, engine="mapconcat_kernel")
    got = count_dispatch(stream, eps, engine="mapconcat_sharded")
    fail.check(np.array_equal(got, single),
               "sharded: one-shot counts differ from the single-device kernel")
    fail.check(np.array_equal(got[idx], oracle),
               "sharded: one-shot counts differ from the oracle")

    # streaming, window by window against the XLA MapConcatenate
    # (same commit frontier, so the same per-window attribution), at the
    # end against the single-device kernel and the oracle
    windows = list(partition_windows(stream, size.window_ms))
    ctr_s = StreamingCounter(eps, engine="mapconcat_sharded")
    ctr_x = StreamingCounter(eps, engine="mapconcatenate", use_kernel=False)
    for j, w in enumerate(windows):
        final = j == len(windows) - 1
        a, b = ctr_s.update(w, final=final), ctr_x.update(w, final=final)
        fail.check(np.array_equal(a, b), f"sharded: streaming window {j} "
                                         "differs from the XLA scan")
    fail.check(ctr_s._shard_d == 4, "sharded: streaming residency did not "
                                    "shard")
    fail.check(np.array_equal(a, single),
               "sharded: streaming counts differ from the single-device kernel")
    fail.check(np.array_equal(a[idx], oracle),
               "sharded: streaming counts differ from the oracle")

    # the batcher's fused launch, two lanes (two recordings, one shape)
    layout = kops.mapconcat_layout(eps, inclusive_lower=False)
    w_max = int(np.asarray(eps.max_span).max())
    lanes = []
    for seed in (1, 2):
        s = sym26(seconds=size.seconds, seed=seed)[0]
        tau, wt, wtt = make_segments(s, 8, w_max)
        lanes.append(layout + (kops.segment_bricks(wt, wtt, tau),))
    interp = kops.kernel_mode()
    batcher = CrossSessionBatcher(fusion_gate=False)
    fused = {}

    def lane(i):
        batcher.bind_session(f"lane-{i}")
        try:
            fused[i] = batcher.mapc_sharded_scan(lanes[i], 3, 4, interp, d)
        finally:
            batcher.end_step(f"lane-{i}")

    for i in range(2):
        batcher.begin_step(f"lane-{i}")
    threads = [threading.Thread(target=lane, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fail.check(batcher.fused_requests == 2 and batcher.batches == 1,
               "sharded: the two lanes did not fuse")
    spans = 0
    for i in range(2):
        one = kops.a1_mapconcat_tuples(*lanes[i], n_levels=3, lcap=4,
                                       interpret=interp)
        shard = kops.a1_mapconcat_sharded_tuples(
            *lanes[i], n_levels=3, lcap=4, interpret=interp, num_devices=d)
        spans = len(shard[1].sharding.device_set)
        for name, x, y, z in zip("acbfo", fused.get(i, ()), one, shard):
            fail.check(np.array_equal(np.asarray(x), np.asarray(y))
                       and np.array_equal(np.asarray(z), np.asarray(y)),
                       f"sharded: fused lane {i} output {name} differs from "
                       "the single-device launch")
    fail.check(spans == 4, f"sharded: launch output spans {spans} devices")
    fail.check(kops.KERNEL_CALLS["a1_mapc_shard"] > shard0,
               "sharded: a1_mapc_shard never launched")
    return {"devices": d, "events": len(stream), "spans": spans,
            "oracle_checked": len(idx)}


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, interpret-mode kernels")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("[chip_smoke] run from the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["REPRO_KERNEL_INTERPRET"] = "1"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")

    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees {dev.platform} devices",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[chip_smoke] {len(devices)} devices, --chips {args.chips}",
              file=sys.stderr)
        return 1

    from repro.kernels.tally import KERNEL_CALLS, fallback_counts
    from repro.obs.jaxprof import ensure_recompile_listener

    ensure_recompile_listener()
    clock = CompileClock()
    size = REHEARSAL if args.rehearse else FULL
    rng = np.random.default_rng(args.seed)
    print(f"[chip_smoke] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    fail = Failures()
    calls0 = dict(KERNEL_CALLS)
    phases = ([("sharded", phase_sharded)] if args.chips == 4
              else [("serve", phase_serve), ("engines", phase_engines)])
    for name, phase in phases:
        t0, c0 = time.perf_counter(), clock.seconds
        info = phase(size, fail, rng)
        print(f"[chip_smoke] phase {name}: "
              f"{time.perf_counter() - t0:.1f} s wall, "
              f"{clock.seconds - c0:.1f} s compiling, {info}", flush=True)

    if args.chips == 1:
        moved = {k: KERNEL_CALLS[k] - calls0.get(k, 0) for k in ENGINE_KINDS}
        fail.check(all(moved.values()), f"kernel kinds that never launched: "
                                        f"{[k for k, v in moved.items() if not v]}")
        print(f"[chip_smoke] kernel launches {moved}", flush=True)
    fallbacks = fallback_counts()
    fail.check(not fallbacks, f"kernel→XLA fallbacks {fallbacks}")
    stats = dev.memory_stats() or {}
    print(f"[chip_smoke] compile {clock.seconds:.1f} s, "
          f"{clock.cache_hits} cache hits; peak device bytes "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    if fail:
        print(f"[chip_smoke] {len(fail)} checks failed", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
