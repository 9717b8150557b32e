#!/usr/bin/env python3
"""Chip benchmark of the wire-served episode miner.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> --rehearse

Run from the root of a checkout. One run is one process: it makes the
cell's arrays from ``--seed`` (``bench/streams.py``), starts the program's
daemon (``MiningDaemon``: a ``WireServer`` over a ``MiningService``, what
``mine_serve --listen`` builds) on loopback in threads of this process,
and drives one ``MiningClient`` per array over ``EVENT_BATCH`` and
``POLL``. Set-up mines every array's first windows, which compiles (or
loads from the compile cache) every kernel shape the cell uses; then the
cell's load loop (``bench/load.py``) runs for ``--seconds``. Afterwards
every delta the clients received, for every window sent, is compared with
the plain reference (``bench/reference.py``).

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window and the program's spans. The numbers compared
for ``correct`` are printed last on standard error and last in that line.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result. ``--rehearse`` runs the cell at its rehearsal size on
the CPU with the kernels in interpret mode; its line names the CPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# inside the checkout: the compile cache at a fixed path (the path is part
# of the cache key), and each run's own wire checkpoints and trace, removed
# when it ends
CACHE = BENCH / ".cache"
GRACE_S = 60.0  # how long past the window a late delta is waited for

sys.path.insert(0, str(BENCH))

import load  # noqa: E402
import measure  # noqa: E402
import reference  # noqa: E402
import spec as spec_mod  # noqa: E402
import streams  # noqa: E402
import trace_reduce  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events, with the time each happened."""

    def __init__(self):
        import jax

        self.compiles: list[tuple[float, float]] = []  # (when, seconds)
        self.cache_hits: list[float] = []

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((time.perf_counter(), secs))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> tuple[int, float, int]:
        """Compiles, their seconds, and cache hits in [t0, t1)."""
        inside = [s for t, s in self.compiles if t0 <= t < t1]
        return (len(inside), sum(inside),
                sum(1 for t in self.cache_hits if t0 <= t < t1))


def scaled(cell, rehearse: bool) -> tuple[dict, dict]:
    cfg, traffic = dict(cell.config), dict(cell.traffic)
    if rehearse:
        cfg.update(cfg.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))
    return cfg, traffic


def session_config(cfg: dict, traffic: dict):
    from repro.service import SessionConfig

    return SessionConfig(
        intervals=(tuple(cfg["interval_ms"]),),
        theta=theta(cfg, traffic), theta_mode="per_window",
        max_level=int(cfg["max_level"]), window_ms=int(traffic["window_ms"]),
        two_pass=bool(cfg["two_pass"]), history_limit=int(cfg["history_limit"]),
        lcap=int(cfg["lcap"]))


def theta(cfg: dict, traffic: dict) -> int:
    return int(round(float(cfg["theta_per_s"]) * traffic["window_ms"] / 1e3))


def stream_windows(traffic: dict, seconds: float) -> int:
    """Windows each array's recording needs: warm-up, the window, spare."""
    warm = int(traffic["warmup_windows"])
    if traffic["loop"] == "open":
        per_s = float(traffic["clock_factor"]) * 1e3 / traffic["window_ms"]
        return warm + math.ceil(seconds * per_s) + 2
    return warm + int(traffic["max_windows"])


def prepare(rehearse: bool) -> None:
    """Environment of a run, set before JAX is imported: the compile cache
    inside the checkout, the program on the path, and for a rehearsal the
    CPU with the kernels in interpret mode."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["REPRO_KERNEL_INTERPRET"] = "1"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / ("jax-cpu" if rehearse
                                                           else "jax"))
    # libtpu's own logs stay inside the checkout too
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    Path(os.environ["TPU_LOG_DIR"]).mkdir(parents=True, exist_ok=True)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="rehearsal size on the CPU, kernels in interpret mode")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program under {ROOT / 'src'}: run from a whole checkout")
        return 2
    cell = spec_mod.resolve(spec_mod.load_spec(ROOT), args.workload)
    cfg, traffic = scaled(cell, args.rehearse)
    prepare(args.rehearse)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    t_import = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    t_jax = time.perf_counter()
    chips = int(cell.workload["chips"])
    if not args.rehearse and dev.platform != "tpu":
        log(f"no TPU: JAX sees {dev.platform} devices")
        return 1
    if len(devices) < chips:
        log(f"{len(devices)} devices, the cell needs {chips}")
        return 1

    from repro.obs import TRACER
    from repro.service.client import MiningClient
    from repro.service.daemon import DaemonConfig, MiningDaemon

    TRACER.enabled = bool(args.trace)
    TRACER.clear()
    clock = CompileClock()
    n_arrays = int(traffic["arrays"])
    window_ms = int(traffic["window_ms"])
    warm = int(traffic["warmup_windows"])
    n_win = stream_windows(traffic, args.seconds)
    recs = [streams.recording(cfg, n_win * window_ms / 1e3 + 1.0, args.seed, a)
            for a in range(n_arrays)]
    bounds = [streams.window_bounds(r, window_ms) for r in recs]
    offsets = streams.array_rng(args.seed, n_arrays).uniform(0.0, 1.0, n_arrays)
    sess_cfg = session_config(cfg, traffic)
    t_streams = time.perf_counter()

    from repro.core.events import EventStream

    def windows_of(a):
        r, b = recs[a], bounds[a]

        def window(j):
            return EventStream(r.types[b[j]:b[j + 1]], r.times[b[j]:b[j + 1]],
                               r.num_types)
        return window

    CACHE.mkdir(parents=True, exist_ok=True)
    scratch = Path(tempfile.mkdtemp(prefix="run-", dir=CACHE))
    data_dir = scratch / "serve-data"
    daemon = MiningDaemon(DaemonConfig(address="127.0.0.1:0", data_dir=str(data_dir),
                                       max_sessions=n_arrays))
    address = daemon.server.start()
    clients = [MiningClient(address, f"array-{a}", sess_cfg, deadline_s=600.0,
                            rpc_timeout_s=300.0, rng_seed=a)
               for a in range(n_arrays)]
    served: list[dict[int, dict]] = [{} for _ in range(n_arrays)]
    duplicates = 0  # deltas of a window whose delta had already arrived
    errors: list[str] = []
    trace_dir = scratch / "trace"
    try:
        t_daemon = time.perf_counter()
        for a, c in enumerate(clients):
            c.open()
            for j in range(warm):
                c.submit(windows_of(a)(j))
        for a, c in enumerate(clients):
            for d in c.drain(deadline_s=900.0):
                duplicates += d["window_idx"] in served[a]
                served[a][d["window_idx"]] = d
        setup_s = time.perf_counter() - T_PROCESS
        log(f"{dev.platform} {dev.device_kind} x{len(devices)}; set-up "
            f"{setup_s:.1f} s ({len(clock.compiles)} compiles, "
            f"{len(clock.cache_hits)} cache hits): imports "
            f"{t_import - T_PROCESS:.1f} s, JAX's devices {t_jax - t_import:.1f} s, "
            f"arrays made {t_streams - t_jax:.1f} s, "
            f"daemon {t_daemon - t_streams:.1f} s, warm-up windows "
            f"{setup_s - (t_daemon - T_PROCESS):.1f} s")

        start = time.perf_counter() + 0.5
        stop = start + args.seconds
        result: dict = {}
        worker = threading.Thread(
            target=lambda: result.setdefault("loads", load.run_load(
                traffic, clients, [windows_of(a) for a in range(n_arrays)],
                warm, offsets, start, args.seconds, GRACE_S)),
            name="load", daemon=True)
        trace_rows, sync_perf = None, None
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            sync_perf = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
                pass
        worker.start()
        # the window lasts until the last delta owed has arrived
        worker.join(args.seconds + GRACE_S + 300.0)
        gave_up = time.perf_counter()
        if args.trace:
            jax.profiler.stop_trace()
        loads = result.get("loads")
        if loads is None:
            raise RuntimeError("the load loops did not finish")
        run = measure.Run(setup_s=setup_s, start=start, stop=stop, gave_up=gave_up,
                          loop=traffic["loop"], loads=loads)
        compiles, run.compile_s, hits = clock.between(start, run.end)
        log(f"in the window ({run.seconds:.2f} s, sending for {args.seconds:g} s): "
            f"{compiles} compiles ({run.compile_s:.2f} s), {hits} cache hits")
        if args.trace:
            run.spans = [e for e in TRACER.events() if e.t0 + e.dur >= start
                         and e.t0 < run.end]
            path = next(trace_dir.rglob("*.xplane.pb"))
            trace_rows = trace_reduce.read_xplane(path)
        stats = dev.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        for ld in loads:
            if ld.error:
                errors.append(f"array {ld.array}: {ld.error}")
            for r in ld.sent:
                if r.delta is not None:
                    served[ld.array][r.idx] = r.delta
            for d in ld.extra:
                duplicates += d["window_idx"] in served[ld.array]
                served[ld.array].setdefault(d["window_idx"], d)
        unexpected = list(daemon.server.unexpected)
    finally:
        for c in clients:
            c.close()
        daemon.server.shutdown(drain=False)
        shutil.rmtree(scratch, ignore_errors=True)
    errors += [f"server: {u}" for u in unexpected]

    breakdown = None
    if args.trace:
        off = trace_reduce.sync_offset_ns(trace_rows, sync_perf)
        if off is None:
            raise RuntimeError("the trace has no sync marker")
        run.trace, run.trace_lo, run.trace_hi = (trace_rows, start * 1e9 + off,
                                                 run.end * 1e9 + off)
        host = [(e.name, e.t0 * 1e9 + off, (e.t0 + e.dur) * 1e9 + off, e.depth)
                for e in run.spans]
        lo, hi = run.trace_lo, run.trace_hi
        breakdown = {
            "device_ops": trace_reduce.top(trace_reduce.op_seconds(trace_rows, lo, hi)),
            "idle_gaps": trace_reduce.idle_by_host(trace_rows, host, lo, hi),
        }

    # the plain reference, over every window each array was sent
    due = run.due()
    t_ref = time.perf_counter()
    theta_ = theta(cfg, traffic)
    faults = {"load_errors": len(errors), "duplicate_windows": duplicates}
    bad_windows = set()
    for a in range(n_arrays):
        sent = warm + len(loads[a].sent)
        b = bounds[a]
        r = recs[a]
        n_ev = int(b[sent])
        ref = reference.ArrayReference(r.types[:n_ev], r.times[:n_ev], r.num_types,
                                       b[:sent + 1], sent, theta_,
                                       int(cfg["max_level"]),
                                       tuple(cfg["interval_ms"]))
        f, bad = reference.compare(ref, served[a])
        for k, v in f.items():
            faults[k] = faults.get(k, 0) + v
        bad_windows |= {(a, p) for p in bad}
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    log("arrivals (array, window, sent, arrived; s from the start): " + " ".join(
        f"{r.array},{r.idx},{r.sent - run.start:.3f},"
        f"{'-' if r.arrived is None else f'{r.arrived - run.start:.3f}'}"
        for r in due))
    lat = measure.latency_s(run)
    half = (run.start + run.stop) / 2
    log(f"{len(run.delivered())} windows delivered, {len(due)} due; "
        f"latency p50 {measure.percentile(lat, 50)} s "
        f"p90 {measure.percentile(lat, 90)} s (replay: service time from the "
        f"send); backlog at half {load.backlog(loads, half)}, "
        f"at end {load.backlog(loads, run.stop)}")
    for e in errors:
        log(f"error: {e}")
    correct = all(v == 0 for v in faults.values())
    attempted = len(due)
    failed = sum(1 for r in due if r.arrived is None or (r.array, r.idx) in bad_windows)
    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for m in wanted:
        v = spec_mod.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = trace_reduce.busy_seconds(run.trace, run.trace_lo,
                                                     run.trace_hi)
        device["window_s"] = run.seconds
    checks = {k: {"value": v, "limit": 0} for k, v in faults.items()}
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
