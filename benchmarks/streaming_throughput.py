"""Sustained streaming throughput: carried machines vs restart-per-window.

The companion accelerator paper (arXiv:0905.2203) makes sustained
events/sec across stream partitions the figure of merit. This benchmark
counts a fixed candidate batch over a sym26 spike stream window-by-window
three ways:

* ``kernel``  — ``StreamingCounter`` with the carried Pallas kernel:
  machine state resident in the kernel's brick layout, one
  state-in/state-out launch per window (compiled on TPU; interpret mode
  with ``--kernel interpret`` — an emulation-speed *path* check on CPU,
  not a fair timing).
* ``carry``   — the carried XLA scan (``use_kernel=False``):
  shape-bucketed staging, window p+1 staged while window p counts.
* ``restart`` — the seed behavior: a fresh one-shot count per window
  (state rebuilt, per-window shapes recompiled as they vary, boundary
  occurrences lost).

Reported per window size: sustained events/sec (whole session), steady
events/sec (first, compile-warming window excluded), and the boundary
occurrences the restart baseline lost (both carried variants are asserted
bit-equal to one-shot counting on the full stream before any timing is
trusted).

A ``--segments`` sweep benchmarks the in-kernel MapConcatenate
(segments × window size): ``StreamingCounter`` on the segmented-kernel
residency (one Pallas launch per commit, grid = episode tile × time
segment, Concatenate fold fused on-chip). Besides wall-clock ev/s it
records the *serial-step proxy* — the longest per-segment event walk of a
one-shot segmentation, i.e. the per-worker critical path the paper's
mapping shortens from n to ~n/P + 2W. On CPU CI (interpret mode =
emulation speed) the proxy is the meaningful scaling signal; on TPU the
wall clock is.

A ``--devices`` sweep benchmarks the mesh-sharded residency
(``engine="mapconcat_sharded"``): one child process per device count
(``XLA_FLAGS=--xla_force_host_platform_device_count=d`` must precede the
jax import, hence subprocesses) runs the sharded streaming counter and
reports wall clock plus the per-*device* serial-step proxy — the longest
per-device segment-group walk, ceil(P/d) × steps-per-segment, i.e. the
critical path the data-axis sharding divides by d while the all-gathered
tuple fold stays O(P·N). Forced host devices share the physical CPU, so
wall clock is the TPU-side signal and the proxy the CPU CI one, as above.

Usage:
  PYTHONPATH=src python benchmarks/streaming_throughput.py \
      [--seconds 12] [--m 128] [--n 3] [--windows-ms 2000 4000 8000] \
      [--kernel auto|interpret|off] [--segments 1 2 4 8] \
      [--devices 1 2 4 8]
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

import numpy as np

try:  # package mode (python -m benchmarks.run)
    from .common import Report, random_candidates, sym26_stream
except ImportError:  # direct script mode
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from common import Report, random_candidates, sym26_stream

from repro.core import StreamingCounter, count_a1  # noqa: E402
from repro.data import partition_windows  # noqa: E402
from repro.obs import TRACER  # noqa: E402
from repro.telemetry import ThroughputMeter  # noqa: E402

_HOST_SPANS = ("stream.prepare", "stream.commit", "stream.checkpoint")


def stream_phases() -> dict:
    """Host-vs-device split of the buffered stream.* spans: prepare /
    commit / checkpoint are host-side staging, launch is the dispatch
    call's wall time (a device lower bound on accelerator backends)."""
    host = dev = 0.0
    for e in TRACER.events():
        if e.name in _HOST_SPANS:
            host += e.dur
        elif e.name == "stream.launch":
            dev += e.dur
    return {"host_s": round(host, 4), "device_s": round(dev, 4)}


def bench_carry(windows, eps, engine, use_kernel=False, num_segments=8):
    ctr = StreamingCounter(eps, engine=engine, use_kernel=use_kernel,
                           num_segments=num_segments)
    meter = ThroughputMeter()
    TRACER.clear()  # per-run phase attribution (stream_phases)
    gen = ctr.run(windows)
    for w in windows:
        meter.start()
        out = next(gen)
        meter.stop(len(w))
    return out, meter, ctr


def serial_step_proxy(stream, eps, num_segments):
    """Longest per-segment event walk of a one-shot P-way segmentation —
    the per-worker critical path (fori_loop trips per grid step) that the
    segmented kernel shortens from n to ~n/P + 2W. Interpret-mode CI uses
    this as the scaling signal; compiled runs use the wall clock."""
    from repro.core import make_segments
    w_max = int(np.asarray(eps.max_span).max())
    tau, wt, _ = make_segments(stream, num_segments, w_max)
    return int(wt.shape[1]), int(wt.shape[0])


def _sharded_rows(d: int, seconds: int, m: int, n: int, windows_ms,
                  num_segments: int = 8):
    """Sharded streaming counter per window size over this process's
    devices (``d`` of them), exactness asserted; returns the rows."""
    import jax

    from repro.kernels import ops as kops

    stream, truth = sym26_stream(seconds=seconds)
    eps = random_candidates(m, n,
                            include=[truth["short"][0], truth["long"][0]])
    oracle = count_a1(stream, eps, use_kernel=False)
    rows = []
    for wms in windows_ms:
        windows = list(partition_windows(stream, wms))
        calls0 = kops.KERNEL_CALLS["a1_mapc_shard"]
        final, meter, ctr = bench_carry(windows, eps, "mapconcat_sharded",
                                        use_kernel=True,
                                        num_segments=num_segments)
        np.testing.assert_array_equal(
            final, oracle,
            err_msg=f"sharded counts diverged at {wms}ms devices={d}")
        s = meter.summary()
        # a capable counter may still take single-device launches on every
        # commit (spans too short for one stitch-safe segment per device);
        # tag the mode — and claim the d-way proxy division — only when
        # sharded launches actually ran
        sharded_ran = kops.KERNEL_CALLS["a1_mapc_shard"] > calls0
        d_eff = max(ctr._shard_d, 1) if sharded_ran else 1
        steps, p_eff = serial_step_proxy(stream, eps,
                                         max(num_segments, d_eff))
        per_dev = steps * -(p_eff // -d_eff)  # ceil(P/d) groups per device
        mode = ("sharded-kernel" if sharded_ran
                else ("kernel" if ctr._mapc_kernel else "fallback-xla"))
        rows.append({
            "label": f"mapcs/w{wms}/d{d}", "seconds": s["seconds"],
            "platform": jax.devices()[0].platform,
            "devices": d_eff, "segments": p_eff,
            "windows": s["windows"], "events": s["events"],
            "ev_per_s": round(s["events_per_sec"]),
            "steady_ev_per_s": round(s["steady_events_per_sec"]),
            "serial_steps_per_device": per_dev,
            "proxy_speedup_vs_1dev": round(p_eff * steps / per_dev, 3),
            "mapc_mode": mode})
    return rows


def _emulated_rows(d, seconds, m, n, windows_ms, kernel):
    """One CPU child with ``d`` forced host devices (the flag must precede
    its jax import). ``JAX_PLATFORMS=cpu`` keeps the child off any
    accelerator the parent may hold. Returns its rows, or None."""
    import json
    import subprocess

    script = Path(__file__).resolve()
    root = script.parent.parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
    env["PYTHONPATH"] = str(root / "src")
    if kernel == "interpret":
        env["REPRO_KERNEL_INTERPRET"] = "1"
    cmd = [sys.executable, str(script), "--sharded-child", str(d),
           "--seconds", str(seconds), "--m", str(m), "--n", str(n),
           "--windows-ms"] + [str(w) for w in windows_ms]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         cwd=str(root))
    if out.returncode != 0:
        print(f"[stream-bench] devices={d} sweep failed:\n"
              f"{out.stderr[-2000:]}", file=sys.stderr)
        return None
    return json.loads(out.stdout.strip().splitlines()[-1])


def _sharded_sweep(rep, devices, seconds, m, n, windows_ms, kernel):
    """``--devices``: with a TPU, one in-process run over the host's real
    devices (the chip belongs to this process); without one, a
    forced-host-device CPU emulation per requested device count."""
    import jax

    from repro.core.mapconcat import shard_device_count

    if jax.default_backend() == "tpu":
        sweeps = [_sharded_rows(shard_device_count(), seconds, m, n,
                                windows_ms)]
    else:
        sweeps = [_emulated_rows(d, seconds, m, n, windows_ms, kernel)
                  for d in devices]
    for rows in sweeps:
        for row in rows or ():
            label = row.pop("label")
            seconds_row = row.pop("seconds")
            rep.add(label, seconds_row, **row)
            print(f"[stream-bench] {label} ({row['mapc_mode']}, "
                  f"{row['platform']}): "
                  f"{row['steady_ev_per_s']:,} ev/s steady, "
                  f"{row['serial_steps_per_device']} serial steps/device "
                  f"({row['proxy_speedup_vs_1dev']:.2f}x vs 1-dev)")


def bench_restart(windows, eps):
    meter = ThroughputMeter()
    total = np.zeros(eps.M, np.int64)
    for w in windows:
        meter.start()
        total += count_a1(w, eps, use_kernel=False)
        meter.stop(len(w))
    return total, meter


def run(seconds: int = 12, m: int = 128, n: int = 3,
        windows_ms=(2000, 4000, 8000), engine: str = "ptpe",
        kernel: str = "auto", segments=(), devices=()):
    if kernel == "interpret":
        os.environ["REPRO_KERNEL_INTERPRET"] = "1"
    stream, truth = sym26_stream(seconds=seconds)
    eps = random_candidates(m, n,
                            include=[truth["short"][0], truth["long"][0]])
    oracle = count_a1(stream, eps, use_kernel=False)
    rep = Report("streaming_throughput")

    if segments and kernel != "off":
        # segmented-kernel sweep: segments × window size, exactness
        # asserted per cell, serial-step proxy vs the 1-segment kernel
        steps1, _ = serial_step_proxy(stream, eps, 1)
        for wms in windows_ms:
            windows = list(partition_windows(stream, wms))
            for p in segments:
                final, meter, ctr = bench_carry(
                    windows, eps, "mapconcatenate", use_kernel=True,
                    num_segments=p)
                np.testing.assert_array_equal(
                    final, oracle,
                    err_msg=f"segmented-kernel counts diverged at "
                            f"{wms}ms P={p}")
                steps, p_eff = serial_step_proxy(stream, eps, p)
                s = meter.summary()
                mode = ("kernel" if ctr._mapc_kernel else "fallback-xla")
                rep.add(f"mapck/w{wms}/p{p}", s["seconds"],
                        segments=p_eff, windows=s["windows"],
                        events=s["events"],
                        ev_per_s=round(s["events_per_sec"]),
                        steady_ev_per_s=round(s["steady_events_per_sec"]),
                        serial_steps_per_segment=steps,
                        proxy_speedup_vs_1seg=round(steps1 / steps, 3),
                        mapc_mode=mode, **stream_phases())
                print(f"[stream-bench] mapck w={wms}ms P={p_eff} "
                      f"({mode}): {s['steady_events_per_sec']:,.0f} ev/s "
                      f"steady, serial steps/segment {steps} "
                      f"({steps1 / steps:.2f}x vs 1-seg)")

    if devices and kernel != "off":
        # mesh-sharded sweep: one subprocess per device count
        _sharded_sweep(rep, devices, seconds, m, n, windows_ms, kernel)

    for wms in windows_ms:
        windows = list(partition_windows(stream, wms))
        kernel_line = ""
        if kernel != "off":
            kfinal, meter_k, kctr = bench_carry(windows, eps, engine,
                                                use_kernel=True)
            np.testing.assert_array_equal(
                kfinal, oracle,
                err_msg=f"kernel-carry counts diverged at {wms}ms")
            sk = meter_k.summary()
            mode = ("interpret" if kernel == "interpret"
                    else ("compiled" if kctr._kernel else "fallback-scan"))
            rep.add(f"kernel/w{wms}", sk["seconds"],
                    windows=sk["windows"], events=sk["events"],
                    ev_per_s=round(sk["events_per_sec"]),
                    steady_ev_per_s=round(sk["steady_events_per_sec"]),
                    kernel_mode=mode, **stream_phases())
            kernel_line = (f"kernel({mode}) "
                           f"{sk['steady_events_per_sec']:,.0f} ev/s vs ")
        final, meter_c, _ = bench_carry(windows, eps, engine)
        carry_phases = stream_phases()
        np.testing.assert_array_equal(
            final, oracle,
            err_msg=f"carry counts diverged from one-shot at {wms}ms")
        restart_total, meter_r = bench_restart(windows, eps)
        lost = int((oracle - restart_total).sum())
        sc, sr = meter_c.summary(), meter_r.summary()
        rep.add(f"carry/w{wms}", sc["seconds"],
                windows=sc["windows"], events=sc["events"],
                ev_per_s=round(sc["events_per_sec"]),
                steady_ev_per_s=round(sc["steady_events_per_sec"]),
                **carry_phases)
        rep.add(f"restart/w{wms}", sr["seconds"],
                windows=sr["windows"], events=sr["events"],
                ev_per_s=round(sr["events_per_sec"]),
                steady_ev_per_s=round(sr["steady_events_per_sec"]),
                boundary_occurrences_lost=lost)
        speedup = (sr["seconds"] / sc["seconds"]) if sc["seconds"] else 0.0
        print(f"[stream-bench] window {wms} ms: {kernel_line}carry "
              f"{sc['steady_events_per_sec']:,.0f} ev/s steady vs restart "
              f"{sr['steady_events_per_sec']:,.0f} ev/s "
              f"({speedup:.2f}x wall), restart lost {lost} boundary "
              f"occurrences")
    rep.save()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=int, default=12)
    ap.add_argument("--m", type=int, default=128,
                    help="candidate batch size")
    ap.add_argument("--n", type=int, default=3, help="episode size")
    ap.add_argument("--windows-ms", type=int, nargs="+",
                    default=[2000, 4000, 8000])
    ap.add_argument("--engine", default="ptpe",
                    choices=["ptpe", "mapconcatenate", "hybrid", "mapconcat_kernel"])
    ap.add_argument("--kernel", default="auto",
                    choices=["auto", "interpret", "off"],
                    help="carried-kernel variant: auto = dispatch policy "
                         "decides (compiled on TPU, scan fallback on CPU), "
                         "interpret = force interpret-mode kernels "
                         "(path check; emulation speed), off = skip")
    ap.add_argument("--segments", type=int, nargs="*", default=[],
                    help="in-kernel MapConcatenate sweep: one "
                         "segmented-kernel run per (window size, P)")
    ap.add_argument("--devices", type=int, nargs="*", default=[],
                    help="mesh-sharded sweep: on a TPU host one run over "
                         "the real devices; elsewhere one forced-host-"
                         "device-count CPU subprocess per d, sharded "
                         "streaming counter per window size")
    ap.add_argument("--sharded-child", type=int, default=None,
                    help=argparse.SUPPRESS)  # internal: --devices child
    args = ap.parse_args()
    if args.sharded_child is not None:
        import json
        print(json.dumps(_sharded_rows(args.sharded_child, args.seconds,
                                       args.m, args.n, args.windows_ms)))
        return
    run(seconds=args.seconds, m=args.m, n=args.n,
        windows_ms=args.windows_ms, engine=args.engine, kernel=args.kernel,
        segments=tuple(args.segments), devices=tuple(args.devices))


if __name__ == "__main__":
    main()
