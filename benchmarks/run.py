"""Benchmark entrypoint — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV lines (the harness contract) and
writes JSON rows under experiments/bench/. The dry-run/roofline benchmarks
(40-cell table) live in repro.launch.dryrun / repro.launch.roofline — they
need the 512-device flag and are not imported here."""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="shorter streams (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated figure names (fig7,fig8,...)")
    args = ap.parse_args()
    seconds = 8 if args.quick else 20

    from . import (fig7_mapping, fig8_crossover, fig9_twopass,
                   fig10_resources, fig11_engine_vs_sequential,
                   service_scale, service_wire, streaming_throughput)
    figs = {
        "fig7": lambda: fig7_mapping.run(seconds=min(seconds, 20),
                                         segments=(1, 2, 4, 8)),
        "fig8": lambda: fig8_crossover.run(seconds=min(seconds, 15)),
        "fig9": lambda: fig9_twopass.run(seconds=min(seconds, 20)),
        "fig10": lambda: fig10_resources.run(seconds=min(seconds, 20)),
        "fig11": lambda: fig11_engine_vs_sequential.run(
            seconds=min(seconds, 10)),
        "stream": lambda: streaming_throughput.run(
            seconds=min(seconds, 12),
            segments=(1, 2) if args.quick else (1, 2, 4),
            devices=(1, 2) if args.quick else (1, 2, 4)),
        "service": lambda: service_scale.run(
            sessions=(2, 8) if args.quick else (2, 4, 8),
            seconds=min(seconds, 8)),
        "wire": lambda: service_wire.run(seconds=min(seconds, 8)),
    }
    chosen = args.only.split(",") if args.only else list(figs)
    t0 = time.perf_counter()
    print("name,us_per_call,derived")
    for name in chosen:
        figs[name]()
    print(f"# total {time.perf_counter() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
