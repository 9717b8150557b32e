"""One-shot dispatch calibration CLI (ROADMAP "Calibrated dispatch").

Times every available engine over a small (N, M, n, q) grid on the
actual hardware, fits the per-engine cost model (``core.calibrate``)
seeded by the roofline constants this module shares with
``launch/roofline.py``, and caches the fitted table per device kind
under the service data dir — atomically, with a versioned schema that
invalidates on device-kind or code-version change.

Usage:
  PYTHONPATH=src python -m repro.launch.calibrate            # full grid
  PYTHONPATH=src python -m repro.launch.calibrate --smoke    # CI-sized
      [--out PATH] [--data-dir DIR] [--repeats K] [--hlo] [--json-out P]

The cached table is consulted when a process opts in: serving via
``mine_serve --calibrate/--policy-table``, anything via the
``REPRO_POLICY_TABLE`` / ``REPRO_CALIBRATION_DIR`` environment hooks.
``--hlo`` additionally lowers the PTPE scan core for one representative
grid point and records the loop-corrected HLO traffic
(``launch/hlo_analysis``) next to the fit — the measured-bytes
cross-check for the analytic seed.
"""

from __future__ import annotations

import argparse
import json
import sys

from repro.core.calibrate import (CalibrationTable, GridSpec,
                                  calibrate_and_save, device_fingerprint)
from repro.runtime.compile_cache import enable_compile_cache

from .roofline import HBM_BW, ICI_BW, PEAK_FLOPS

# the analytic-seed hardware envelope, shared with the roofline pass so
# the dispatcher's cost model and the dry-run analysis cannot disagree
# about what the hardware is
ROOFLINE_HW = {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
               "ici_bw": ICI_BW}


def hlo_traffic_probe(n_episode: int = 3, m: int = 64,
                      n_events: int = 1024, lcap: int = 4) -> dict:
    """Lower the PTPE scan core at one grid point and return the
    loop-corrected HLO traffic/FLOP totals plus the HBM-implied
    seconds — the measured-bytes sanity check for ``analytic_seconds``."""
    import jax
    import jax.numpy as jnp

    from repro.core.count_a1 import _a1_scan_core
    from repro.core.events import TIME_NEG_INF

    from .hlo_analysis import analyze

    et = jnp.zeros((m, n_episode), jnp.int32)
    tlo = jnp.full((m, n_episode - 1), 5, jnp.int32)
    thi = jnp.full((m, n_episode - 1), 10, jnp.int32)
    ev_t = jnp.zeros((n_events,), jnp.int32)
    ev_tt = jnp.arange(n_events, dtype=jnp.int32)
    s = jnp.full((m, n_episode, lcap), TIME_NEG_INF, jnp.int32)
    text = jax.jit(_a1_scan_core).lower(
        et, tlo, thi, ev_t, ev_tt, s, jnp.zeros((m, n_episode), jnp.int32),
        jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.bool_)) \
        .compile().as_text()
    summ = analyze(text)
    return {"point": {"n_episode": n_episode, "m": m,
                      "n_events": n_events, "lcap": lcap},
            "traffic_bytes": summ.traffic_bytes,
            "dot_flops": summ.dot_flops,
            "hbm_implied_s": summ.traffic_bytes / HBM_BW}


def run(spec: GridSpec, *, out_path: str | None, data_dir: str | None,
        hlo: bool = False, quiet: bool = False) -> tuple[CalibrationTable,
                                                         str]:
    def progress(pt):
        if not quiet:
            print(f"[calibrate] {pt['engine']:>18} N={pt['n_episode']} "
                  f"M={pt['m']:<4} n={pt['n_events']:<5} q={pt['q']:<2} "
                  f"-> {pt['seconds']*1e3:8.2f} ms")
    table, path = calibrate_and_save(
        spec, hw=ROOFLINE_HW, out_path=out_path, data_dir=data_dir,
        progress=progress)
    if hlo:
        table.meta["hlo"] = hlo_traffic_probe()
        table.save(path)
    return table, path


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        description="Calibrate the dispatch cost model on this host.")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grid (one warmup + one sample per "
                         "point, short streams)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="table path (default: per-device-kind cache "
                         "under the service data dir)")
    ap.add_argument("--data-dir", default=None, metavar="DIR",
                    help="calibration cache dir (default: "
                         "$REPRO_CALIBRATION_DIR or "
                         "$REPRO_DATA_DIR/calibration or "
                         "serve-data/calibration)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timed samples per grid point (first, "
                         "jit-compiling call always discarded)")
    ap.add_argument("--hlo", action="store_true",
                    help="record the loop-corrected HLO traffic of the "
                         "PTPE scan core next to the fit "
                         "(launch/hlo_analysis)")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="also dump the fitted table document here")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    spec = GridSpec.smoke() if args.smoke else GridSpec()
    if args.repeats is not None:
        import dataclasses
        spec = dataclasses.replace(spec, repeats=max(args.repeats, 1))
    print(f"[calibrate] device {device_fingerprint()}, "
          f"{'smoke' if args.smoke else 'full'} grid "
          f"({len(spec.points())} admission points)")
    table, path = run(spec, out_path=args.out, data_dir=args.data_dir,
                      hlo=args.hlo, quiet=args.quiet)
    print(f"[calibrate] fitted {sorted(table.coeffs)} over "
          f"{len(table.grid)} measured points; cached at {path}")
    if "hlo" in table.meta:
        h = table.meta["hlo"]
        print(f"[calibrate] HLO cross-check: {h['traffic_bytes']:.3e} B "
              f"-> {h['hbm_implied_s']*1e6:.1f} us HBM-implied at the "
              f"probe point")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(table.to_doc(), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
