"""Paper Table 1 + Fig. 8: crossover points (#episodes below which
MapConcatenate wins) per episode size, and the f(N) = a/N + b vs a·N + b
fit comparison.

Segment parallelism needs real parallel hardware (the paper's thread
blocks; our mesh devices) — on one device PTPE wins at any M (fig7). On a
TPU host the crossover is measured in this process over the real devices;
elsewhere it is emulated in a CPU child with 8 forced host devices
(``JAX_PLATFORMS=cpu``), where ``mapconcatenate_sharded`` fans segments out
across host threads. Every row names the platform it ran on."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from .common import Report

ROOT = Path(__file__).resolve().parent.parent
EMULATED_DEVICES = 8


def crossovers(seconds: int) -> dict:
    """Crossover M per episode size N over this process's devices."""
    import jax

    from repro.core import count_dispatch
    from repro.core.mapconcat import data_mesh, mapconcatenate_sharded
    from repro.data import sym26

    from .common import random_candidates, timeit

    mesh = data_mesh()
    stream, _ = sym26(seconds=seconds, seed=0)
    out = {"platform": jax.devices()[0].platform,
           "devices": mesh.shape["data"]}
    for n in (2, 3, 4, 5, 6):
        probes = []
        for m in (8, 16, 32, 64, 128, 256):
            eps = random_candidates(m, n, seed=n * 31 + m)
            t_p = timeit(lambda: count_dispatch(stream, eps, engine="ptpe"),
                         repeats=2)
            t_m = timeit(lambda: mapconcatenate_sharded(stream, eps, mesh),
                         repeats=2)
            probes.append((m, t_p, t_m))
        # crossover: first M where PTPE <= MapConcat (log-interp between)
        x = probes[-1][0]
        prev = None
        for m, t_p, t_m in probes:
            r = t_p / t_m
            if r <= 1.0:
                if prev is None:
                    x = m
                else:
                    pm, pr = prev
                    f = np.log(pr) / max(np.log(pr) - np.log(r), 1e-9)
                    x = int(np.exp(np.log(pm) + f * (np.log(m)
                                                     - np.log(pm))))
                break
            prev = (m, r)
        out[str(n)] = {"crossover": x,
                  "probes": [(m, round(tp, 4), round(tm, 4))
                             for m, tp, tm in probes]}
    return out


def _emulated(seconds: int) -> dict:
    """``crossovers`` in a CPU child with forced host devices (the flag
    must precede the child's jax import)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={EMULATED_DEVICES}")
    env["PYTHONPATH"] = str(ROOT / "src")
    code = ("import json; from benchmarks.fig8_crossover import crossovers; "
            f"print(json.dumps(crossovers({int(seconds)})))")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, cwd=str(ROOT))
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(seconds: int = 15) -> Report:
    import jax

    rep = Report("fig8_crossover")
    data = (crossovers(seconds) if jax.default_backend() == "tpu"
            else _emulated(seconds))
    where = {"platform": data.pop("platform"), "devices": data.pop("devices")}
    ns = np.array(sorted(int(k) for k in data), float)
    xs = np.array([data[str(int(n))]["crossover"] for n in ns], float)
    for n, x in zip(ns, xs):
        rep.add(f"crossover_N{int(n)}", 0.0, crossover=int(x),
                probes=data[str(int(n))]["probes"], **where)
    A1 = np.stack([1.0 / ns, np.ones_like(ns)], 1)
    A2 = np.stack([ns, np.ones_like(ns)], 1)
    c1, res1, *_ = np.linalg.lstsq(A1, xs, rcond=None)
    c2, res2, *_ = np.linalg.lstsq(A2, xs, rcond=None)
    r1 = float(res1[0]) if len(res1) else 0.0
    r2 = float(res2[0]) if len(res2) else 0.0
    rep.add("fit", 0.0, recip_a=round(float(c1[0]), 1),
            recip_b=round(float(c1[1]), 1), recip_resid=round(r1, 1),
            linear_resid=round(r2, 1),
            reciprocal_fit_better=bool(r1 <= r2), **where)
    rep.save()
    return rep


if __name__ == "__main__":
    run()
