"""Distributed tests (8 host devices, subprocess-isolated so the main
pytest process keeps its single-device view): shard_map MapConcatenate
equals the sequential oracle; compressed cross-pod psum is within
quantization tolerance of exact psum."""

import json
import os
import subprocess
import sys
import textwrap



def _run(script: str) -> dict:
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=str(root))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_shard_map_mapconcatenate_equals_oracle():
    r = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax
        from repro.core import EpisodeBatch, count_a1_sequential
        from repro.core.mapconcat import mapconcatenate_sharded
        from repro.data import random_stream

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        st = random_stream(6, 1200, 8000, seed=3)
        et = rng.integers(0, 6, size=(9, 3)).astype(np.int32)
        tlo = rng.integers(0, 5, size=(9, 2)).astype(np.int32)
        thi = (tlo + rng.integers(1, 8, size=(9, 2))).astype(np.int32)
        eps = EpisodeBatch(et, tlo, thi)
        want = count_a1_sequential(st, eps)
        got = mapconcatenate_sharded(st, eps, mesh, axis="data")
        print(json.dumps({"match": bool((want == got).all()),
                          "want": want.tolist(), "got": got.tolist()}))
    """))
    assert r["match"], r


def test_compressed_psum_close_to_exact():
    r = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.runtime import compressed_psum_ef, zero_residual

        mesh = jax.make_mesh((8,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32)) * 0.1

        def f(gs):
            grads = {"w": gs[0]}
            out, r = compressed_psum_ef(grads, zero_residual(grads), "pod")
            exact = jax.tree.map(lambda x: jax.lax.psum(x, "pod"), grads)
            err = jnp.max(jnp.abs(out["w"] - exact["w"]))
            ref = jnp.max(jnp.abs(exact["w"]))
            # residual must equal the per-device quantization error bound
            rmax = jnp.max(jnp.abs(r["w"]))
            return err[None], ref[None], rmax[None]

        err, ref, rmax = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
            check_vma=False))(g)
        print(json.dumps({"rel": float(err.max() / ref.max()),
                          "rmax": float(rmax.max())}))
    """))
    # Σ of 8 int8-rounded shards: error ≤ 8·(s/2) ≈ 8/254 of amax ≈ 3%
    assert r["rel"] < 0.05, r
    assert r["rmax"] > 0  # error feedback actually carries the residual
