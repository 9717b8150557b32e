"""Randomized cross-engine equivalence harness.

The repo's exactness story rests on hand-picked adversarial cases
(mid-tie splits, exactly-τ+W straddlers). This harness pins the claim
down the other way: generate random (stream, episode batch, lcap,
segment count, window partition) tuples — τ ties, lcap-overflow
pressure, arbitrary cut points included — and assert that EVERY engine
returns bit-identical counts:

  * one-shot ``count_dispatch`` over ptpe / mapconcatenate /
    mapconcat_kernel / mapconcat_sharded == the sequential oracle;
  * ``count_two_pass`` per engine: exact counts for survivors, the A2
    upper bound and cull mask consistent with the scan reference;
  * ``StreamingCounter`` per engine × {unbounded, bounded} over the
    random window partition == the oracle (per-window snapshots of the
    two residencies equal each other, final counts equal the oracle);
  * ``StreamingA2Counter`` chunked == one-shot A2.

Hypothesis drives the sweep when installed (``REPRO_EQ_EXAMPLES``
scales it — 60 examples/function by default, so a default local run
generates 240+ cases; ``derandomize=True`` keeps CI subsets
deterministic); without hypothesis a fixed seed sweep runs the same
property. Kernel engines join the sweep automatically when the dispatch
policy allows (TPU or ``REPRO_KERNEL_INTERPRET=1``), and the sharded
engine exercises real multi-device dispatch when the process has >1
device (the CI job forces ``--xla_force_host_platform_device_count=8``).
Single-device runs still cover the sharded entry points' fallback
contract; the subprocess tests at the bottom always exercise the real
8-device sharded launches and the cross-device-count checkpoint
portability, regardless of the host process's device view.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # deterministic fallback sweep below
    HAVE_HYPOTHESIS = False

from repro.core import (EpisodeBatch, EventStream, StreamingA2Counter,
                        StreamingCounter, count_a1_sequential, count_a2,
                        count_dispatch, count_two_pass)

MAX_EXAMPLES = int(os.environ.get("REPRO_EQ_EXAMPLES", "60"))
FALLBACK_SEEDS = list(range(10))


def _kernel_available() -> bool:
    try:
        from repro.kernels import ops as kops
        kops.kernel_mode()
        return True
    except kops.KernelDeclined:
        return False


def engines_under_test():
    """ptpe + XLA mapconcatenate always; the kernel engines when the
    dispatch policy engages them. mapconcat_sharded is included even
    single-device — its graceful-degradation contract (fall back to the
    single-device kernel / XLA paths, bit-identically) is part of what
    the harness pins down."""
    engines = ["ptpe", "mapconcatenate", "mapconcat_sharded"]
    if _kernel_available():
        engines.insert(2, "mapconcat_kernel")
    return engines


def make_case(seed: int):
    """One random case: tie-heavy stream, random episode batch (random τ
    bounds — equal timestamps land on zone boundaries), lcap chosen to
    sometimes force live evictions (the ovf exact-recount path), random
    segment count, random window cut points (mid-tie cuts included)."""
    rng = np.random.default_rng(seed)
    n_ev = int(rng.integers(150, 400))
    num_types = int(rng.integers(3, 7))
    gaps = rng.choice([0, 0, 1, 1, 2, 3, 8], size=n_ev)
    times = (np.cumsum(gaps) + 1).astype(np.int32)
    types = rng.integers(0, num_types, size=n_ev).astype(np.int32)
    stream = EventStream(types, times, num_types)
    n = int(rng.integers(2, 4))
    m = 6
    et = rng.integers(0, num_types, size=(m, n)).astype(np.int32)
    tlo = rng.integers(0, 4, size=(m, n - 1)).astype(np.int32)
    thi = (tlo + rng.integers(1, 7, size=(m, n - 1))).astype(np.int32)
    eps = EpisodeBatch(et, tlo, thi)
    lcap = int(rng.choice([1, 2, 4]))
    num_segments = int(rng.choice([2, 4, 8]))
    k = int(rng.integers(2, 6))
    cuts = np.sort(rng.choice(np.arange(1, n_ev), size=k - 1,
                              replace=False))
    return stream, eps, lcap, num_segments, cuts


def split_at(stream: EventStream, cuts) -> list[EventStream]:
    idx = [0] + [int(c) for c in cuts] + [len(stream.types)]
    return [EventStream(stream.types[a:b], stream.times[a:b],
                        stream.num_types)
            for a, b in zip(idx[:-1], idx[1:])]


# ------------------------------------------------------------ properties


def check_dispatch(seed: int):
    stream, eps, lcap, num_segments, _ = make_case(seed)
    want = count_a1_sequential(stream, eps)
    for engine in engines_under_test():
        got = count_dispatch(stream, eps, engine=engine, lcap=lcap,
                             num_segments=num_segments)
        np.testing.assert_array_equal(
            got, want, err_msg=f"seed {seed} engine {engine} "
                               f"lcap={lcap} P={num_segments}")


def check_two_pass(seed: int):
    stream, eps, lcap, num_segments, _ = make_case(seed)
    want = count_a1_sequential(stream, eps)
    a2_ref = count_a2(stream, eps, use_kernel=False)
    theta = max(1, int(np.median(a2_ref)))
    for engine in engines_under_test():
        res = count_two_pass(stream, eps, theta=theta, engine=engine,
                             lcap=lcap, num_segments=num_segments)
        msg = f"seed {seed} engine {engine} theta={theta}"
        np.testing.assert_array_equal(res.a2_counts, a2_ref, err_msg=msg)
        np.testing.assert_array_equal(res.survived, a2_ref >= theta,
                                      err_msg=msg)
        np.testing.assert_array_equal(res.counts[res.survived],
                                      want[res.survived], err_msg=msg)
        np.testing.assert_array_equal(
            res.frequent, res.survived & (res.counts >= theta),
            err_msg=msg)
        # Theorem 5.1 on the random case: the cull never removes a truly
        # frequent episode
        assert not ((want >= theta) & ~res.survived).any(), msg


def check_streaming(seed: int):
    stream, eps, lcap, num_segments, cuts = make_case(seed)
    want = count_a1_sequential(stream, eps)
    windows = split_at(stream, cuts)
    for engine in ("ptpe", "mapconcatenate", "mapconcat_sharded"):
        ctr = StreamingCounter(eps, engine=engine, lcap=lcap,
                               num_segments=num_segments)
        bnd = StreamingCounter(eps, engine=engine, lcap=lcap,
                               num_segments=num_segments,
                               checkpoint_interval=2)
        for i, w in enumerate(windows):
            final = i == len(windows) - 1
            got = ctr.update(w, final=final)
            got_b = bnd.update(w, final=final)
            np.testing.assert_array_equal(
                got_b, got, err_msg=f"seed {seed} engine {engine} "
                                    f"window {i}: bounded != unbounded")
        np.testing.assert_array_equal(
            got, want, err_msg=f"seed {seed} engine {engine} final")


def check_streaming_a2(seed: int):
    stream, eps, _, _, cuts = make_case(seed)
    want = count_a2(stream, eps, use_kernel=False)
    a2c = StreamingA2Counter(eps.relaxed())
    for w in split_at(stream, cuts):
        got = a2c.update(w)
    np.testing.assert_array_equal(got, want,
                                  err_msg=f"seed {seed} streaming A2")


if HAVE_HYPOTHESIS:
    _settings = settings(max_examples=MAX_EXAMPLES, deadline=None,
                         derandomize=True)

    @_settings
    @given(hst.integers(0, 10_000_000))
    def test_dispatch_engines_bit_equal(seed):
        check_dispatch(seed)

    @_settings
    @given(hst.integers(0, 10_000_000))
    def test_two_pass_bit_equal(seed):
        check_two_pass(seed)

    @_settings
    @given(hst.integers(0, 10_000_000))
    def test_streaming_modes_bit_equal(seed):
        check_streaming(seed)

    @_settings
    @given(hst.integers(0, 10_000_000))
    def test_streaming_a2_bit_equal(seed):
        check_streaming_a2(seed)
else:  # deterministic sweep over the same seed-driven strategy
    @pytest.mark.parametrize("seed", FALLBACK_SEEDS)
    def test_dispatch_engines_bit_equal(seed):
        check_dispatch(seed)

    @pytest.mark.parametrize("seed", FALLBACK_SEEDS)
    def test_two_pass_bit_equal(seed):
        check_two_pass(seed)

    @pytest.mark.parametrize("seed", FALLBACK_SEEDS)
    def test_streaming_modes_bit_equal(seed):
        check_streaming(seed)

    @pytest.mark.parametrize("seed", FALLBACK_SEEDS)
    def test_streaming_a2_bit_equal(seed):
        check_streaming_a2(seed)


# ----------------------------------------- real multi-device (subprocess)
#
# The host pytest process usually sees one device; these force 8 host
# devices (XLA_FLAGS must precede the jax import, hence subprocesses) and
# interpret-mode kernels, so the *real* sharded launches run on CPU CI.


_ROOT = Path(__file__).resolve().parent.parent


def _run(script: str, timeout: int = 900) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_ROOT / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["REPRO_KERNEL_INTERPRET"] = "1"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=str(_ROOT),
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_CASE_SRC = textwrap.dedent(f"""
    import sys
    sys.path.insert(0, {str(_ROOT / "tests")!r})
    from test_equivalence_random import make_case, split_at
""")


def test_sharded_dispatch_equals_oracle_8dev():
    """Random cases on a real 8-device mesh: the sharded engine (and its
    per-device-count variants) == segmented kernel == XLA == oracle, and
    the sharded kernel dispatch actually ran (KERNEL_CALLS)."""
    r = _run(_CASE_SRC + textwrap.dedent("""
        import json
        import numpy as np
        from repro.core import count_a1_sequential, count_dispatch
        from repro.core.mapconcat import mapconcatenate_sharded_kernel
        from repro.kernels import ops as kops

        checked = 0
        for seed in (11, 29, 47):
            stream, eps, lcap, num_segments, _ = make_case(seed)
            want = count_a1_sequential(stream, eps)
            for engine in ("mapconcatenate", "mapconcat_kernel",
                           "mapconcat_sharded"):
                got = count_dispatch(stream, eps, engine=engine,
                                     lcap=lcap,
                                     num_segments=num_segments)
                assert (got == want).all(), (seed, engine)
                checked += 1
            for d in (2, 4, 8):
                got = mapconcatenate_sharded_kernel(
                    stream, eps, num_segments=8, lcap=lcap,
                    num_devices=d)
                assert (got == want).all(), (seed, d)
                checked += 1
        print(json.dumps({"checked": checked,
                          "shard_calls":
                              kops.KERNEL_CALLS["a1_mapc_shard"]}))
    """))
    assert r["checked"] == 18
    assert r["shard_calls"] > 0


def test_sharded_streaming_equals_oracle_8dev():
    """Streaming sharded residency on a real mesh: per-commit sharded
    launches over random window partitions == oracle, including bounded
    mode and the lcap=1 ovf fallback."""
    r = _run(_CASE_SRC + textwrap.dedent("""
        import json
        import numpy as np
        from repro.core import StreamingCounter, count_a1_sequential
        from repro.kernels import ops as kops

        checked = 0
        for seed, lcap in ((5, 4), (13, 1)):
            stream, eps, _, num_segments, cuts = make_case(seed)
            want = count_a1_sequential(stream, eps)
            for interval in (None, 2):
                ctr = StreamingCounter(
                    eps, engine="mapconcat_sharded", lcap=lcap,
                    num_segments=num_segments,
                    checkpoint_interval=interval)
                assert ctr._shard_d == 8
                windows = split_at(stream, cuts)
                for i, w in enumerate(windows):
                    got = ctr.update(w, final=i == len(windows) - 1)
                assert (got == want).all(), (seed, lcap, interval)
                checked += 1
        print(json.dumps({"checked": checked,
                          "shard_calls":
                              kops.KERNEL_CALLS["a1_mapc_shard"]}))
    """))
    assert r["checked"] == 4
    assert r["shard_calls"] > 0


def test_state_dict_portable_8dev_to_1dev(tmp_path):
    """Checkpoint portability, sharded → single-device: a state_dict
    written under 8-device sharded residency restores onto this (single
    device, scan-residency) process's counter; the resumed counts equal
    the oracle on the full stream."""
    ck = tmp_path / "sharded.npz"
    stream, eps, lcap, num_segments, cuts = make_case(101)
    windows = split_at(stream, cuts)
    cut = len(windows) // 2
    r = _run(_CASE_SRC + textwrap.dedent(f"""
        import json
        import numpy as np
        from repro.core import StreamingCounter

        stream, eps, lcap, num_segments, cuts = make_case(101)
        windows = split_at(stream, cuts)
        ctr = StreamingCounter(eps, engine="mapconcat_sharded",
                               lcap=lcap, num_segments=num_segments)
        assert ctr._shard_d == 8
        for w in windows[:{cut}]:
            ctr.update(w)
        np.savez({str(ck)!r}, **ctr.state_dict())
        print(json.dumps({{"ok": True}}))
    """))
    assert r["ok"]
    resumed = StreamingCounter(eps, engine="mapconcatenate", lcap=lcap,
                               num_segments=num_segments)
    with np.load(ck) as d:
        resumed.load_state_dict(dict(d))
    for i, w in enumerate(windows[cut:]):
        got = resumed.update(w, final=cut + i == len(windows) - 1)
    np.testing.assert_array_equal(got, count_a1_sequential(stream, eps))


def test_state_dict_portable_1dev_to_8dev(tmp_path):
    """And the reverse: a single-device (scan-residency) checkpoint
    restores under 8-device sharded residency and finishes with
    oracle-exact counts."""
    ck = tmp_path / "single.npz"
    stream, eps, lcap, num_segments, cuts = make_case(202)
    windows = split_at(stream, cuts)
    cut = max(1, len(windows) // 2)
    ctr = StreamingCounter(eps, engine="mapconcatenate", lcap=lcap,
                           num_segments=num_segments)
    for w in windows[:cut]:
        ctr.update(w)
    np.savez(ck, **ctr.state_dict())
    want = count_a1_sequential(stream, eps)
    r = _run(_CASE_SRC + textwrap.dedent(f"""
        import json
        import numpy as np
        from repro.core import StreamingCounter
        from repro.kernels import ops as kops

        stream, eps, lcap, num_segments, cuts = make_case(202)
        windows = split_at(stream, cuts)
        ctr = StreamingCounter(eps, engine="mapconcat_sharded",
                               lcap=lcap, num_segments=num_segments)
        assert ctr._shard_d == 8
        with np.load({str(ck)!r}) as d:
            ctr.load_state_dict(dict(d))
        for i, w in enumerate(windows[{cut}:]):
            got = ctr.update(w, final={cut} + i == len(windows) - 1)
        print(json.dumps({{"counts": got.tolist()}}))
    """))
    np.testing.assert_array_equal(np.asarray(r["counts"]), want)
