"""Pallas kernel validation (interpret=True on CPU) — shape sweeps against
both the layout oracle (kernels/ref.py) and the paper pseudocode oracle."""

import numpy as np
import pytest

from repro.core import EpisodeBatch, count_a1_sequential, count_a2_sequential
from repro.core.count_a1 import count_a1_vectorized
from repro.data import random_stream
from repro.kernels import ops, ref as kref


def _batch(rng, m, n, num_types, relaxed=False):
    et = rng.integers(0, num_types, size=(m, n)).astype(np.int32)
    tlo = rng.integers(0, 5, size=(m, n - 1)).astype(np.int32)
    if relaxed:
        tlo = np.zeros_like(tlo)
    thi = (tlo + rng.integers(1, 10, size=(m, n - 1))).astype(np.int32)
    return EpisodeBatch(et, tlo, thi)


@pytest.mark.parametrize("m", [1, 7, 128, 300])
@pytest.mark.parametrize("n", [2, 3, 5, 8])
def test_a2_kernel_vs_sequential_oracle(m, n):
    rng = np.random.default_rng(n * 100 + m)
    st = random_stream(6, 250, 500, seed=m + n)
    eps = _batch(rng, m, n, 6, relaxed=True)
    want = count_a2_sequential(st, eps)
    got = ops.a2_count(st, eps, force="interpret")
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m", [1, 64, 200])
@pytest.mark.parametrize("n", [2, 4, 6])
@pytest.mark.parametrize("lcap", [2, 4])
def test_a1_kernel_vs_vectorized_and_oracle(m, n, lcap):
    rng = np.random.default_rng(7 * n + m + lcap)
    st = random_stream(5, 250, 400, seed=m * n)
    eps = _batch(rng, m, n, 5)
    kc, kovf = ops.a1_count(st, eps, lcap=lcap, force="interpret")
    vc, vovf = count_a1_vectorized(st, eps, lcap=lcap)
    np.testing.assert_array_equal(kc, vc)  # kernel == XLA-scan engine
    np.testing.assert_array_equal(kovf, vovf)
    want = count_a1_sequential(st, eps)
    exact = ~kovf
    np.testing.assert_array_equal(kc[exact], want[exact])


def test_a2_kernel_layout_oracle_identity():
    """Kernel == its pure-jnp layout oracle on identical padded inputs."""
    rng = np.random.default_rng(0)
    st = random_stream(4, 150, 300, seed=1)
    eps = _batch(rng, 37, 4, 4, relaxed=True)
    et, tlo, thi = ops.episode_layout(eps, inclusive_lower=True)
    ev = ops.event_layout(st, with_dup=False)
    a = ops.a2_count_kernel(et, tlo, thi, ev, n_levels=4, interpret=True)
    b = kref.a2_count_ref(et, tlo, thi, ev, n_levels=4)
    np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b))


def test_a1_kernel_layout_oracle_identity():
    rng = np.random.default_rng(1)
    st = random_stream(4, 150, 300, seed=2)
    eps = _batch(rng, 29, 3, 4)
    et, tlo, thi = ops.episode_layout(eps, inclusive_lower=False)
    ev = ops.event_layout(st, with_dup=True)
    ac, ao = ops.a1_count_kernel(et, tlo, thi, ev, n_levels=3, lcap=4,
                                 interpret=True)
    bc, bo = kref.a1_count_ref(et, tlo, thi, ev, n_levels=3, lcap=4)
    np.testing.assert_array_equal(np.asarray(ac)[0], np.asarray(bc))
    np.testing.assert_array_equal(np.asarray(ao)[0].astype(bool),
                                  np.asarray(bo))


def test_kernel_dispatch_declines_on_cpu(monkeypatch):
    monkeypatch.delenv("REPRO_INTERPRET_KERNELS", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    rng = np.random.default_rng(3)
    st = random_stream(4, 50, 100, seed=3)
    eps = _batch(rng, 8, 3, 4)
    with pytest.raises(ops.KernelDeclined):
        ops.a2_count(st, eps.relaxed())


@pytest.mark.parametrize("n_events", [1, 127, 128, 129])
def test_event_padding_boundaries(n_events):
    rng = np.random.default_rng(n_events)
    st = random_stream(4, n_events, 300, seed=n_events)
    eps = _batch(rng, 16, 3, 4, relaxed=True)
    want = count_a2_sequential(st, eps)
    got = ops.a2_count(st, eps, force="interpret")
    np.testing.assert_array_equal(got, want)


def _lowering_refusal(*args, **kwargs):
    # what Pallas raises when Mosaic cannot lower a primitive
    raise NotImplementedError(
        "Unimplemented primitive in Pallas TPU lowering for "
        "KernelType.TC: scatter.")


@pytest.mark.parametrize("engine", ["ptpe", "mapconcat_kernel"])
def test_kernel_lowering_error_propagates(monkeypatch, engine):
    """On the compiled (TPU) path a kernel that fails to lower raises out
    of the dispatcher; it must not reroute onto the XLA scan."""
    from repro.core import count_a1, count_dispatch
    from repro.kernels.tally import fallback_counts

    monkeypatch.setattr(ops, "_mode", lambda force=None: False)
    for name in ("a1_count_kernel", "a1_count_state_kernel",
                 "a1_mapconcat_kernel"):
        monkeypatch.setattr(ops, name, _lowering_refusal)
    rng = np.random.default_rng(11)
    st = random_stream(4, 300, 600, seed=11)
    eps = _batch(rng, 8, 3, 4)
    before = fallback_counts()
    with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
        count_dispatch(st, eps, engine=engine)
    with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
        count_a1(st, eps, use_kernel=True)
    assert fallback_counts() == before


def test_no_tpu_decline_still_reroutes_and_tallies(monkeypatch):
    from repro.core import count_a1
    from repro.kernels.tally import fallback_counts

    monkeypatch.delenv("REPRO_INTERPRET_KERNELS", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    rng = np.random.default_rng(12)
    st = random_stream(4, 300, 600, seed=12)
    eps = _batch(rng, 8, 3, 4)
    before = fallback_counts().get("a1_count", 0)
    got = count_a1(st, eps, use_kernel=True)
    np.testing.assert_array_equal(got, count_a1_sequential(st, eps))
    assert fallback_counts().get("a1_count", 0) == before + 1
