"""Kernel-carried streaming equivalence (interpret mode).

The bug this suite pins down: stateful calls used to bypass the Pallas
kernels silently, so the streaming/service hot path never executed a
kernel line no matter what ``use_kernel`` said — and CPU CI could not see
it. Every test here (a) forces the interpret-mode dispatch policy, (b)
asserts via ``kernels.ops.KERNEL_CALLS`` that the kernel path actually
ran, and (c) asserts chunked stateful-kernel counts are bit-identical to
one-shot counting on the concatenated stream.
"""

import numpy as np
import pytest

from repro.core import (EpisodeBatch, EventStream, StreamingA2Counter,
                        StreamingCounter, StreamingMiner, count_a1,
                        count_a1_sequential, count_a2, count_a2_sequential,
                        count_dispatch, count_two_pass, mine)
from repro.core.count_a1 import count_a1_vectorized
from repro.core.count_a2 import count_single_slot
from repro.kernels import ops

NUM_TYPES = 5


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    """Force the kernel dispatch policy on (interpret mode) and zero the
    dispatch tally, so each test can assert the Pallas path executed.
    The hybrid's availability probe is cached per process, so flipping
    the environment must also drop the cache — both ways, or a suite
    running earlier (or later) in the same process sees a stale answer."""
    from repro.core import hybrid
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    hybrid._reset_probe_cache()
    ops.reset_kernel_calls()
    yield
    hybrid._reset_probe_cache()


def tie_heavy_stream(seed, n=160):
    rng = np.random.default_rng(seed)
    gaps = rng.choice([0, 0, 1, 2], size=n)
    times = (np.cumsum(gaps) + 1).astype(np.int32)
    types = rng.integers(0, NUM_TYPES, size=n).astype(np.int32)
    return EventStream(types, times, NUM_TYPES)


def batch():
    return EpisodeBatch(
        np.int32([[0, 1, 2], [1, 2, 3], [2, 2, 0], [4, 0, 1]]),
        np.int32([[1, 0], [0, 2], [0, 0], [0, 0]]),
        np.int32([[5, 6], [4, 7], [3, 3], [6, 2]]))


def split_by_index(stream, k):
    n = stream.types.shape[0]
    cuts = [0] + [n * j // k for j in range(1, k)] + [n]
    return [EventStream(stream.types[a:b], stream.times[a:b],
                        stream.num_types)
            for a, b in zip(cuts[:-1], cuts[1:])]


# ------------------------------------------------------ layout round-trip


def test_a1_state_layout_round_trip():
    """Host [M, N, L] layout → kernel brick → host is the identity, for a
    state mid-stream (populated lists, advanced pointers, sticky flags)."""
    stream = tie_heavy_stream(7)
    eps = batch()
    _, _, st = count_a1_vectorized(stream, eps, lcap=2, return_state=True)
    back = ops.a1_state_unpack(*ops.a1_state_layout(st), eps.M, eps.N)
    np.testing.assert_array_equal(np.asarray(back.s), np.asarray(st.s))
    np.testing.assert_array_equal(np.asarray(back.ptr), np.asarray(st.ptr))
    np.testing.assert_array_equal(np.asarray(back.count),
                                  np.asarray(st.count))
    np.testing.assert_array_equal(np.asarray(back.ovf), np.asarray(st.ovf))


def test_a2_state_layout_round_trip():
    stream = tie_heavy_stream(8)
    eps = batch().relaxed()
    _, st = count_single_slot(stream, eps, inclusive_lower=True,
                              return_state=True)
    back = ops.a2_state_unpack(*ops.a2_state_layout(st), eps.M, eps.N)
    np.testing.assert_array_equal(np.asarray(back.s), np.asarray(st.s))
    np.testing.assert_array_equal(np.asarray(back.count),
                                  np.asarray(st.count))


# ------------------------------------------- stateful one-shot-chunk APIs


def test_stateful_apis_run_kernel_and_match_scan():
    """count_a1/count_a2/count_dispatch/count_two_pass stateful modes with
    ``use_kernel=True`` must execute the Pallas kernels (instrumented) and
    equal both the scan-stateful and the one-shot results."""
    stream = tie_heavy_stream(2)
    eps = batch()
    ok = np.nonzero(np.diff(stream.times) > 0)[0] + 1
    cut = int(ok[len(ok) // 2])
    chunks = [EventStream(stream.types[:cut], stream.times[:cut], NUM_TYPES),
              EventStream(stream.types[cut:], stream.times[cut:], NUM_TYPES)]
    st_a1 = st_a2 = st_tp = st_d = None
    for ch in chunks:
        c_a1, st_a1 = count_a1(ch, eps, state=st_a1, return_state=True)
        c_a2, st_a2 = count_a2(ch, eps, state=st_a2, return_state=True)
        tp, st_tp = count_two_pass(ch, eps, theta=2, state=st_tp,
                                   return_state=True)
        c_d, st_d = count_dispatch(ch, eps, engine="hybrid", state=st_d,
                                   return_state=True)
    assert ops.KERNEL_CALLS["a1_state"] >= 4  # a1 + two_pass + dispatch × 2
    assert ops.KERNEL_CALLS["a2_state"] >= 4  # a2 + two_pass pass-1 × 2
    np.testing.assert_array_equal(c_a1, count_a1(stream, eps,
                                                 use_kernel=False))
    np.testing.assert_array_equal(c_d, c_a1)
    np.testing.assert_array_equal(c_a2, count_a2(stream, eps,
                                                 use_kernel=False))
    one = count_two_pass(stream, eps, theta=2, use_kernel=False)
    np.testing.assert_array_equal(tp.counts, one.counts)
    np.testing.assert_array_equal(tp.survived, one.survived)
    # the carried state itself is bit-identical to the scan engine's
    _, _, want = count_a1_vectorized(stream, eps, return_state=True)
    np.testing.assert_array_equal(np.asarray(st_a1.s), np.asarray(want.s))
    np.testing.assert_array_equal(np.asarray(st_a1.ptr),
                                  np.asarray(want.ptr))


@pytest.mark.parametrize("lcap", [1, 2, 4])
def test_stateful_kernel_lcap_sweep_ovf_parity(lcap):
    """Eviction-flag (ovf) parity under chunking: the kernel-carried flags
    match the scan-carried flags at every capacity, and flagged episodes
    restore to the oracle through the usual recount."""
    stream = tie_heavy_stream(1, n=200)
    eps = batch()
    ok = np.nonzero(np.diff(stream.times) > 0)[0] + 1
    cuts = [0, int(ok[len(ok) // 3]), int(ok[2 * len(ok) // 3]),
            stream.types.shape[0]]
    k_state = s_state = None
    for a, b in zip(cuts[:-1], cuts[1:]):
        ch = EventStream(stream.types[a:b], stream.times[a:b], NUM_TYPES)
        kc, kovf, k_state = ops.a1_count_stateful(ch, eps, state=k_state,
                                                  lcap=lcap)
        sc, sovf, s_state = count_a1_vectorized(ch, eps, lcap=lcap,
                                                state=s_state,
                                                return_state=True)
    np.testing.assert_array_equal(kc, sc)
    np.testing.assert_array_equal(kovf, sovf)
    np.testing.assert_array_equal(np.asarray(k_state.ovf),
                                  np.asarray(s_state.ovf))
    oracle = count_a1_sequential(stream, eps)
    exact = ~kovf
    np.testing.assert_array_equal(kc[exact], oracle[exact])


# -------------------------------------------------- streaming counters


@pytest.mark.parametrize("k", [1, 2, 3, 8])
def test_streaming_counter_kernel_carried_equals_one_shot(k):
    """Window-by-window kernel-carried counts == one-shot on the
    concatenation, including mid-tie-group splits (the index splits land
    inside tie groups of the tie-heavy stream)."""
    for seed in (0, 3):
        stream = tie_heavy_stream(seed)
        eps = batch()
        oracle = count_a1_sequential(stream, eps)
        ops.reset_kernel_calls()
        ctr = StreamingCounter(eps, engine="ptpe", use_kernel=True)
        assert ctr._kernel, "kernel residency must engage under interpret"
        for w in split_by_index(stream, k):
            ctr.update(w)
        np.testing.assert_array_equal(ctr.finalize(), oracle)
        assert ops.KERNEL_CALLS["a1_state"] >= 1


@pytest.mark.parametrize("lcap", [1, 2])
def test_streaming_counter_kernel_flagged_restored(lcap):
    """Tiny capacities force live-eviction flags through the kernel path;
    counts() must still restore exactness via the history recount."""
    stream = tie_heavy_stream(1, n=200)
    eps = batch()
    oracle = count_a1_sequential(stream, eps)
    ctr = StreamingCounter(eps, engine="ptpe", lcap=lcap, use_kernel=True)
    assert ctr._kernel
    for w in split_by_index(stream, 3):
        ctr.update(w)
    np.testing.assert_array_equal(ctr.finalize(), oracle)
    assert ops.KERNEL_CALLS["a1_state"] >= 1


def test_streaming_counter_kernel_bounded_checkpointing():
    """Bounded mode (checkpoint_interval) unpacks the kernel brick at each
    base advance and repacks the resolved state — still exact."""
    stream = tie_heavy_stream(4, n=240)
    eps = batch()
    oracle = count_a1_sequential(stream, eps)
    for lcap in (1, 2):
        ctr = StreamingCounter(eps, engine="ptpe", lcap=lcap,
                               checkpoint_interval=2, use_kernel=True)
        assert ctr._kernel
        for w in split_by_index(stream, 5):
            ctr.update(w)
        np.testing.assert_array_equal(ctr.finalize(), oracle)


def test_streaming_a2_counter_kernel_carried():
    stream = tie_heavy_stream(5)
    eps = batch()
    want = count_a2_sequential(stream, eps.relaxed())
    ctr = StreamingA2Counter(eps, use_kernel=True)
    assert ctr._kernel
    for w in split_by_index(stream, 4):
        out = ctr.update(w)
    np.testing.assert_array_equal(out, want)
    assert ops.KERNEL_CALLS["a2_state"] >= 1


def test_streaming_counters_launch_fixed_blocks(monkeypatch):
    """Batches wider than one lane block and windows longer than one
    event chunk run as several launches of ONE shape (ops.stream_block),
    carrying each block's state through the chunks — counts equal the
    sequential oracles window by window."""
    rng = np.random.default_rng(7)
    m = 300  # three 128-lane blocks in interpret mode
    et = rng.integers(0, NUM_TYPES, size=(m, 3)).astype(np.int32)
    lo = rng.integers(0, 2, size=(m, 2)).astype(np.int32)
    eps = EpisodeBatch(et, lo, lo + rng.integers(1, 6, size=(m, 2))
                       .astype(np.int32))
    stream = tie_heavy_stream(6, n=700)
    windows = split_by_index(stream, 2)  # ~350 events: three chunks each
    shapes = set()
    a1_call, a2_call = ops.a1_state_call, ops.a2_state_call

    def spy1(*args, **kw):
        shapes.add(("a1", args[0].shape, args[3].shape))
        return a1_call(*args, **kw)

    def spy2(*args, **kw):
        shapes.add(("a2", args[0].shape, args[3].shape))
        return a2_call(*args, **kw)

    monkeypatch.setattr(ops, "a1_state_call", spy1)
    monkeypatch.setattr(ops, "a2_state_call", spy2)
    ctr = StreamingCounter(eps, engine="ptpe", use_kernel=True)
    a2c = StreamingA2Counter(eps, use_kernel=True)
    assert len(ctr._keps) == len(a2c._keps) == 3
    seen = EventStream(stream.types[:0], stream.times[:0], NUM_TYPES)
    for j, w in enumerate(windows):
        final = j == len(windows) - 1
        got1, got2 = ctr.update(w, final=final), a2c.update(w, final=final)
        seen = EventStream(np.concatenate([seen.types, w.types]),
                           np.concatenate([seen.times, w.times]), NUM_TYPES)
        np.testing.assert_array_equal(got2, count_a2_sequential(
            seen, eps.relaxed()))
    np.testing.assert_array_equal(got1, count_a1_sequential(stream, eps))
    bm, be = ops.stream_block(True)
    assert shapes == {("a1", (8, bm), (3, be)), ("a2", (8, bm), (2, be))}
    assert ops.KERNEL_CALLS["a1_state"] >= 3 * 2 * 2


def test_streaming_state_dict_round_trip_through_kernel_layout():
    """state_dict → load_state_dict → resume: the carried kernel-layout
    state round-trips through the canonical checkpoint form, and the
    resumed counter (still on the kernel path) finishes bit-identically.
    A scan-engine counter must also restore the same checkpoint (layout
    portability across dispatch modes)."""
    stream = tie_heavy_stream(6, n=200)
    eps = batch()
    oracle = count_a1_sequential(stream, eps)
    wins = split_by_index(stream, 4)
    src = StreamingCounter(eps, engine="ptpe", use_kernel=True)
    assert src._kernel
    for w in wins[:2]:
        src.update(w)
    sd = src.state_dict()
    resumed = StreamingCounter(eps, engine="ptpe", use_kernel=True)
    resumed.load_state_dict(sd)
    assert resumed._kernel
    ops.reset_kernel_calls()
    for w in wins[2:]:
        resumed.update(w)
    np.testing.assert_array_equal(resumed.finalize(), oracle)
    assert ops.KERNEL_CALLS["a1_state"] >= 1
    # same checkpoint restores onto the scan engine (and vice-versa shape)
    scan = StreamingCounter(eps, engine="ptpe", use_kernel=False)
    scan.load_state_dict(sd)
    for w in wins[2:]:
        scan.update(w)
    np.testing.assert_array_equal(scan.finalize(), oracle)


# ------------------------------------------------- miner: engine × twopass


@pytest.mark.parametrize("engine", ["ptpe", "mapconcatenate", "hybrid"])
@pytest.mark.parametrize("two_pass", [True, False])
def test_streaming_miner_kernel_equals_one_shot(engine, two_pass):
    """Cumulative kernel-carried mining ends bit-identical to one-shot
    ``mine`` on the concatenation for every engine × two-pass combination
    (the acceptance matrix). The kernel instrumentation must show the
    carried Pallas path ran whenever the ptpe machines are in play."""
    from repro.data import embedded_chain_stream
    st = embedded_chain_stream(NUM_TYPES, [1, 2, 3], (2, 6),
                               num_occurrences=25, noise_events=200,
                               t_max=15_000, seed=11)
    one = mine(st, intervals=[(2, 6)], theta=10, max_level=3,
               engine=engine, two_pass=two_pass)
    ops.reset_kernel_calls()
    miner = StreamingMiner([(2, 6)], 10, max_level=3, mode="cumulative",
                           engine=engine, two_pass=two_pass,
                           use_kernel=True)
    wins = split_by_index(st, 3)
    for i, w in enumerate(wins):
        res = miner.update(w, final=i == len(wins) - 1)
    assert len(res.frequent) == len(one.frequent)
    for fa, fb, ca, cb in zip(res.frequent, one.frequent,
                              res.counts, one.counts):
        np.testing.assert_array_equal(fa.etypes, fb.etypes)
        np.testing.assert_array_equal(fa.tlo, fb.tlo)
        np.testing.assert_array_equal(fa.thi, fb.thi)
        np.testing.assert_array_equal(ca, cb)
    if two_pass:
        assert ops.KERNEL_CALLS["a2_state"] >= 1
    if engine == "ptpe":
        assert ops.KERNEL_CALLS["a1_state"] >= 1


# ------------------------------------------------------- config plumbing


def test_use_kernel_defaults_unified():
    """The PR-3 satellite: StreamingCounter no longer defaults to False
    while everything above it defaults to True."""
    import inspect
    from repro.service import SessionConfig
    assert inspect.signature(
        StreamingCounter.__init__).parameters["use_kernel"].default is True
    assert inspect.signature(
        StreamingA2Counter.__init__).parameters["use_kernel"].default is True
    assert inspect.signature(
        StreamingMiner.__init__).parameters["use_kernel"].default is True
    assert SessionConfig().use_kernel is True


def test_count_dispatch_validates_engine_in_stateful_mode():
    """The PR-3 satellite: a bogus engine must raise even when a carried
    state short-circuits the engine dispatch."""
    stream = tie_heavy_stream(9)
    eps = batch()
    with pytest.raises(ValueError, match="bogus"):
        count_dispatch(stream, eps, engine="bogus", return_state=True)
    _, st = count_dispatch(stream, eps, engine="ptpe", return_state=True)
    with pytest.raises(ValueError, match="bogus"):
        count_dispatch(stream, eps, engine="bogus", state=st)


def test_scan_fallback_when_kernel_unavailable(monkeypatch):
    """Without a TPU or interpret mode the carried calls silently use the
    XLA scans — same bits, no kernel dispatches."""
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_INTERPRET_KERNELS", raising=False)
    stream = tie_heavy_stream(0)
    eps = batch()
    ops.reset_kernel_calls()
    ctr = StreamingCounter(eps, engine="ptpe", use_kernel=True)
    assert not ctr._kernel
    for w in split_by_index(stream, 2):
        ctr.update(w)
    np.testing.assert_array_equal(ctr.finalize(),
                                  count_a1_sequential(stream, eps))
    assert ops.KERNEL_CALLS["a1_state"] == 0
