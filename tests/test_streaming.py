"""Streaming-equivalence suite: partitioned counting must be bit-identical
to one-shot counting on the concatenated stream — for every engine, with
two-pass on and off, under splits that land mid-occurrence and on duplicate
timestamps (the tie-holdback and zone-inclusive boundary cases)."""

import numpy as np
import pytest

from repro.core import (EpisodeBatch, EventStream, StreamingA2Counter,
                        StreamingCounter, StreamingMiner, bucket_size,
                        count_a1, count_a1_sequential, count_a2,
                        count_a2_sequential, count_dispatch, count_level1,
                        count_two_pass, mine, mine_partitions,
                        type_histogram)
from repro.telemetry import ThroughputMeter

NUM_TYPES = 5


def tie_heavy_stream(seed, n=160):
    """Gaps drawn from {0, 0, 1, 2}: long runs of equal timestamps, so
    index-based splits routinely land inside a tie group."""
    rng = np.random.default_rng(seed)
    gaps = rng.choice([0, 0, 1, 2], size=n)
    times = (np.cumsum(gaps) + 1).astype(np.int32)
    types = rng.integers(0, NUM_TYPES, size=n).astype(np.int32)
    return EventStream(types, times, NUM_TYPES)


def batch():
    """Episodes with repeated types, zero lower bounds (tie-sensitive), and
    heterogeneous spans (exercises the inclusive τ+W stitch zone)."""
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


@pytest.mark.parametrize("engine", ["ptpe", "mapconcatenate", "hybrid"])
@pytest.mark.parametrize("k", [1, 2, 3, 8])
def test_streaming_counter_equals_one_shot(engine, k):
    for seed in (0, 3):
        stream = tie_heavy_stream(seed)
        eps = batch()
        oracle = count_a1_sequential(stream, eps)
        ctr = StreamingCounter(eps, engine=engine)
        outs = list(ctr.run(split_by_index(stream, k)))
        np.testing.assert_array_equal(outs[-1], oracle)
        # and through update()/finalize()
        ctr2 = StreamingCounter(eps, engine=engine)
        for w in split_by_index(stream, k):
            ctr2.update(w)
        np.testing.assert_array_equal(ctr2.finalize(), oracle)


@pytest.mark.parametrize("lcap", [1, 2])
def test_run_snapshots_match_update_snapshots(lcap):
    """run()'s prefetch stages window p+1 (and records its history) before
    window p's counts are read; flagged-episode recounts must still cover
    exactly the consumed prefix, i.e. every intermediate snapshot equals the
    unpipelined update() path."""
    stream = tie_heavy_stream(2, n=200)
    eps = batch()
    wins = split_by_index(stream, 4)
    a = StreamingCounter(eps, engine="ptpe", lcap=lcap)
    piped = list(a.run(wins))
    b = StreamingCounter(eps, engine="ptpe", lcap=lcap)
    for i, w in enumerate(wins):
        np.testing.assert_array_equal(piped[i],
                                      b.update(w, final=i == len(wins) - 1))


@pytest.mark.parametrize("lcap", [1, 2])
def test_streaming_flagged_episodes_restored(lcap):
    """Tiny list capacities force live-eviction flags; streaming counts must
    still be exact via the history recount."""
    stream = tie_heavy_stream(1, n=200)
    eps = batch()
    oracle = count_a1_sequential(stream, eps)
    for engine in ("ptpe", "mapconcatenate"):
        ctr = StreamingCounter(eps, engine=engine, lcap=lcap)
        for w in split_by_index(stream, 3):
            ctr.update(w)
        np.testing.assert_array_equal(ctr.finalize(), oracle)


def test_streaming_a2_counter_equals_one_shot():
    for seed in (0, 5):
        stream = tie_heavy_stream(seed)
        eps = batch()
        want = count_a2_sequential(stream, eps.relaxed())
        for k in (1, 2, 3, 8):
            ctr = StreamingA2Counter(eps)
            for w in split_by_index(stream, k):
                out = ctr.update(w)
            np.testing.assert_array_equal(out, want)


def test_stateful_count_apis_chunked_equal_one_shot():
    stream = tie_heavy_stream(2)
    eps = batch()
    a1_one = count_a1(stream, eps, use_kernel=False)
    a2_one = count_a2(stream, eps, use_kernel=False)
    tp_one = count_two_pass(stream, eps, theta=2, use_kernel=False)
    # split at a strict time increase so per-chunk dup flags stay exact
    ok = np.nonzero(np.diff(stream.times) > 0)[0] + 1
    cut = int(ok[len(ok) // 2])
    chunks = [EventStream(stream.types[:cut], stream.times[:cut], NUM_TYPES),
              EventStream(stream.types[cut:], stream.times[cut:], NUM_TYPES)]
    st_a1 = st_a2 = st_tp = st_disp = None
    for ch in chunks:
        c_a1, st_a1 = count_a1(ch, eps, use_kernel=False, state=st_a1,
                               return_state=True)
        c_a2, st_a2 = count_a2(ch, eps, use_kernel=False, state=st_a2,
                               return_state=True)
        tp, st_tp = count_two_pass(ch, eps, theta=2, use_kernel=False,
                                   state=st_tp, return_state=True)
        c_d, st_disp = count_dispatch(ch, eps, engine="ptpe",
                                      use_kernel=False, state=st_disp,
                                      return_state=True)
    np.testing.assert_array_equal(c_a1, a1_one)
    np.testing.assert_array_equal(c_a2, a2_one)
    np.testing.assert_array_equal(c_d, a1_one)
    np.testing.assert_array_equal(tp.a2_counts, tp_one.a2_counts)
    np.testing.assert_array_equal(tp.survived, tp_one.survived)
    np.testing.assert_array_equal(tp.counts, tp_one.counts)


@pytest.mark.parametrize("two_pass", [True, False])
def test_streaming_miner_cumulative_equals_one_shot_mine(two_pass):
    from repro.data import embedded_chain_stream
    st = embedded_chain_stream(NUM_TYPES, [1, 2, 3], (2, 6),
                               num_occurrences=40, noise_events=400,
                               t_max=30_000, seed=11)
    for engine in ("hybrid", "mapconcatenate"):
        one = mine(st, intervals=[(2, 6)], theta=15, max_level=3,
                   engine=engine, two_pass=two_pass)
        miner = StreamingMiner([(2, 6)], 15, max_level=3, mode="cumulative",
                               engine=engine, two_pass=two_pass)
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


def test_mine_partitions_cumulative_final_window():
    """mine_partitions in cumulative mode over an exact partition (dedup
    off: the split may legally land on a timestamp tie) ends bit-identical
    to one-shot mine on the concatenation."""
    stream = tie_heavy_stream(4, n=300)
    one = mine(stream, intervals=[(0, 4)], theta=8, max_level=3)
    wins = split_by_index(stream, 4)
    results = list(mine_partitions(wins, [(0, 4)], 8, max_level=3,
                                   mode="cumulative", overlap_dedup=False))
    assert [i for i, _ in results] == list(range(4))
    res = results[-1][1]
    for fa, fb, ca, cb in zip(res.frequent, one.frequent,
                              res.counts, one.counts):
        np.testing.assert_array_equal(fa.etypes, fb.etypes)
        np.testing.assert_array_equal(ca, cb)


def test_mine_partitions_per_window_counts_boundary_spanners():
    """A single planted occurrence straddling the partition cut must be
    counted by the carried miner (in the window where it completes) and is
    invisible to the restart baseline."""
    # A@10 B@13 | C@16 with the cut between 13 and 16
    types = np.int32([0, 1, 2])
    times = np.int32([10, 13, 16])
    w1 = EventStream(types[:2], times[:2], 3)
    w2 = EventStream(types[2:], times[2:], 3)
    eps_counts = []
    for carry in (True, False):
        total = 0
        for _, res in mine_partitions([w1, w2], [(1, 5)], 1, max_level=3,
                                      carry=carry, two_pass=False):
            if len(res.frequent) >= 3 and res.frequent[2].M:
                hits = [tuple(e) for e in res.frequent[2].etypes.tolist()]
                if (0, 1, 2) in hits:
                    total += int(res.counts[2][hits.index((0, 1, 2))])
        eps_counts.append(total)
    assert eps_counts == [1, 0]  # carry sees the straddler, restart cannot


def test_count_level1_helper_matches_naive():
    stream = tie_heavy_stream(6)
    padded = stream.padded_to(256)
    hist = type_histogram(padded)
    naive = np.array([(stream.types == e).sum() for e in range(NUM_TYPES)],
                     np.int64)
    np.testing.assert_array_equal(hist, naive)
    ets = np.int32([3, 0, 0, 4])
    np.testing.assert_array_equal(count_level1(padded, ets), naive[ets])


def test_bucket_size_powers_of_two():
    assert bucket_size(0) == 128
    assert bucket_size(128) == 128
    assert bucket_size(129) == 256
    assert bucket_size(1000, minimum=32) == 1024


def test_streaming_counter_rejects_out_of_order_windows():
    eps = batch()
    ctr = StreamingCounter(eps, engine="ptpe")
    ctr.update(EventStream(np.int32([0, 1]), np.int32([5, 9]), NUM_TYPES))
    with pytest.raises(ValueError, match="partition"):
        ctr.update(EventStream(np.int32([2]), np.int32([3]), NUM_TYPES))


def test_throughput_meter_summary():
    m = ThroughputMeter()
    for n in (100, 200):
        m.start()
        m.stop(n)
    s = m.summary()
    assert s["windows"] == 2 and s["events"] == 300
    assert s["events_per_sec"] > 0 and s["steady_events_per_sec"] > 0


def test_miner_counters_share_one_attribution_frontier(monkeypatch):
    """An episode's per-window deltas come from a different candidate
    batch's counter each window; they telescope to the exact total only if
    every counter of the miner attributes at the same frontier. Where the
    segmented kernel is available the per-batch hybrid choice would put
    small batches on MapConcatenate (W ticks behind), so the miner resolves
    "hybrid" to PTPE for all of its counters: every reported delta is the
    A1 count at PTPE's frontier (the window's last timestamp, held back)
    minus the previous window's, and the deltas sum to the oracle."""
    from repro.core import hybrid
    from repro.core.streaming import StreamingMiner
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    monkeypatch.setattr(hybrid, "_PROBE_CACHE", True)
    rng = np.random.default_rng(11)
    n, k = 900, 8
    times = (np.cumsum(rng.choice([0, 1, 1, 2, 3], size=n)) + 1).astype(
        np.int32)
    types = rng.integers(0, k, size=n).astype(np.int32)
    stream = EventStream(types, times, k)
    cuts = np.searchsorted(times, np.arange(1, 6) * (times[-1] // 6))
    windows = [EventStream(types[a:b], times[a:b], k) for a, b in
               zip(np.r_[0, cuts], np.r_[cuts, n])]
    miner = StreamingMiner([(0, 6)], 4, max_level=3)
    ops.reset_kernel_calls()
    reported = []
    for j, w in enumerate(windows):
        res = miner.update(w, final=j == len(windows) - 1)
        for batch_, cnt in zip(res.frequent[1:], res.counts[1:]):
            reported += [(j, tuple(et), int(c))
                         for et, c in zip(batch_.etypes, cnt)]
    assert ops.KERNEL_CALLS["a1_state"] > 0
    assert not ops.KERNEL_CALLS["a1_mapc"]
    eps_all = sorted({et for _, et, _ in reported})
    by_n = {}
    for et in eps_all:
        by_n.setdefault(len(et), []).append(et)
    cum = {}
    for n_, ets in by_n.items():
        eps = EpisodeBatch(np.int32(ets), np.zeros((len(ets), n_ - 1),
                                                   np.int32),
                           np.full((len(ets), n_ - 1), 6, np.int32))
        for j, w in enumerate(windows):
            end = (n if j == len(windows) - 1
                   else int(np.searchsorted(times, w.times[-1])))
            c = count_a1_sequential(
                EventStream(types[:end], times[:end], k), eps)
            cum.update({(j, et): int(x) for et, x in zip(ets, c)})
    assert len(reported) > 50
    for j, et, c in reported:
        assert c == cum[(j, et)] - cum.get((j - 1, et), 0), (j, et)
    every = {}
    for j, et, c in reported:
        every.setdefault(et, []).append(c)
    whole = [et for et, cs in every.items() if len(cs) == len(windows)]
    assert whole
    for et in whole:
        assert sum(every[et]) == cum[(len(windows) - 1, et)]
