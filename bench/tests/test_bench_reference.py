"""The plain reference: its two counters agree with each other and with a
brute-force non-overlapped count, on uniform streams and on bursting ones,
and the comparison flags every kind of wrong delta."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import reference  # noqa: E402
import streams  # noqa: E402

SYM = {"num_types": 6, "rate_hz": 40.0, "interval_ms": [5, 10],
       "chains": [{"types": [0, 1, 2], "rate_hz": 10.0}]}
BURSTS = json.loads((Path(__file__).parent / "data" / "burst_culture.json").read_text())


def _random_stream(seed, n=400, types=4, t_max=600):
    rng = np.random.default_rng(seed)
    times = np.sort(rng.integers(0, t_max, n)).astype(np.int32)  # many ties
    return rng.integers(0, types, n).astype(np.int32), times


def _burst_stream(name, types, seconds=15.0):
    """A culture's recording (the burst fixture) on ``types`` channels,
    every one recruited by every burst: channels fire together in one tick
    and each fires more than 4 times within 12 ms, so an A1 list holds more
    than 4 partial occurrences."""
    cfg = {**BURSTS, "num_types": types,
           "bursts": {**BURSTS["bursts"], "recruited": 1.0}}
    k = int(name.removeprefix("bursts-"))
    rec = streams.spike_train(cfg, seconds, streams.array_rng(k, types))
    t = rec.times.astype(np.int64)
    assert np.count_nonzero(np.diff(t) == 0) > len(t) // 10  # cross-channel ties
    assert max(np.count_nonzero(t[rec.types == c][4:] - t[rec.types == c][:-4] <= 12)
               for c in range(types)) > 0
    return rec.types, rec.times


def _stream(name, n, types, t_max):
    """Seeds (ints) name uniform random streams, "bursts-<k>" a culture."""
    if isinstance(name, str):
        return _burst_stream(name, types)
    return _random_stream(name, n, types, t_max)


def _frontiers(n, marks, base):
    """``marks`` set for a stream of ``base`` events, scaled to ``n``."""
    return np.array([0, *(m * n // base for m in marks), n])


def _greedy(types, times, et, lo, hi):
    """Earliest-completion greedy count of non-overlapped occurrences with
    every edge in (lo, hi] (exhaustive search; tiny inputs only)."""
    ev = list(zip(types.tolist(), times.tolist()))
    n, start, count = len(et), 0, 0
    while True:
        best = None

        def dfs(level, prev_t, j0):
            nonlocal best
            for j in range(j0, len(ev)):
                e, t = ev[j]
                if best is not None and j >= best:
                    return
                if e != et[level]:
                    continue
                if level and not (lo < t - prev_t <= hi):
                    if t - prev_t > hi:
                        return
                    continue
                if level == n - 1:
                    best = j
                    return
                dfs(level + 1, t, j + 1)

        dfs(0, 0, start)
        if best is None:
            return count
        count += 1
        start = best + 1


@pytest.mark.parametrize("seed", [*range(6), "bursts-0", "bursts-1", "bursts-2"])
def test_pair_counts_equal_algorithm_1(seed):
    types, times = _stream(seed, 400, 4, 600)
    frontiers = _frontiers(len(types), [50, 133, 290], 400)
    pairs = reference.pair_counts(types, times, 4, 1, 12, frontiers)
    for a in range(4):
        for b in range(4):
            want = reference.a1_counts(types, times, (a, b), [1], [12], frontiers)
            assert np.array_equal(pairs[:, a, b], want), (a, b)


@pytest.mark.parametrize("seed", [*range(6), "bursts-0", "bursts-1", "bursts-2"])
def test_chain_counts_equal_algorithm_1(seed):
    types, times = _stream(seed if isinstance(seed, str) else 200 + seed, 500, 3, 900)
    frontiers = _frontiers(len(types), [7, 100, 250, 499], 500)
    episodes = [(0, 1), (1, 1), (0, 1, 2), (0, 0, 1), (2, 1, 2), (1, 1, 1),
                (0, 1, 0), (0, 1, 1), (2, 0, 1, 2), (0, 1, 0, 1), (0, 1, 0, 2)]
    for lo, hi in [(1, 12), (0, 5), (5, 10)]:
        for n in (2, 3, 4):
            # one batch per length, where episodes share prefixes
            eps = [et for et in episodes if len(et) == n]
            k = n - 1
            batch = reference.chain_counts(types, times, eps, [lo] * k, [hi] * k,
                                           frontiers)
            for et, in_batch in zip(eps, batch):
                want = reference.a1_counts(types, times, et, [lo] * k, [hi] * k,
                                           frontiers)
                alone = reference.chain_counts(types, times, [et], [lo] * k,
                                               [hi] * k, frontiers)[0]
                assert np.array_equal(alone, want), (et, lo, hi)
                assert np.array_equal(in_batch, want), (et, lo, hi)


@pytest.mark.parametrize("seed", range(4))
def test_algorithm_1_equals_greedy_on_chains(seed):
    types, times = _random_stream(100 + seed, n=120, types=3, t_max=200)
    for et in [(0, 1), (1, 1), (0, 1, 2), (2, 0, 1, 2)]:
        got = reference.a1_counts(types, times, et, [1] * (len(et) - 1),
                                  [9] * (len(et) - 1), np.array([len(types)]))
        assert got[0] == _greedy(types, times, et, 1, 9), et


def _served(ref):
    """Deltas exactly as the reference expects them."""
    return {p: {"n_events": int(ref.bounds[p + 1] - ref.bounds[p]),
                "episodes": [[list(ep), c] for ep, c in ref.due(p).items()]}
            for p in range(ref.n_windows)}


@pytest.fixture
def ref():
    rec = streams.spike_train(SYM, 12.0, streams.array_rng(2**40 + 7, 0))
    bounds = streams.window_bounds(rec, 1000)
    return reference.ArrayReference(rec.types, rec.times, rec.num_types,
                                    bounds[:11], 10, 6, 4, (5, 10))


def test_compare_accepts_the_reference_and_flags_each_fault(ref):
    served = _served(ref)
    assert any(len(ep) == 3 for d in served.values() for ep, _ in d["episodes"])
    faults, bad = reference.compare(ref, served)
    assert not any(faults.values()) and not bad

    altered = {p: dict(d) for p, d in served.items()}
    ep, c = altered[4]["episodes"][-1]
    altered[4]["episodes"] = altered[4]["episodes"][:-1] + [[ep, c + 1]]
    faults, bad = reference.compare(ref, altered)
    assert faults["count_mismatches"] == 1 and bad == {4}

    dropped = {p: dict(d) for p, d in served.items()}
    dropped[6]["episodes"] = [e for e in dropped[6]["episodes"] if len(e[0]) != 2]
    faults, bad = reference.compare(ref, dropped)
    assert faults["missed_episodes"] > 0 and bad == {6}

    missing = {p: d for p, d in served.items() if p != 3}
    missing[12] = served[3]
    faults, bad = reference.compare(ref, missing)
    assert faults["missing_windows"] == 1 and faults["extra_windows"] == 1


def test_streams_are_fixed_by_the_seed():
    a = streams.spike_train(SYM, 5.0, streams.array_rng(-3, 1))
    b = streams.spike_train(SYM, 5.0, streams.array_rng(-3, 1))
    c = streams.spike_train(SYM, 5.0, streams.array_rng(-3, 2))
    assert np.array_equal(a.times, b.times) and np.array_equal(a.types, b.types)
    assert not np.array_equal(a.types[:50], c.types[:50])
    assert (np.diff(a.times) >= 0).all() and a.times[-1] < 5000
