"""Seeded spike trains for the benchmark's arrays.

The generator follows the paper's synthetic model (arXiv:0905.2200, §6.1.1,
the Sym26 data set): every channel fires as a homogeneous Poisson process,
and causal chains are planted on top, each occurrence a run of the chain's
channels with every delay uniform in (lo, hi] milliseconds. Times are
integer milliseconds, the program's tick.

A configuration file (``bench/configs/<name>.json``) names the channel
count, the background rate, the chains and their rates, the interval, and
the ``recording_seed`` of its recordings: array a of every run is the same
recording, drawn from ``array_rng(recording_seed, a)``. A run's own seed
relabels the recording's channels (``recording``): every seed mines the
same amount of work in another order, so runs with different seeds
measure alike, and the same seed gives the same arrays on every machine.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Recording:
    """One array's spike train, sorted by time (stable within a tick)."""

    types: np.ndarray  # int32[n]
    times: np.ndarray  # int32[n], non-decreasing milliseconds
    num_types: int


def array_rng(seed: int, index: int) -> np.random.Generator:
    """Generator of array ``index`` in a run seeded ``seed``. Any whole
    number is a valid seed, negative or past 64 bits included."""
    return np.random.default_rng([seed % (1 << 64), index])


def spike_train(cfg: dict, seconds: float, rng: np.random.Generator) -> Recording:
    """``seconds`` of one array under configuration ``cfg``."""
    n_types = int(cfg["num_types"])
    t_max = int(seconds * 1000)
    rate = float(cfg["rate_hz"])
    lo, hi = (int(x) for x in cfg["interval_ms"])
    # background: exponential gaps, drawn in bulk with headroom, cut at t_max
    n_draw = int(rate * seconds + 8 * np.sqrt(rate * seconds) + 16)
    gaps = rng.exponential(1000.0 / rate, size=(n_types, n_draw))
    t_bg = np.cumsum(gaps, axis=1)
    keep = t_bg < t_max
    types = [np.broadcast_to(np.arange(n_types)[:, None], t_bg.shape)[keep]]
    times = [t_bg[keep].astype(np.int64)]
    for chain in cfg["chains"]:
        nodes = np.asarray(chain["types"], np.int64)
        n_occ = int(round(float(chain["rate_hz"]) * seconds))
        span = (len(nodes) - 1) * hi + 1
        anchors = np.sort(rng.integers(1, t_max - span, size=n_occ))
        delays = rng.integers(lo + 1, hi + 1, size=(n_occ, len(nodes) - 1))
        t_occ = anchors[:, None] + np.concatenate(
            [np.zeros((n_occ, 1), np.int64), np.cumsum(delays, axis=1)], axis=1)
        types.append(np.broadcast_to(nodes, t_occ.shape).reshape(-1))
        times.append(t_occ.reshape(-1))
    types = np.concatenate(types)
    times = np.concatenate(times)
    order = np.argsort(times, kind="stable")
    return Recording(types[order].astype(np.int32), times[order].astype(np.int32),
                     n_types)


def recording(cfg: dict, seconds: float, seed: int, index: int) -> Recording:
    """Array ``index`` of a run seeded ``seed``: the configuration's fixed
    recording with its channels permuted by the seed."""
    rec = spike_train(cfg, seconds, array_rng(int(cfg["recording_seed"]), index))
    perm = array_rng(seed, index).permutation(rec.num_types).astype(np.int32)
    return Recording(perm[rec.types], rec.times, rec.num_types)


def window_bounds(rec: Recording, window_ms: int) -> np.ndarray:
    """Event index bounds of consecutive ``window_ms`` windows starting at
    tick 0: window j holds events ``bounds[j]:bounds[j + 1]``."""
    n_win = int(rec.times[-1]) // window_ms + 1
    edges = np.arange(n_win + 1, dtype=np.int64) * window_ms
    return np.searchsorted(rec.times, edges, side="left")
