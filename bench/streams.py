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

A dissociated culture on a multi-electrode array (Wagenaar, Pine & Potter,
BMC Neurosci. 7:11, 2006) adds optional keys, each a statistic such a
recording is described by:

* ``rate_sigma``: the channels' background rates spread log-normally,
  ``rate_hz * exp(rate_sigma * z)`` with z standard normal, drawn once per
  recording (``rate_hz`` is the median);
* ``bursts``: network bursts. Onsets come as a Poisson process at
  ``per_min`` a minute, each burst lasts a whole number of milliseconds
  uniform in ``duration_ms`` [lo, hi] and recruits ``recruited`` of the
  channels (that share of them, rounded, chosen afresh for each burst).
  Each channel has an onset lag uniform in ``lag_ms`` [lo, hi], drawn once
  per recording: a recruited channel fires at the burst's onset plus its
  lag, the leader-to-follower order a culture repeats from burst to burst,
  and then as a Poisson process at ``rate_hz`` for the burst's duration;
* ``refractory_ms``: no channel fires twice within this many milliseconds
  (at least 1, so never twice in a tick). A spike closer than that to the
  channel's previous spike is dropped, whichever process drew it.

These draw from the recording's own child generators (``culture_rngs``),
never from the background's stream: a configuration without them draws
exactly what it drew before they existed.
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


@dataclasses.dataclass(frozen=True)
class Bursts:
    """A recording's network bursts (milliseconds)."""

    lag: np.ndarray  # int64[num_types], each channel's onset lag
    onset: np.ndarray  # int64[n], ascending
    duration: np.ndarray  # int64[n]
    recruited: np.ndarray  # int64[n, k], the channels each burst recruits


def array_rng(seed: int, index: int) -> np.random.Generator:
    """Generator of array ``index`` in a run seeded ``seed``. Any whole
    number is a valid seed, negative or past 64 bits included."""
    return np.random.default_rng([seed % (1 << 64), index])


def culture_rngs(rng: np.random.Generator):
    """The generators of a recording's rate spread and of its bursts:
    children of ``rng`` that leave its own stream where it was."""
    return rng.spawn(2)


def network_bursts(cfg: dict, num_types: int, seconds: float,
                   rng: np.random.Generator) -> Bursts:
    """The bursts of ``seconds`` of one recording under ``cfg["bursts"]``,
    drawn from ``rng`` (the second of ``culture_rngs``)."""
    b = cfg["bursts"]
    t_max = int(seconds * 1000)
    lag = rng.integers(int(b["lag_ms"][0]), int(b["lag_ms"][1]) + 1, num_types)
    n = int(rng.poisson(float(b["per_min"]) * seconds / 60.0))
    onset = np.sort(rng.integers(0, t_max, n))
    duration = rng.integers(int(b["duration_ms"][0]), int(b["duration_ms"][1]) + 1, n)
    k = int(round(float(b["recruited"]) * num_types))
    recruited = np.argsort(rng.random((n, num_types)), axis=1)[:, :k]
    return Bursts(lag, onset, duration, recruited)


def _burst_spikes(cfg: dict, bursts: Bursts, rng: np.random.Generator):
    """Each recruited channel's onset spike, then Poisson spikes uniform
    over the burst's duration from that onset."""
    ch = bursts.recruited
    start = bursts.onset[:, None] + bursts.lag[ch]
    dur = np.broadcast_to(bursts.duration[:, None], ch.shape)
    n = rng.poisson(float(cfg["bursts"]["rate_hz"]) * dur / 1000.0)
    reps = n.reshape(-1)
    extra = (np.repeat(start.reshape(-1), reps)
             + rng.integers(0, np.repeat(dur.reshape(-1), reps)))
    return (np.concatenate([ch.reshape(-1), np.repeat(ch.reshape(-1), reps)]),
            np.concatenate([start.reshape(-1), extra]))


def _refractory(types: np.ndarray, times: np.ndarray, gap: int) -> np.ndarray:
    """Mask of the spikes kept when each channel, in time order, drops every
    spike less than ``gap`` ms after the last one it kept."""
    order = np.lexsort((times, types))
    keep = np.ones(len(types), bool)
    last_c, last_t = -1, 0
    for j, c, t in zip(order.tolist(), types[order].tolist(), times[order].tolist()):
        if c == last_c and t - last_t < gap:
            keep[j] = False
        else:
            last_c, last_t = c, t
    return keep


def spike_train(cfg: dict, seconds: float, rng: np.random.Generator) -> Recording:
    """``seconds`` of one array under configuration ``cfg``."""
    n_types = int(cfg["num_types"])
    t_max = int(seconds * 1000)
    lo, hi = (int(x) for x in cfg["interval_ms"])
    spread_rng, burst_rng = culture_rngs(rng)
    rate = float(cfg["rate_hz"])
    if "rate_sigma" in cfg:
        rate = rate * np.exp(float(cfg["rate_sigma"])
                             * spread_rng.standard_normal(n_types))[:, None]
    # background: exponential gaps, drawn in bulk with headroom, cut at t_max
    top = float(np.max(rate))
    n_draw = int(top * seconds + 8 * np.sqrt(top * seconds) + 16)
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
    if "bursts" in cfg:
        b_types, b_times = _burst_spikes(
            cfg, network_bursts(cfg, n_types, seconds, burst_rng), burst_rng)
        inside = b_times < t_max
        types.append(b_types[inside])
        times.append(b_times[inside])
    types = np.concatenate(types)
    times = np.concatenate(times)
    if "refractory_ms" in cfg:
        gap = int(cfg["refractory_ms"])
        if gap < 1:
            raise ValueError(f"refractory_ms must be at least 1, not {gap}")
        keep = _refractory(types, times, gap)
        types, times = types[keep], times[keep]
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
