"""The culture keys of ``bench/streams.py``: a configuration without them
draws the same recordings as before they existed, a bursting recording
matches its own parameters, and a bursting cell added as files catches a
program that drops the exact recount of overflowed bounded lists."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "burst_culture.json"
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402
import streams  # noqa: E402

SYM26 = json.loads((BENCH / "configs" / "sym26.json").read_text())
CULTURE = json.loads(FIXTURE.read_text())


def _digest(rec) -> str:
    return hashlib.sha256(rec.types.tobytes() + rec.times.tobytes()).hexdigest()


# SHA-256 of types then times of streams.recording(sym26, seconds, seed,
# array), taken before the culture keys existed: 60 s, and each sym26
# cell's own length (2 warm-up + 300 windows of 2 s + 1 s; 2 + 150)
@pytest.mark.parametrize("seconds, seed, array, digest", [
    (60.0, 3, 0, "5e147ef00e91938a86fcc7181a0f72c72ae725b98b1c70e0bbecc63ad6a63ebf"),
    (60.0, 3, 1, "a2fcdc468df86567310e33ead61865048459770052552acf290818ec71ea1bd3"),
    (60.0, 2**40 + 5, 0,
     "fdeb3739241ffc037339d04e84deb97ec42f667da36a2558256c92fb33777503"),
    (60.0, 2**40 + 5, 1,
     "b33fa8ae6449d96530b42f621160a9f723ef018b33a236c618f8bd59e2dc5668"),
    (605.0, 2**33 + 1, 0,
     "11bd1a8bdaf10fc1e493bc1816ba9326ccfe3f6b82648916af94cb3fc35c09f2"),
    (305.0, 2**33 + 1, 1,
     "9cab1cb4e1c192c1913cd95bbd2bfce30460e156aa6ecf2058063a0fe74027c3"),
])
def test_sym26_recordings_are_unchanged(seconds, seed, array, digest):
    assert _digest(streams.recording(SYM26, seconds, seed, array)) == digest


def test_bursts_draw_from_their_own_generators():
    """Bursts added to sym26 leave its background and chains as they were:
    the plain recording is the bursting one less the burst spikes."""
    culture = {**SYM26, "bursts": CULTURE["bursts"]}
    plain = streams.spike_train(SYM26, 30.0, streams.array_rng(26, 0))
    both = streams.spike_train(culture, 30.0, streams.array_rng(26, 0))
    pairs = Counter(zip(both.types.tolist(), both.times.tolist()))
    pairs.subtract(zip(plain.types.tolist(), plain.times.tolist()))
    assert min(pairs.values()) >= 0  # every plain spike is still there
    assert sum(pairs.values()) > len(plain.types) // 10


def test_a_bursting_recording_matches_its_parameters():
    """The fixture's 60 s against its own keys. It has no chains and a
    refractory period of 1 ms, so a tick holds at most one spike of a
    channel, and a Poisson process of rate r leaves a spike in a tick with
    probability 1 - exp(-r / 1000). Counts drawn from Poisson processes
    are held within 4 standard deviations of their mean."""
    cfg, n, seconds = CULTURE, CULTURE["num_types"], 60.0
    b = cfg["bursts"]
    rng = streams.array_rng(cfg["recording_seed"], 0)
    rec = streams.spike_train(cfg, seconds, rng)
    spread, burst_rng = streams.culture_rngs(streams.array_rng(cfg["recording_seed"], 0))
    bursts = streams.network_bursts(cfg, n, seconds, burst_rng)
    rate = cfg["rate_hz"] * np.exp(cfg["rate_sigma"] * spread.standard_normal(n))
    t = rec.times.astype(np.int64)

    # refractory: no channel fires twice within refractory_ms
    for c in range(n):
        assert np.diff(t[rec.types == c]).min() >= cfg["refractory_ms"]
    # ties: most burst ticks hold several channels
    assert np.count_nonzero(np.diff(t) == 0) > len(t) // 5

    # the schedule: Poisson onsets, durations and lags in range, a fixed
    # number of distinct channels recruited by each burst
    mean = b["per_min"] * seconds / 60
    assert abs(len(bursts.onset) - mean) <= 4 * np.sqrt(mean)
    assert (np.diff(bursts.onset) >= 0).all()
    lo, hi = b["duration_ms"]
    assert ((lo <= bursts.duration) & (bursts.duration <= hi)).all()
    assert ((b["lag_ms"][0] <= bursts.lag) & (bursts.lag <= b["lag_ms"][1])).all()
    k = round(b["recruited"] * n)
    assert bursts.recruited.shape == (len(bursts.onset), k)
    assert all(len(set(r)) == k for r in bursts.recruited.tolist())

    # the recording against the schedule: each recruited channel fires at
    # the burst's onset plus its lag, and then through the burst
    start = bursts.onset[:, None] + bursts.lag[bursts.recruited]
    spikes = set(zip(rec.types.tolist(), rec.times.tolist()))
    assert all((c, s) in spikes for c, s in zip(bursts.recruited.ravel().tolist(),
                                                start.ravel().tolist())
               if s < seconds * 1000)
    inside = np.zeros((n, int(seconds * 1000)), bool)  # channel's burst ticks
    for ch, s, d in zip(bursts.recruited, start, bursts.duration):
        for c, s0 in zip(ch.tolist(), s.tolist()):
            inside[c, s0:s0 + d] = True
    burst_ticks = int(inside.sum())
    p_tick = 1 - np.exp(-b["rate_hz"] / 1000)
    got_in = int(inside[rec.types, t].sum())
    want_in = burst_ticks * p_tick
    # give or take each recruited channel's onset spike in each burst, which
    # comes on top of its Poisson ones (and where bursts overlap, one more)
    assert abs(got_in - want_in) <= 4 * np.sqrt(want_in) + len(start.ravel())
    # outside its bursts a channel fires at its own background rate
    quiet = (~inside).sum(axis=1) / 1000.0  # seconds
    got_out = np.bincount(rec.types[~inside[rec.types, t]], minlength=n)
    want_out = rate * quiet
    assert abs(got_out.sum() - want_out.sum()) <= 4 * np.sqrt(want_out.sum())
    # the spread is the stated one: the log of the channels' background
    # rates has sigma rate_sigma (the deviation of 16 draws strays by about
    # a fifth of itself, so within a third of it)
    sigma = np.std(np.log(got_out / quiet))
    assert abs(sigma - cfg["rate_sigma"]) < cfg["rate_sigma"] / 3
    # the recruited share, measured: in bursts that overlap no other, the
    # channels that fire 5 times or more inside their own span are those
    # recruited, give or take one fast background channel
    ends = bursts.onset + b["lag_ms"][1] + bursts.duration
    for j in range(len(bursts.onset)):
        if ((j and ends[j - 1] > bursts.onset[j])
                or (j + 1 < len(ends) and ends[j] > bursts.onset[j + 1])):
            continue
        s = bursts.onset[j] + bursts.lag
        e = s + bursts.duration[j]
        busy = {c for c in range(n)
                if np.count_nonzero((t[rec.types == c] >= s[c])
                                    & (t[rec.types == c] < e[c])) >= 5}
        assert set(bursts.recruited[j].tolist()) <= busy
        assert len(busy) <= k + 1


def _checkout_with_the_burst_cell(tmp_path) -> Path:
    """A checkout whose benchmark gains, as files and entries only, the
    burst fixture as a configuration and a replay cell of it."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(".cache"))
    (root / "src").symlink_to(ROOT / "src")
    shutil.copy(FIXTURE, root / "bench" / "configs" / "burst-culture.json")
    shutil.copy(DATA / "replay-burst.json", root / "bench" / "traffic")
    s = spec.load_spec(ROOT)
    s["configs"].append({"name": "burst-culture", "source": "bench/tests/data",
                         "file": "bench/configs/burst-culture.json", "reduced": [],
                         "why": "x"})
    s["workloads"].append({"name": "burst-culture-replay", "config": "burst-culture",
                           "traffic": "replay-burst", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    return root


def test_a_burst_cell_catches_a_dropped_recount(tmp_path):
    """Rehearsed on the CPU, the burst cell is correct; with the exact
    recount of overflowed lists switched off (``no_recount``, which the
    sym26 cells do not catch: their lists never overflow at LCAP 4) the
    served counts differ from the reference."""
    root = _checkout_with_the_burst_cell(tmp_path)
    args = ["--workload", "burst-culture-replay", "--seed", str(2**35 + 15),
            "--seconds", "1", "--trace", "0", "--rehearse"]
    runs = {name: subprocess.Popen(
        [sys.executable, *cmd, *args], cwd=root, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=dict(os.environ))
        for name, cmd in [("sound", ["bench/run.py"]),
                          ("no_recount", ["bench/tests/faulty_run.py", "no_recount"])]}
    lines = {}
    for name, p in runs.items():
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-3000:]
        lines[name] = json.loads(out.strip().splitlines()[-1])
    assert lines["sound"]["correct"] is True, lines["sound"]["checks"]
    assert lines["no_recount"]["correct"] is False
    assert lines["no_recount"]["checks"]["count_mismatches"]["value"] > 0
