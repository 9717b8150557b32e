"""Daemon lifecycle + crash recovery: the mining service survives
disconnects, SIGTERM drains, and SIGKILL-at-a-window-boundary restarts
with bit-identical counts.

The load-bearing claims:

* a daemon SIGKILLed mid-stream at a randomized (but seeded) window
  boundary, restarted cold from its checkpoint store, and resumed from
  the last durable sequence number produces *bit-identical* per-window
  episode counts — for every engine × two-pass combination;
* SIGTERM during in-flight work commits staged windows (drain +
  quiesce + checkpoint) before exit: nothing queued is lost, nothing
  is double-counted across the restart;
* the pidfile lifecycle (start/status/stop, stale-pidfile cleanup) and
  the heartbeat gauges in ``stats()`` behave as the ops runbook says.

All daemons here are real subprocesses over Unix sockets — in-process
threads cannot be SIGKILLed honestly.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import EventStream
from repro.runtime.faultinject import kill_point
from repro.service import MiningSession, SessionConfig
from repro.service.client import MiningClient
from repro.service.daemon import MiningDaemon
from repro.service.wire import delta_payload

NUM_TYPES = 5
SRC = str(Path(__file__).resolve().parents[1] / "src")


def tie_heavy_stream(seed, n=240):
    rng = np.random.default_rng(seed)
    gaps = rng.choice([0, 0, 1, 2], size=n)
    times = (np.cumsum(gaps) + 1).astype(np.int32)
    types = rng.integers(0, NUM_TYPES, size=n).astype(np.int32)
    return EventStream(types, times, NUM_TYPES)


def split_by_index(stream, k):
    n = stream.types.shape[0]
    cuts = [0] + [n * j // k for j in range(1, k)] + [n]
    return [EventStream(stream.types[a:b], stream.times[a:b],
                        stream.num_types)
            for a, b in zip(cuts[:-1], cuts[1:])]


def local_reference(cfg, wins):
    s = MiningSession("ref", cfg)
    for j, w in enumerate(wins):
        s.enqueue(w, final=(j == len(wins) - 1))
    while s.queue_depth:
        p = s.prepare()
        s.commit(p, s.execute(p))
    return [delta_payload(d) for d in s.poll()]


def spawn_daemon(tmp_path, crash_after=None, extra=()):
    """Foreground daemon subprocess on a Unix socket under tmp_path;
    returns (Popen, address) once the pidfile reports the bound socket."""
    sock = tmp_path / "d.sock"
    data = tmp_path / "data"
    argv = [sys.executable, "-m", "repro.service.daemon",
            "--listen", f"unix:{sock}", "--data-dir", str(data),
            *extra]
    if crash_after is not None:
        argv += ["--crash-after-commits", str(crash_after)]
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    # the daemon's entry point places a persistent compile cache; keep
    # the test daemons off it (its load warnings would fill the unread
    # output pipe and stall the daemon)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    proc = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    pidfile = data / "daemon.pid"
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"daemon died at boot:\n{proc.stdout.read()}")
        doc = MiningDaemon.read_pidfile(pidfile)
        if doc and doc.get("address"):
            return proc, doc["address"]
        time.sleep(0.05)
    proc.kill()
    raise TimeoutError("daemon never became ready")


def stop_daemon(proc):
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise


# ----------------------------------------------------------- lifecycle


def test_daemon_lifecycle_pidfile_heartbeat_sigterm(tmp_path):
    proc, addr = spawn_daemon(tmp_path)
    pidfile = tmp_path / "data" / "daemon.pid"
    try:
        doc = MiningDaemon.status(pidfile)
        assert doc is not None and doc["pid"] == proc.pid
        assert doc["address"] == addr

        cfg = SessionConfig(intervals=((0, 4),), theta=3, max_level=3)
        c = MiningClient(addr, "hb", cfg, rng_seed=0)
        pong = c.ping()
        assert pong["op"] == "ping" and not pong["draining"]
        stats = c.stats()
        # the heartbeat thread feeds the obs gauges the runbook monitors
        assert stats["daemon"]["heartbeat_ts"] > 0
        assert stats["daemon"]["uptime_s"] >= 0
        assert time.time() - stats["daemon"]["heartbeat_ts"] < 30
        c.close()

        # graceful stop via the pidfile (SIGTERM + wait)
        assert MiningDaemon.stop(pidfile, timeout_s=90)
        proc.wait(timeout=30)
        assert proc.returncode == 0
        assert MiningDaemon.status(pidfile) is None
    finally:
        if proc.poll() is None:
            proc.kill()


def test_stale_pidfile_detected_after_sigkill(tmp_path):
    proc, addr = spawn_daemon(tmp_path)
    pidfile = tmp_path / "data" / "daemon.pid"
    proc.kill()  # SIGKILL: no cleanup, pidfile left behind
    proc.wait(timeout=30)
    deadline = time.monotonic() + 10
    while MiningDaemon.status(pidfile) is not None \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    assert MiningDaemon.status(pidfile) is None  # stale → cleaned up
    assert not pidfile.exists()


def test_sigterm_midstream_commits_staged_windows(tmp_path):
    """Satellite acceptance: SIGTERM lands while submitted windows are
    still queued/staged (pipeline_depth=2 daemon default). The drain
    handler must quiesce staged preps and mine + checkpoint everything
    queued; after a cold restart every window is present exactly once,
    bit-identical to an unperturbed run."""
    cfg = SessionConfig(intervals=((0, 4),), theta=3, max_level=3,
                        history_limit=4)
    wins = split_by_index(tie_heavy_stream(11, n=220), 5)

    proc, addr = spawn_daemon(tmp_path)
    c = MiningClient(addr, "term", cfg, rng_seed=3, deadline_s=180.0)
    for j, w in enumerate(wins):
        c.submit(w, final=(j == len(wins) - 1))
    # SIGTERM immediately: most windows are still pending or staged
    proc.send_signal(signal.SIGTERM)
    proc.wait(timeout=120)
    assert proc.returncode == 0
    c.close()

    proc2, addr2 = spawn_daemon(tmp_path)
    try:
        c2 = MiningClient(addr2, "term", cfg, rng_seed=4,
                          deadline_s=180.0)
        c2.next_seq = c.next_seq  # same producer, resumed
        got = sorted(c2.drain(deadline_s=180),
                     key=lambda d: d["window_idx"])
        ref = local_reference(cfg, wins)
        assert len(got) == len(ref), \
            "SIGTERM dropped or duplicated a staged window"
        assert [r["episodes"] for r in ref] == [g["episodes"] for g in got]
        stats = c2.stats()
        assert stats["recovery"]["cold_boots"] >= 1
        assert stats["recovery"]["sessions_restored"] >= 1
        c2.close()
    finally:
        stop_daemon(proc2)


# --------------------------------------------- SIGKILL crash recovery


# fixed per-combination seeds: the kill point must be deterministic run
# to run (PYTHONHASHSEED randomizes hash(), so no hash()-derived seeds)
_CRASH_SEEDS = {("hybrid", False): 101, ("hybrid", True): 102,
                ("ptpe", False): 103, ("ptpe", True): 104,
                ("mapconcatenate", False): 105,
                ("mapconcatenate", True): 106}


@pytest.mark.parametrize("engine", ["hybrid", "ptpe", "mapconcatenate"])
@pytest.mark.parametrize("two_pass", [False, True])
def test_sigkill_restart_resume_bit_identical(tmp_path, engine, two_pass):
    """The headline acceptance: SIGKILL the daemon mid-stream at a
    randomized (seeded, deterministic) window boundary, restart it cold,
    let the client resume from the last-acked sequence number — final
    per-window counts are bit-identical to an uninterrupted run, for
    every engine × two-pass combination.

    A supervisor thread restarts the daemon the moment it dies, the way
    a process manager would; the client rides through the outage on its
    reconnect/backoff path without ever seeing an error."""
    import threading

    seed = _CRASH_SEEDS[(engine, two_pass)]
    cfg = SessionConfig(intervals=((0, 4),), theta=3, max_level=3,
                        engine=engine, two_pass=two_pass, history_limit=4)
    wins = split_by_index(tie_heavy_stream(17, n=200), 5)
    crash_at = kill_point(seed, 1, len(wins))  # a real window boundary

    proc, addr = spawn_daemon(tmp_path, crash_after=crash_at)
    procs = [proc]
    crashed = threading.Event()

    def supervise():
        proc.wait()
        if proc.returncode == -signal.SIGKILL:
            crashed.set()
            procs.append(spawn_daemon(tmp_path)[0])  # clean restart

    sup = threading.Thread(target=supervise, daemon=True)
    sup.start()
    c = MiningClient(addr, "kill", cfg, rng_seed=seed, deadline_s=240.0)
    try:
        for j, w in enumerate(wins):
            c.submit(w, final=(j == len(wins) - 1))
        got = sorted(c.drain(deadline_s=240),
                     key=lambda d: d["window_idx"])
        sup.join(timeout=240)
        assert crashed.is_set(), \
            f"daemon was not SIGKILLed at commit {crash_at}"
        ref = local_reference(cfg, wins)
        assert len(got) == len(ref), \
            f"crash at commit {crash_at}: windows lost or duplicated"
        for r, g in zip(ref, got):
            assert r["episodes"] == g["episodes"], \
                f"window {r['window_idx']} diverged across SIGKILL"
        stats = c.stats()
        assert stats["recovery"]["cold_boots"] >= 1
        assert stats["recovery"]["sessions_restored"] >= 1
        c.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_sessions_manifest_written_atomically(tmp_path):
    proc, addr = spawn_daemon(tmp_path)
    try:
        cfg = SessionConfig(intervals=((0, 4),), theta=3)
        c = MiningClient(addr, "m0", cfg, rng_seed=0)
        c.open()
        w = tie_heavy_stream(0, n=40)
        c.submit(w)
        manifest = tmp_path / "data" / "SESSIONS.json"
        deadline = time.monotonic() + 30
        while not manifest.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        doc = json.loads(manifest.read_text())
        assert "m0" in doc["sessions"]
        assert doc["sessions"]["m0"]["theta"] == 3
        c.close()
    finally:
        stop_daemon(proc)


def test_daemon_parent_touches_no_device_before_detaching(tmp_path):
    """``mine_serve --listen --daemon`` builds the daemon in the parent and
    then execs a fresh interpreter that serves. The parent must not bring
    up a JAX backend first: on a TPU host it would hold the chip the
    daemon needs."""
    code = f"""
import sys
from jax._src import xla_bridge
from repro.launch import mine_serve
from repro.service.daemon import MiningDaemon

def start_detached(self):
    print("backends", len(xla_bridge._backends))
    return {{"pid": 0, "address": "-", "data_dir": "-"}}

MiningDaemon.start_detached = start_detached
sys.argv = ["mine_serve", "--listen", "127.0.0.1:0", "--daemon",
            "--data-dir", {str(tmp_path)!r}]
sys.exit(mine_serve.main())
"""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "backends 0" in out.stdout, out.stdout
