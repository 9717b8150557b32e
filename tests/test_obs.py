"""Observability-plane tests: span exception-safety and nesting (down
through a batched service run, and every span inside a window's life
with its arguments), the ring's eviction count, registry snapshot/delta
determinism, the KERNEL_CALLS facade ≡ registry equivalence (including a
forced kernel→XLA degradation), Chrome trace-event export schema, and
the jaxprof/tracecheck recompile-regex pin."""

import json
import re
import threading

import numpy as np
import pytest

import jax

from repro.core.episodes import EpisodeBatch
from repro.core.streaming import StreamingCounter
from repro.data import partition_windows, sym26
from repro.kernels.tally import (KERNEL_CALLS, fallback_counts,
                                 record_fallback, reset_kernel_calls)
from repro.obs import REGISTRY, TRACER
from repro.obs.jaxprof import _COMPILE_RE, ensure_recompile_listener
from repro.obs.registry import Registry
from repro.obs.trace import Tracer
from repro.service import MiningService, SchedulerPolicy, SessionConfig


# ------------------------------------------------------------------ spans


def test_span_closes_on_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("boom")
    assert tr.current() is None  # both stacks unwound
    names = [e.name for e in tr.events()]
    assert names == ["inner", "outer"]  # closed inside-out, both recorded


def test_span_nesting_depth_and_args():
    tr = Tracer()
    with tr.span("a", step=1):
        assert tr.current() == "a"
        with tr.span("b"):
            assert tr.current() == "b"
    evs = tr.events()
    by_name = {e.name: e for e in evs}
    assert by_name["a"].depth == 0 and by_name["b"].depth == 1
    assert by_name["a"].args == {"step": 1}
    assert by_name["b"].t0 >= by_name["a"].t0
    assert by_name["b"].dur <= by_name["a"].dur


def test_span_disabled_records_nothing():
    tr = Tracer()
    tr.enabled = False
    with tr.span("x"):
        pass
    assert tr.events() == []


def test_note_adds_args_only_when_on():
    tr = Tracer()
    with tr.span("x", a=1) as sp:
        sp.note(b=2)
    with tr.span("y") as sp:
        sp.note(c=3)
    assert [e.args for e in tr.events()] == [{"a": 1, "b": 2}, {"c": 3}]
    tr.enabled = False
    with tr.span("z") as sp:
        sp.note(d=4)
    assert len(tr.events()) == 2


def test_ring_counts_evicted_spans():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    assert tr.dropped == 6
    assert [e.args["i"] for e in tr.events()] == [6, 7, 8, 9]
    tr.clear()
    assert tr.dropped == 0 and tr.events() == []
    with tr.span("s"):
        pass
    assert tr.dropped == 0


def test_spans_are_per_thread():
    tr = Tracer()
    gate = threading.Barrier(4)  # overlap the threads so tids are distinct

    def work(i):
        with tr.span("t", i=i):
            gate.wait(timeout=10)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == 4
    assert len({e.tid for e in evs}) == 4
    assert all(e.depth == 0 for e in evs)  # no cross-thread stack bleed


# --------------------------------------------------------------- registry


def test_registry_snapshot_and_delta_determinism():
    reg = Registry()
    reg.counter("req_total", route="a").inc(3)
    reg.counter("req_total", route="b").inc()
    reg.gauge("depth").set(7)
    reg.histogram("lat_s").observe(0.01)
    reg.histogram("lat_s").observe(0.02)
    s1 = reg.snapshot()
    s2 = reg.snapshot()
    assert s1 == s2
    assert list(s1) == sorted(s1)  # deterministic ordering
    assert s1["req_total{route=a}"] == 3
    assert s1["depth"] == 7
    assert s1["lat_s"]["count"] == 2

    before = reg.snapshot()
    reg.counter("req_total", route="a").inc(2)
    reg.histogram("lat_s").observe(0.05)
    d = Registry.delta(before, reg.snapshot())
    assert d["req_total{route=a}"] == 2
    assert d["lat_s"]["count"] == 1
    assert "depth" not in d  # unchanged series dropped
    assert "req_total{route=b}" not in d


def test_registry_type_conflict_rejected():
    reg = Registry()
    reg.counter("thing")
    with pytest.raises(TypeError):
        reg.gauge("thing")


def test_histogram_quantiles_bracket_observations():
    reg = Registry()
    h = reg.histogram("h")
    for v in (0.001, 0.01, 0.1, 1.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 4 and d["min"] == 0.001 and d["max"] == 1.0
    assert d["min"] <= d["p50"] <= d["p99"] <= d["max"] * 1.01


# --------------------------------------------- KERNEL_CALLS facade ≡ registry


def test_kernel_calls_view_is_the_registry():
    reset_kernel_calls()
    KERNEL_CALLS["a1"] += 3
    KERNEL_CALLS["a2_state"] += 1
    assert REGISTRY.counter("kernel_calls", kind="a1").value == 3
    assert dict(KERNEL_CALLS) == {"a1": 3, "a2_state": 1}
    assert KERNEL_CALLS["never_touched"] == 0  # Counter semantics
    record_fallback("some_site")
    assert KERNEL_CALLS["fallback:some_site"] == 1
    assert fallback_counts()["some_site"] == 1
    assert REGISTRY.snapshot()["kernel_calls{kind=fallback:some_site}"] == 1
    reset_kernel_calls()
    assert dict(KERNEL_CALLS) == {}
    assert "kernel_calls{kind=a1}" not in REGISTRY.snapshot()


def test_forced_degradation_lands_in_registry(monkeypatch):
    if jax.default_backend() == "tpu":
        pytest.skip("kernel dispatch cannot be declined on TPU")
    for var in ("REPRO_KERNEL_INTERPRET", "REPRO_INTERPRET_KERNELS"):
        monkeypatch.delenv(var, raising=False)
    reset_kernel_calls()
    eps = EpisodeBatch(np.array([[0, 1]], np.int32),
                       np.array([[2]], np.int32), np.array([[9]], np.int32))
    # no TPU, interpret not requested -> the kernel residency probe must
    # decline and the downgrade must land in the shared registry
    StreamingCounter(eps, engine="ptpe", use_kernel=True)
    assert KERNEL_CALLS["fallback:stream_a1_residency"] == 1
    assert REGISTRY.counter(
        "kernel_calls", kind="fallback:stream_a1_residency").value == 1
    assert fallback_counts() == {"stream_a1_residency": 1}
    reset_kernel_calls()


# ---------------------------------------------------------------- exports


def test_chrome_trace_schema(tmp_path):
    tr = Tracer()
    with tr.span("phase.outer", k="v"):
        with tr.span("phase.inner"):
            pass
    path = tmp_path / "trace.json"
    n = tr.export_chrome(path)
    assert n == 2
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2 and len(ms) == 1
    for e in xs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0  # µs from trace origin
        assert e["tid"] == 0  # single thread remaps to small int
    assert ms[0]["name"] == "thread_name"
    inner, outer = sorted(xs, key=lambda e: e["ts"], reverse=True)
    assert inner["name"] == "phase.inner"
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0

    jl = tmp_path / "trace.jsonl"
    assert tr.export_jsonl(jl) == 2
    rows = [json.loads(line) for line in jl.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["phase.inner", "phase.outer"]
    assert all({"name", "ts", "dur_s", "tid", "depth", "args"} <= set(r)
               for r in rows)


# ------------------------------------------------- service-threaded spans


def test_spans_nest_through_batched_service():
    TRACER.clear()
    svc = MiningService(policy=SchedulerPolicy(max_sessions=4))
    feeds = {}
    for i in range(2):
        stream, _ = sym26(seconds=1, rate_hz=10.0, seed=40 + i)
        sid = svc.create_session(f"obs-{i}", SessionConfig(window_ms=500))
        wins = list(partition_windows(stream, 500))
        feeds[sid] = wins
    for sid, wins in feeds.items():
        for j, w in enumerate(wins):
            svc.ingest(sid, w, final=j == len(wins) - 1)
    svc.pump()
    evs = TRACER.events()
    names = {e.name for e in evs}
    assert {"service.ingest", "schedule.step", "schedule.snapshot",
            "session.mine_window", "batch.barrier_wait"} <= names
    # every mine_window nests inside some schedule.step's window
    steps = [e for e in evs if e.name == "schedule.step"]
    for m in (e for e in evs if e.name == "session.mine_window"):
        assert any(s.t0 <= m.t0 and m.t0 + m.dur <= s.t0 + s.dur + 1e-6
                   for s in steps)
    # candidate generation and the batcher's parking nest inside a
    # window's mining on the lane's own thread
    mines = [e for e in evs if e.name == "session.mine_window"]
    for name in ("mine.candidates", "batch.barrier_wait"):
        inner = [e for e in evs if e.name == name]
        assert inner, name
        assert all(_inside(e, mines) for e in inner), name
    assert len(steps) > 0

    stats = svc.stats()
    assert stats["scheduler"]["queue_depth"] == 0
    assert stats["scheduler"]["heartbeat_ts"] > 0
    assert "recompiles" in stats["kernel"]
    assert "fallbacks" in stats["kernel"]
    assert stats["metrics"]["scheduler_steps_total"] >= len(steps)
    for sid in feeds:
        assert f"session_windows_total{{session={sid}}}" in stats["metrics"]


def _inside(e, parents) -> bool:
    """``e`` lies within one of ``parents`` on the same thread, deeper."""
    return any(p.tid == e.tid and p.depth < e.depth and p.t0 <= e.t0
               and e.t0 + e.dur <= p.t0 + p.dur + 1e-6 for p in parents)


# where each span of a window's life may sit, and the arguments it carries
_WINDOW_SPANS = {
    "mine.candidates": (("session.mine_window",), {"level", "m"}),
    "stream.counter_init": (("session.mine_window",), {"kind", "m"}),
    "stream.replay": (("session.mine_window",), {"windows"}),
    "stream.readback": (("session.mine_window", "ckpt.state"), {"m"}),
    "stream.recount": (("session.mine_window",),
                       {"episodes", "events", "fed"}),
    "ckpt.state": (("service.checkpoint", "schedule.snapshot",
                    "schedule.stage"), {"leaves"}),
    "ckpt.write": (("service.checkpoint",), {"leaves", "bytes"}),
}


def test_window_life_spans_nest_and_carry_args(tmp_path):
    """A service with a checkpoint directory mines a few Sym26 windows:
    every span inside a window's life appears, inside the span that
    causes it, with its arguments, and the operator counters agree with
    the spans' arguments."""
    TRACER.clear()
    recounted = REGISTRY.counter("stream_recount_episodes_total").value
    carried = REGISTRY.counter("stream_recount_carried_total").value
    stepped = REGISTRY.counter("stream_recount_events_total").value
    written = REGISTRY.counter("checkpoint_bytes_total").value
    svc = MiningService(policy=SchedulerPolicy(max_sessions=2))
    # lcap 1 overflows the bounded lists, so episodes are recounted;
    # history_limit 2 advances the counters' base every other window
    cfg = SessionConfig(intervals=((5, 10),), theta=3, max_level=3,
                        window_ms=500, history_limit=2, lcap=1)
    stream, _ = sym26(seconds=3, rate_hz=12.0, seed=7)
    sid = svc.create_session("life", cfg)
    for j, w in enumerate(partition_windows(stream, 500)):
        assert svc.ingest(sid, w) == j  # the window's session-local index
        svc.pump()
        svc.checkpoint_all(tmp_path)
    evs = TRACER.events()
    assert TRACER.dropped == 0
    mines = [e for e in evs if e.name == "session.mine_window"]
    assert [e.args["window"] for e in mines] == list(range(len(mines)))
    assert [d.window_idx for d in svc.poll(sid)] == list(range(len(mines)))
    for name, (parents, args) in _WINDOW_SPANS.items():
        got = [e for e in evs if e.name == name]
        assert got, name
        outer = [e for e in evs if e.name in parents]
        assert all(_inside(e, outer) for e in got), name
        assert all(args <= set(e.args) for e in got), name
    # the base advance reads the counters back and recounts inside itself
    adv = [e for e in evs if e.name == "stream.checkpoint"]
    assert any(_inside(e, adv) for e in evs if e.name == "stream.recount")
    assert any(_inside(e, adv) for e in evs if e.name == "stream.readback")
    assert any(_inside(e, [c for c in evs if c.name == "service.checkpoint"])
               for e in evs if e.name == "ckpt.state")
    assert {e.args["kind"] for e in evs
            if e.name == "stream.counter_init"} == {"a1", "a2"}
    assert all(e.args["windows"] >= 1 for e in evs if e.name == "stream.replay")
    stats = svc.stats()
    assert (stats["streaming"]["recount_episodes"] - recounted
            == sum(e.args["episodes"] for e in evs if e.name == "stream.recount") > 0)
    # flagged episodes' oracles are carried from read to read, and only
    # the events of their own channels are stepped
    assert stats["streaming"]["recount_carried"] - carried > 0
    assert (stats["streaming"]["recount_events"] - stepped
            == sum(e.args["fed"] for e in evs if e.name == "stream.recount") > 0)
    assert (stats["recovery"]["checkpoint_bytes"] - written
            == sum(e.args["bytes"] for e in evs if e.name == "ckpt.write") > 0)


# ---------------------------------------------------------------- jaxprof


def test_recompile_regex_pinned_to_tracecheck():
    from repro.analysis.tracecheck import _COMPILE_RE as tc_re
    assert _COMPILE_RE.pattern == tc_re.pattern


def test_recompile_listener_counts_compiles():
    assert ensure_recompile_listener()
    before = {labels["kernel"]: m.value
              for labels, m in REGISTRY.family_items("recompiles")}

    def _obs_probe_fn(x):
        return x * 2 + 1

    jax.jit(_obs_probe_fn)(np.arange(37, dtype=np.int32))
    after = {labels["kernel"]: m.value
             for labels, m in REGISTRY.family_items("recompiles")}
    grew = [k for k in after if after[k] > before.get(k, 0)]
    assert any("_obs_probe_fn" in k for k in grew), (before, after)


def test_recompile_regex_accepts_jax_names():
    m = re.match(_COMPILE_RE, "Compiling _a1_scan_core with global shapes "
                              "and types [ShapedArray(int32[128])].")
    assert m and m.group(1) == "_a1_scan_core"


def test_recompile_regex_accepts_jit_wrapped_names():
    # the JAX 0.9 spelling of the same log line
    m = re.match(_COMPILE_RE, "Compiling jit(a1_count_state_kernel) with "
                              "global shapes and types [ShapedArray(int32[8,128])].")
    assert m and m.group(1) == "a1_count_state_kernel"
