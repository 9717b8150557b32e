"""Wire transport: framing, exactly-once ingest, typed refusals, fuzz
safety, fault-injected exactness, and the quiesce-before-checkpoint
ordering fix.

The load-bearing claims:

* duplicated / retried / out-of-order batches never double-count — the
  per-session sequence horizon dedups replays and refuses gaps with
  typed statuses;
* malformed bytes (random mutations included) never crash a server
  thread: every failure is a typed STATUS frame or a clean close, and
  ``WireServer.unexpected`` stays empty;
* backpressure and shed decisions are observable as typed status codes
  and ``wire_*`` registry counters, not silent drops;
* checkpoints taken while the pipelined scheduler holds staged
  uncommitted preps first return them to the pending queues
  (``scheduler.quiesce``) — a restore replays each window exactly once.
"""

import json
import socket
import threading
import zlib

import numpy as np
import pytest

from repro.core import EventStream
from repro.obs import REGISTRY, TRACER
from repro.service import (MiningService, MiningSession, SchedulerPolicy,
                           SessionConfig)
from repro.service.client import MiningClient
from repro.service.wire import (HEADER, MAGIC, PROTO_VERSION, Frame,
                                FrameType, Status, WireServer,
                                decode_events, delta_payload, encode_events,
                                encode_frame, parse_address, read_frame)

NUM_TYPES = 5


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


def small_config(**kw):
    base = dict(intervals=((0, 4),), theta=3, max_level=3,
                history_limit=4)
    base.update(kw)
    return SessionConfig(**base)


def local_reference(cfg, wins):
    s = MiningSession("ref", cfg)
    for j, w in enumerate(wins):
        s.enqueue(w, final=(j == len(wins) - 1))
    while s.queue_depth:
        p = s.prepare()
        s.commit(p, s.execute(p))
    return [delta_payload(d) for d in s.poll()]


@pytest.fixture
def server(tmp_path):
    srv = WireServer(MiningService(), "127.0.0.1:0",
                     data_dir=tmp_path / "data")
    srv.start()
    yield srv
    srv.shutdown(drain=False)
    assert srv.unexpected == [], srv.unexpected


def raw_conn(srv):
    kind, target = parse_address(srv.address)
    sock = socket.socket(
        socket.AF_UNIX if kind == "unix" else socket.AF_INET,
        socket.SOCK_STREAM)
    sock.settimeout(30.0)
    sock.connect(target)
    return sock


def rpc(sock, frame):
    sock.sendall(encode_frame(frame))
    return read_frame(sock)


def open_session(sock, sid, cfg, req=9_000_000):
    from repro.service.wire import config_to_wire
    reply = rpc(sock, Frame(FrameType.OPEN_SESSION, req, json.dumps(
        {"session": sid, "config": config_to_wire(cfg)}).encode()))
    return reply


# --------------------------------------------------------------- framing


def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    f = Frame(FrameType.CONTROL, 123456789, b'{"op": "ping"}', flags=0)
    a.sendall(encode_frame(f))
    got = read_frame(b)
    assert (got.ftype, got.seq, got.payload) == (f.ftype, f.seq, f.payload)
    a.close(), b.close()


def test_events_roundtrip_and_validation():
    w = tie_heavy_stream(0, n=50)
    sid, stream, final = decode_events(encode_events("arr-0", w, True))
    assert sid == "arr-0" and final
    np.testing.assert_array_equal(stream.types, w.types)
    np.testing.assert_array_equal(stream.times, w.times)
    assert stream.num_types == w.num_types


@pytest.mark.parametrize("mutate,exc_code", [
    ("magic", Status.BAD_FRAME), ("version", Status.BAD_VERSION),
    ("crc", Status.BAD_CRC), ("length", Status.BAD_FRAME),
])
def test_torn_frames_raise_typed_errors(mutate, exc_code):
    from repro.service import wire
    raw = bytearray(encode_frame(Frame(FrameType.POLL, 7, b'{"a": 1}')))
    if mutate == "magic":
        raw[0] ^= 0xFF
    elif mutate == "version":
        raw[4] = 99
    elif mutate == "crc":
        raw[-1] ^= 0xFF  # flip a payload byte: CRC no longer matches
    elif mutate == "length":
        # huge declared length
        import struct
        struct.pack_into("!I", raw, 16, wire.MAX_PAYLOAD + 1)
    a, b = socket.socketpair()
    a.sendall(bytes(raw))
    a.close()
    with pytest.raises(wire.ProtocolError) as ei:
        read_frame(b)
    assert ei.value.code == exc_code
    b.close()


def test_parse_address_forms():
    assert parse_address("0.0.0.0:88") == ("tcp", ("0.0.0.0", 88))
    assert parse_address("unix:/tmp/x.sock") == ("unix", "/tmp/x.sock")
    assert parse_address(("h", 5)) == ("tcp", ("h", 5))
    with pytest.raises(ValueError):
        parse_address("nonsense")


# -------------------------------------------------- exactly-once ingest


def test_wire_serving_bit_identical_to_standalone(server):
    cfg = small_config()
    wins = split_by_index(tie_heavy_stream(3, n=200), 4)
    c = MiningClient(server.address, "t0", cfg, rng_seed=0)
    for j, w in enumerate(wins):
        c.submit(w, final=(j == len(wins) - 1))
    got = sorted(c.drain(deadline_s=120), key=lambda d: d["window_idx"])
    ref = local_reference(cfg, wins)
    assert [r["episodes"] for r in ref] == [g["episodes"] for g in got]
    c.close()


def test_one_window_carries_one_id_across_layers(server):
    """Over loopback, a window's ``wire.ingest``, ``session.mine_window``
    and the ``wire.deliver`` that first hands out its delta name the same
    (session, window); the delta's ``window_idx`` is that window."""
    TRACER.clear()
    cfg = small_config()
    wins = split_by_index(tie_heavy_stream(5, n=120), 3)
    c = MiningClient(server.address, "ids", cfg, rng_seed=0)
    for j, w in enumerate(wins):
        c.submit(w, final=(j == len(wins) - 1))
    got = sorted(d["window_idx"] for d in c.drain(deadline_s=120))
    c.close()
    evs = [e for e in TRACER.events() if (e.args or {}).get("session") == "ids"]
    ingested = [(e.args["session"], e.args["window"]) for e in evs
                if e.name == "wire.ingest" and "window" in e.args]
    mined = [(e.args["session"], e.args["window"]) for e in evs
             if e.name == "session.mine_window"]
    delivered = [("ids", w) for e in evs if e.name == "wire.deliver"
                 for w in e.args["windows"]]
    want = [("ids", j) for j in range(len(wins))]
    assert got == list(range(len(wins)))
    assert ingested == mined == sorted(delivered) == want
    # each window's delivery follows its mining, which follows its ingest
    end = {(e.name, e.args.get("window")): e.t0 + e.dur for e in evs}
    start = {(e.name, e.args.get("window")): e.t0 for e in evs}
    for e in evs:
        if e.name == "wire.deliver":
            for w in e.args["windows"]:
                assert end[("wire.ingest", w)] <= start[("session.mine_window", w)]
                assert end[("session.mine_window", w)] <= e.t0


def poll_until(sock, sid, want, deadline_s=120.0, req_base=8_100_000):
    """Poll (without acking) until ``want`` deltas are cached — the
    auto-pump mines asynchronously."""
    import time
    deadline = time.monotonic() + deadline_s
    n = 0
    while time.monotonic() < deadline:
        n += 1
        reply = rpc(sock, Frame(FrameType.POLL, req_base + n, json.dumps(
            {"session": sid, "ack_through": -1}).encode()))
        deltas = json.loads(reply.payload)["deltas"]
        if len(deltas) >= want:
            return sorted(deltas, key=lambda d: d["window_idx"])
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {want} deltas")


def test_duplicated_batch_frames_never_double_count(server):
    """The dedup acceptance: replaying an EVENT_BATCH (a retry after a
    lost ACK) yields one application and a dup ACK — and the mined counts
    equal a single-shot run. A ping between send and replay defeats the
    connection's at-most-once reply cache, forcing the replay down the
    sequence-number dedup path."""
    cfg = small_config()
    wins = split_by_index(tie_heavy_stream(5, n=160), 4)
    sock = raw_conn(server)
    open_session(sock, "dup", cfg)
    dup_acks = 0
    for j, w in enumerate(wins):
        frame = Frame(FrameType.EVENT_BATCH, j + 1,
                      encode_events("dup", w, final=(j == len(wins) - 1)))
        for replay in range(3):
            reply = rpc(sock, frame)
            assert reply.ftype == FrameType.ACK
            doc = json.loads(reply.payload)
            assert doc["applied"] == j + 1
            dup_acks += doc["duplicate"]
            rpc(sock, Frame(FrameType.CONTROL, 7_000_000 + 10 * j + replay,
                            json.dumps({"op": "ping"}).encode()))
    assert dup_acks == 2 * len(wins)  # every replay was deduped
    assert REGISTRY.counter("wire_dedup_hits_total").value >= dup_acks
    got = poll_until(sock, "dup", len(wins))
    ref = local_reference(cfg, wins)
    assert [r["episodes"] for r in ref] == [g["episodes"] for g in got]
    sock.close()


def test_sequence_gap_refused_with_out_of_order(server):
    cfg = small_config()
    sock = raw_conn(server)
    open_session(sock, "gap", cfg)
    w = tie_heavy_stream(1, n=40)
    reply = rpc(sock, Frame(FrameType.EVENT_BATCH, 5,
                            encode_events("gap", w)))
    assert reply.ftype == FrameType.STATUS
    doc = json.loads(reply.payload)
    assert doc["code"] == Status.OUT_OF_ORDER
    assert doc["expect"] == 1  # the client rewinds to this
    sock.close()


def test_poll_redelivers_until_acked(server):
    """At-least-once delivery: deltas stay cached until the client acks
    them via ``ack_through``; a reply lost to a dropped connection is
    re-delivered on the next poll."""
    cfg = small_config()
    sock = raw_conn(server)
    open_session(sock, "redeliver", cfg)
    w = tie_heavy_stream(2, n=60)
    rpc(sock, Frame(FrameType.EVENT_BATCH, 1, encode_events("redeliver", w)))
    p1 = poll_until(sock, "redeliver", 1)
    p2 = json.loads(rpc(sock, Frame(
        FrameType.POLL, 8_000_002,
        json.dumps({"session": "redeliver", "ack_through": -1}).encode()
    )).payload)["deltas"]
    assert p1 and p1 == p2  # unacked → redelivered
    p3 = json.loads(rpc(sock, Frame(
        FrameType.POLL, 8_000_003,
        json.dumps({"session": "redeliver",
                    "ack_through": p1[-1]["window_idx"]}).encode()
    )).payload)["deltas"]
    assert p3 == []  # acked → dropped from the cache
    sock.close()


# ----------------------------------------------------- typed refusals


def test_unknown_session_is_typed_status(server):
    sock = raw_conn(server)
    w = tie_heavy_stream(0, n=20)
    reply = rpc(sock, Frame(FrameType.EVENT_BATCH, 1,
                            encode_events("ghost", w)))
    assert reply.ftype == FrameType.STATUS
    assert json.loads(reply.payload)["code"] == Status.UNKNOWN_SESSION
    reply = rpc(sock, Frame(FrameType.POLL, 8_000_000,
                            json.dumps({"session": "ghost"}).encode()))
    assert json.loads(reply.payload)["code"] == Status.UNKNOWN_SESSION
    sock.close()


def test_admission_rejection_is_typed_status(tmp_path):
    svc = MiningService(policy=SchedulerPolicy(max_sessions=1))
    srv = WireServer(svc, "127.0.0.1:0", data_dir=tmp_path / "d")
    srv.start()
    try:
        sock = raw_conn(srv)
        r1 = open_session(sock, "a", small_config())
        assert r1.ftype == FrameType.SESSION_OK
        r2 = open_session(sock, "b", small_config(), req=9_000_001)
        assert r2.ftype == FrameType.STATUS
        assert json.loads(r2.payload)["code"] == Status.ADMISSION_REJECTED
        # same session, different config: also a typed refusal
        r3 = open_session(sock, "a", small_config(theta=4), req=9_000_002)
        assert json.loads(r3.payload)["code"] == Status.CONFIG_CONFLICT
        sock.close()
    finally:
        srv.shutdown(drain=False)
    assert srv.unexpected == []


def test_backpressure_surfaces_as_typed_status(tmp_path):
    svc = MiningService(policy=SchedulerPolicy(max_pending_windows=1))
    srv = WireServer(svc, "127.0.0.1:0", data_dir=tmp_path / "d",
                     auto_pump=False)
    srv.start()
    before = REGISTRY.counter("wire_backpressure_total").value
    try:
        sock = raw_conn(srv)
        open_session(sock, "bp", small_config())
        wins = split_by_index(tie_heavy_stream(7, n=80), 3)
        r1 = rpc(sock, Frame(FrameType.EVENT_BATCH, 1,
                             encode_events("bp", wins[0])))
        assert r1.ftype == FrameType.ACK
        r2 = rpc(sock, Frame(FrameType.EVENT_BATCH, 2,
                             encode_events("bp", wins[1])))
        assert r2.ftype == FrameType.STATUS
        doc = json.loads(r2.payload)
        assert doc["code"] == Status.BACKPRESSURE
        assert doc["queue_depth"] >= 1
        assert REGISTRY.counter("wire_backpressure_total").value > before
        # the refusal did not consume the seq: drain, retry, accepted
        svc.pump()
        r3 = rpc(sock, Frame(FrameType.EVENT_BATCH, 2,
                             encode_events("bp", wins[1])))
        assert r3.ftype == FrameType.ACK
        # ...and the counters surface in stats()
        stats = svc.stats()
        assert stats["wire"]["backpressure"] >= 1
        assert "recovery" in stats and "daemon" in stats
        sock.close()
    finally:
        srv.shutdown(drain=False)
    assert srv.unexpected == []


# ----------------------------------------------------------------- fuzz


def test_fuzz_random_mutations_never_crash_server(server):
    """Satellite acceptance: mutated frames and raw garbage produce typed
    STATUS frames or clean closes — never an unhandled exception in a
    server thread (``server.unexpected`` must stay empty)."""
    rng = np.random.default_rng(0xFE31)
    cfg = small_config()
    w = tie_heavy_stream(0, n=30)
    valid = [
        encode_frame(Frame(FrameType.OPEN_SESSION, 1, json.dumps(
            {"session": "fz", "config": {}}).encode())),
        encode_frame(Frame(FrameType.EVENT_BATCH, 1,
                           encode_events("fz", w))),
        encode_frame(Frame(FrameType.POLL, 2,
                           json.dumps({"session": "fz"}).encode())),
        encode_frame(Frame(FrameType.CONTROL, 3,
                           json.dumps({"op": "ping"}).encode())),
        encode_frame(Frame(FrameType.STATS, 4, b"")),
        # bogus frame type, valid framing
        encode_frame(Frame(99, 5, b"xx")),
    ]
    for trial in range(50):
        base = bytearray(valid[int(rng.integers(len(valid)))])
        nmut = int(rng.integers(1, 9))
        for _ in range(nmut):
            base[int(rng.integers(len(base)))] = int(rng.integers(256))
        if trial % 7 == 0:  # raw garbage, not even a frame
            base = bytearray(rng.integers(0, 256,
                                          int(rng.integers(1, 128)),
                                          dtype=np.uint8).tobytes())
        sock = raw_conn(server)
        try:
            sock.sendall(bytes(base))
            # a mutated length field can leave the server legitimately
            # waiting for bytes that never come — short timeout, then the
            # close delivers it a clean EOF
            sock.settimeout(1.0)
            try:
                sock.recv(1 << 16)  # STATUS reply or clean EOF — both fine
            except (TimeoutError, OSError):
                pass
        finally:
            sock.close()
    assert server.unexpected == [], server.unexpected
    # the server still serves correct traffic after the abuse
    c = MiningClient(server.address, "after-fuzz", cfg, rng_seed=1)
    wins = split_by_index(tie_heavy_stream(9, n=120), 3)
    for j, win in enumerate(wins):
        c.submit(win, final=(j == len(wins) - 1))
    got = sorted(c.drain(deadline_s=120), key=lambda d: d["window_idx"])
    ref = local_reference(cfg, wins)
    assert [r["episodes"] for r in ref] == [g["episodes"] for g in got]
    c.close()


def test_payload_garbage_keeps_connection_alive(server):
    """A syntactically valid frame with a garbage JSON payload is a
    payload-level error: typed STATUS, connection stays usable."""
    sock = raw_conn(server)
    reply = rpc(sock, Frame(FrameType.POLL, 11, b"\xff\xfenot json"))
    assert reply.ftype == FrameType.STATUS
    assert json.loads(reply.payload)["code"] == Status.BAD_FRAME
    # same connection still works
    reply = rpc(sock, Frame(FrameType.CONTROL, 12,
                            json.dumps({"op": "ping"}).encode()))
    assert reply.ftype == FrameType.CONTROL_OK
    sock.close()


# ----------------------------------- fault-injected client exactness


def test_faulty_link_still_bit_identical(server):
    """Deterministic drop/duplicate/truncate on the client's send path:
    retries, reconnects, and server-side dedup must keep the counts
    bit-identical to a clean run."""
    from repro.launch.wire_load import FaultyClient
    from repro.runtime.faultinject import FaultSpec

    cfg = small_config()
    wins = split_by_index(tie_heavy_stream(13, n=200), 5)
    c = FaultyClient(server.address, "faulty", cfg,
                     fault_spec=FaultSpec(seed=3, drop=0.15,
                                          duplicate=0.15, truncate=0.10),
                     rng_seed=4, deadline_s=120.0)
    for j, w in enumerate(wins):
        c.submit(w, final=(j == len(wins) - 1))
    got = sorted(c.drain(deadline_s=120), key=lambda d: d["window_idx"])
    assert c.injector.total_injected > 0  # the link really was nasty
    ref = local_reference(cfg, wins)
    assert [r["episodes"] for r in ref] == [g["episodes"] for g in got]
    c.close()


def test_fault_injector_is_deterministic():
    from repro.runtime.faultinject import FaultInjector, FaultSpec

    spec = FaultSpec(seed=42, drop=0.2, duplicate=0.2, truncate=0.1)
    frames = [bytes([i]) * (10 + i) for i in range(40)]
    a, b = FaultInjector(spec), FaultInjector(spec)
    plan_a = [a.plan(f) for f in frames]
    plan_b = [b.plan(f) for f in frames]
    assert plan_a == plan_b
    assert a.injected == b.injected
    assert a.total_injected > 0


# ------------------------------ quiesce-before-checkpoint (satellite)


def test_checkpoint_quiesces_staged_preps(tmp_path):
    """Regression for the graceful-shutdown ordering bug: with
    ``pipeline_depth=2`` the scheduler holds prepared-but-uncommitted
    windows that live in neither the pending queue nor the miner state.
    A checkpoint taken without quiescing silently drops them; the fix
    returns them to the queue first, so a cold restore mines every
    window exactly once."""
    svc = MiningService(policy=SchedulerPolicy(pipeline_depth=2))
    cfgs, feeds = {}, {}
    for i, seed in enumerate((0, 5)):
        cfg = small_config()
        sid = svc.create_session(f"q{i}", cfg)
        wins = split_by_index(tie_heavy_stream(seed, n=200), 4)
        cfgs[sid], feeds[sid] = cfg, wins
        for j, w in enumerate(wins):
            svc.ingest(sid, w, final=(j == len(wins) - 1))
    svc.scheduler.step()  # leaves next step's preps staged
    assert svc.scheduler._staged, "pipelined step should stage preps"
    staged_windows = {sid: prep.window_idx
                      for sid, prep in svc.scheduler._staged.items()}
    before = REGISTRY.counter("scheduler_quiesced_preps_total").value
    svc.checkpoint_all(tmp_path)  # must quiesce first
    assert REGISTRY.counter(
        "scheduler_quiesced_preps_total").value - before == len(
        staged_windows)
    assert not svc.scheduler._staged

    # cold restore into a fresh service: every window exactly once
    svc2 = MiningService(policy=SchedulerPolicy(pipeline_depth=2))
    for sid, cfg in cfgs.items():
        svc2.create_session(sid, cfg)
        svc2.session(sid).restore(tmp_path)
    svc2.pump()
    for sid, wins in feeds.items():
        got = [delta_payload(d) for d in svc2.poll(sid)]
        ref = local_reference(cfgs[sid], wins)
        assert len(got) == len(ref), \
            f"{sid}: staged window lost or duplicated across checkpoint"
        assert [r["episodes"] for r in ref] == [g["episodes"] for g in got]


# -------------------------------------------------- concurrent clients


def test_concurrent_sessions_over_one_server(server):
    cfgs = [small_config(), small_config(theta=2)]
    feeds = [split_by_index(tie_heavy_stream(s, n=150), 3)
             for s in (1, 8)]
    results = [None, None]

    def drive(i):
        c = MiningClient(server.address, f"conc-{i}", cfgs[i],
                         rng_seed=i)
        for j, w in enumerate(feeds[i]):
            c.submit(w, final=(j == len(feeds[i]) - 1))
        results[i] = sorted(c.drain(deadline_s=120),
                            key=lambda d: d["window_idx"])
        c.close()

    threads = [threading.Thread(target=drive, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    for i in (0, 1):
        ref = local_reference(cfgs[i], feeds[i])
        assert results[i] is not None, f"client {i} hung"
        assert ([r["episodes"] for r in ref]
                == [g["episodes"] for g in results[i]])


def test_crc_is_actually_checked():
    # direct: flipping one payload bit after encode breaks the CRC
    raw = bytearray(encode_frame(Frame(FrameType.STATS, 1, b"hello")))
    assert zlib.crc32(b"hello") == HEADER.unpack(raw[:HEADER.size])[6]
    assert HEADER.unpack(raw[:HEADER.size])[0] == MAGIC
    assert HEADER.unpack(raw[:HEADER.size])[1] == PROTO_VERSION
