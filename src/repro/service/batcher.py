"""Cross-session batching: shape-bucketed device batches per scan kind,
vmapped over a session axis, with group-scoped flushes and a measured
fusion gate.

The multi-tenant service advances many ``MiningSession``s concurrently.
Each session's miner bottoms out in a handful of jit'd scans (A1
bounded-list, A2 single-slot, MapConcatenate segment map); running S
sessions naively issues S small dispatches per level per window. This
module is the executor that turns those into one dispatch per shape
bucket:

* each session step runs in its own worker thread and installs this
  executor into its counters (``StreamingCounter.executor`` seam);
* a counter's scan call becomes ``submit()`` — the thread parks on an
  event;
* each pending shape-group flushes **the moment its own members are
  parked** (group-scoped flush): expected membership per group is
  learned from the session's previous step's request keys (or declared
  at ``begin_step``), so a group never waits on tenants that were never
  going to join it. The thread whose submit (or ``end_step``) completes
  a group executes its flush: it stacks the group's operands along a new
  leading session axis and runs one jit'd ``vmap`` of the underlying
  scan, scattering per-lane results back. Singleton lanes dispatch
  immediately through the plain unvmapped call. Sessions with no
  prediction yet (first step) are wildcards — all groups then wait for
  every live step to park, the old global barrier — and a
  ``flush_deadline_s`` timeout force-flushes a group should a stale
  prediction ever strand it.
* fusion is **cost-gated** (``FusionCostModel``): per-(key, lane-bucket)
  EWMAs of fused vs standalone launch seconds, fed from the flush paths'
  own timings, decide per group whether the vmapped launch actually
  beats per-lane dispatches; losing groups release their lanes to
  launch concurrently (``batch.self_launch``). Decisions are exported
  as ``batcher_fusion_gate_total{decision=...}``.

The carried Pallas kernels do not fuse: ``a1_kernel_scan`` /
``a2_kernel_scan`` take operands already in kernel brick layout and
launch each request at once on its own thread. Their counters launch one
fixed shape per level (``ops.STREAM_BLOCK_M`` lanes ×
``STREAM_BLOCK_E`` events), so a vmapped form would only add a second
compiled kernel per level (compiled when sessions first step together)
and copy every lane's state in and out, to save a few launch overheads.

The multi-device MapConcatenate rides it too: ``mapc_sharded_scan``
fuses same-shape tenants' sharded commits into one launch that vmaps the
segmented kernel over the lane (session) axis *inside* the shard_map —
devices split the segment axis while lanes fill each device's grid
(``kernels.ops.a1_mapc_sharded_vmapped``).

Every scan in this engine is integer-only (i32 compares/adds, bool
masks), so the vmapped lane computation is bit-identical to the
standalone dispatch — the service's exactness guarantee rests on that and
is asserted by tests/test_service.py. Group sizes are padded to powers of
two (lane 0 repeated) so jit compiles once per (kind, bucket, S-bucket).

Adaptive L re-bucketing: requests are grouped *without* regard to their
event-buffer length — at flush time each lane's event operands are padded
to the group's max L (padded events are machine no-ops: PAD types never
match an episode row, so per-lane results stay bit-identical to the
standalone dispatch). Heterogeneous tenants — different window sizes,
different ingest rates — therefore fuse into one launch instead of
fragmenting into singleton groups keyed by L (the ROADMAP
adaptive-shape-bucketing item). The guardrail on the other side is
``max_pad_ratio``: a group whose lanes' event lengths spread beyond that
factor is split before flushing (``_split_oversized``), so one tenant's
giant windows cap — rather than multiply — the fleet's pad waste.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import Counter, deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.count_a1 import _a1_scan_core
from repro.core.count_a2 import _a2_scan_core
from repro.core.events import PAD_TYPE, TIME_NEG_INF
from repro.core.mapconcat import _map_all_segments
from repro.core.streaming import bucket_size
from repro.obs import REGISTRY, span


@functools.lru_cache(maxsize=None)
def _vmapped_a1():
    return jax.jit(jax.vmap(_a1_scan_core))


@functools.lru_cache(maxsize=None)
def _vmapped_a2():
    return jax.jit(jax.vmap(_a2_scan_core))


@functools.lru_cache(maxsize=None)
def _vmapped_mapc(lcap: int):
    return jax.jit(jax.vmap(lambda *args: _map_all_segments(*args, lcap)))


# per-kind padding specs for the episode (M) axis: (axis in each operand,
# pad value). Episodes are independent lanes of every scan (no cross-M
# interaction), so padding rows with inert machines is bit-safe for the
# real rows — results are sliced back to the caller's M.
_NEG = int(TIME_NEG_INF)  # "empty slot" filler for padded machine state
_PAD_A1 = (
    (0, 0), (0, 0), (0, 1), (None, 0), (None, 0), (0, _NEG), (0, 0), (0, 0), (0, 0)
)
_PAD_A2 = ((0, 0), (0, 0), (0, 1), (None, 0), (None, 0), (0, _NEG), (0, 0))
_PAD_MAPC = ((None, 0), (None, 0), (0, 0), (0, 0), (0, 1), (None, 0), (0, 1))

# event-operand spec per kind for the adaptive L re-bucketing:
# {operand index: event axis}. Padded events are machine no-ops (type =
# PAD_TYPE never matches an episode row; the derived successor-duplicate
# flags are false on and before the pad tail), so padding a lane's event
# operands to the fused group's max length is bit-safe.
_EV_AXES = {
    "a1": {3: 0, 4: 0},  # ev_types[L], ev_times[L]
    "a2": {3: 0, 4: 0},
    "mapc": {0: 1, 1: 1},  # wt[Q, L], wtt[Q, L]
    "mapck": {5: 2},  # segment bricks [P, 5, LW]
    "mapcs": {5: 2},  # sharded segment bricks [P, 5, LW]
}


def _pad_m(args, spec, m_to: int):
    out = []
    for a, (axis, fill) in zip(args, spec):
        a = jnp.asarray(a)
        if axis is None or a.shape[axis] == m_to:
            out.append(a)
            continue
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, m_to - a.shape[axis])
        out.append(jnp.pad(a, pad, constant_values=fill))
    return tuple(out)


def _pad_events(kind: str, args, l_to: int):
    """Pad a lane's event operands along the event axis to the fused
    group's max length. Only the *types* slot needs the PAD_TYPE fill
    (kind "a1"/"a2" operand 3, the ``wt`` half of "mapc", row 0 of the
    kernel bricks); times/dup/τ entries of padded events are never
    consulted — no episode row matches type -1 — so they zero-fill."""
    args = list(args)
    for idx, axis in _EV_AXES[kind].items():
        a = jnp.asarray(args[idx])
        grow = l_to - a.shape[axis]
        if grow == 0:
            continue
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, grow)
        all_types = (kind in ("a1", "a2") and idx == 3) or (kind == "mapc" and idx == 0)
        a = jnp.pad(a, pad, constant_values=PAD_TYPE if all_types else 0)
        if kind in ("mapck", "mapcs"):  # segment brick: types = row 0
            a = a.at[:, 0, l_to - grow:].set(PAD_TYPE)
        args[idx] = a
    return tuple(args)


# seam kind -> the calibrated engine whose standalone cost stands in for
# one lane of that seam (a2 has no separate table entry: its scan is the
# same event walk with a narrower state, ptpe is the honest stand-in)
_PRIOR_ENGINE = {
    "a1": "ptpe",
    "a2": "ptpe",
    "mapc": "mapconcatenate",
    "mapck": "mapconcat_kernel",
    "mapcs": "mapconcat_sharded",
}


def _policy_prior(key) -> float | None:
    """Calibrated standalone-launch estimate for one seam key, or
    ``None`` when no table is installed (the gate then keeps its
    optimistic fuse-first prior).  Decodes the per-seam key layouts
    documented on the seam methods below."""
    from repro.core.calibrate import get_policy
    kind = key[0]
    engine = _PRIOR_ENGINE.get(kind)
    if engine is None:
        return None
    q = devices = 1
    if kind in ("a1", "a2"):  # ("a1", mb, n[, lcap])
        m, n = key[1], key[2]
    elif kind == "mapc":  # ("mapc", mb, n, Q, lcap)
        m, n, q = key[1], key[2], key[3]
    else:  # ("mapck"/"mapcs", n, lcap,
        n, m, q = key[1], key[4][1], key[5]  # interp, shape, P[, d])
        if kind == "mapcs":
            devices = key[6]
    return get_policy().predict_single(engine, n_episode=n, m=m, q=q, devices=devices)


class FusionCostModel:
    """Measured fusion gate: EWMA launch costs fed from the flush paths.

    ``observe_fused`` records pad/fuse + vmapped-launch seconds for a
    (key, power-of-two lane bucket) combo; ``observe_single`` one plain
    dispatch of the same key. The first sample of every combo carries
    the jit compile and is discarded — the gate compares steady states.
    ``decide`` returns ``"fuse"`` when the fused estimate beats
    ``threshold`` × lanes × the standalone estimate, and also while
    either side is still unmeasured: fusing is the optimistic prior (it
    is the only way to measure the fused side, and forcing per-lane
    probe rounds would pay the standalone jit compiles *on top of* the
    fused ones — ruinous on compile-bound hosts). Standalone estimates
    accrue organically from singleton flushes and declined groups.
    ``"standalone"`` means the measurement says per-lane dispatches
    win."""

    def __init__(self, alpha: float = 0.25, threshold: float = 1.0, prior=None):
        self.alpha = alpha
        self.threshold = threshold
        self.prior = prior  # key -> est. standalone seconds | None
        self._fused: dict = {}  # (key, lane bucket) -> EWMA seconds
        self._single: dict = {}  # key -> EWMA seconds
        self._warm: set = set()  # combos whose compile sample is spent

    def _ewma(self, table: dict, key, dt: float) -> None:
        prev = table.get(key)
        table[key] = dt if prev is None else prev + self.alpha * (dt - prev)

    def observe_fused(self, key, lanes: int, dt: float) -> None:
        k = ("f", key, bucket_size(lanes, 1))
        if k not in self._warm:
            self._warm.add(k)
            return
        self._ewma(self._fused, (key, bucket_size(lanes, 1)), dt)

    def observe_single(self, key, dt: float) -> None:
        k = ("s", key)
        if k not in self._warm:
            self._warm.add(k)
            return
        self._ewma(self._single, key, dt)

    def decide(self, key, lanes: int) -> str:
        single = self._single.get(key)
        fused = self._fused.get((key, bucket_size(lanes, 1)))
        if single is None and self.prior is not None:
            # calibrated standalone estimate: lets a measured fused cost
            # trigger "standalone" before any organic singleton flush of
            # this key has been observed
            single = self.prior(key)
            if single is not None:
                REGISTRY.counter("batcher_fusion_prior_total", kind=key[0]).inc()
        if fused is None or single is None:
            return "fuse"  # optimistic until both sides are measured
        if fused <= self.threshold * lanes * single:
            return "fuse"
        return "standalone"


class _Request:
    __slots__ = (
        "kind",
        "key",
        "args",
        "spec",
        "static",
        "m",
        "mb",
        "event",
        "result",
        "error",
        "sid",
        "run_self",
    )

    def __init__(self, kind, key, args, spec, static, m, mb):
        self.kind = kind
        self.key = key
        self.args = args  # raw (unpadded) operands
        self.spec = spec  # episode-axis pad spec, applied only on fusion
        self.static = static
        self.m = m  # real episode count (fused results sliced back)
        self.mb = mb  # shared M bucket this request groups under
        self.event = threading.Event()
        self.result = None
        self.error = None
        self.sid = None  # owning step's session id
        self.run_self = False  # gate verdict: owner launches its own lane


class CrossSessionBatcher:
    """Group-scoped flush executor for cross-session scan batching.

    Protocol (driven by the scheduler): ``begin_step(session_id)`` once
    per session step about to run — from the dispatching thread, before
    any worker starts, so no group ever flushes early because a slow
    thread had not registered yet. Each step then runs in its own worker
    thread, which calls ``bind_session(session_id)`` first and
    ``end_step(session_id)`` when the step finishes, error or not (that
    re-check is what keeps co-tenants from wedging when a step dies
    before its first submit). Counters inside the step call
    ``a1_scan``/``a2_scan``/``mapc_scan``, which park until their shape
    group flushes; single-request groups fall through to the plain
    (unvmapped) dispatch so a lone tenant pays no batching tax and
    shares jit caches with standalone runs. Anonymous ``begin_step()``
    (legacy callers) registers a wildcard step that the first unbound
    submitting thread claims — an all-wildcard fleet reproduces the old
    all-parked global barrier exactly."""

    def __init__(
        self,
        max_pad_ratio: float = 4.0,
        fusion_gate: bool = True,
        flush_deadline_s: float = 0.5,
    ):
        self._lock = threading.Lock()
        self._local = threading.local()
        # group-scoped flush state: pending requests per shape key, the
        # live step set, and per-step predicted/observed key multisets
        self._pending: dict[tuple, list[_Request]] = {}
        self._alive: set[str] = set()
        self._wildcard: set[str] = set()  # steps with no prediction
        self._remaining: dict[str, Counter] = {}  # predicted, not yet seen
        self._seen: dict[str, Counter] = {}  # submitted this step
        self._predicted: dict[str, Counter] = {}  # learned at end_step
        self._parked: Counter = Counter()  # parked requests per step
        self._anon_pool: deque[str] = deque()
        self._anon_ids = itertools.count()
        self.cost_model = FusionCostModel(prior=_policy_prior)
        self.fusion_gate = fusion_gate
        # safety net for stale predictions: a parked group force-flushes
        # after this many seconds even if a predicted member never shows
        self.flush_deadline_s = flush_deadline_s
        self.batches = 0  # flushes that actually fused >1 request
        self.fused_requests = 0
        self.split_groups = 0  # oversized groups split to cap pad waste
        self.pad_events = 0  # event slots added padding lanes to max L
        self.pad_lanes = 0  # repeated lanes padding groups to 2^k
        self.flush_groups = 0  # group flushes, any gate decision
        self.deadline_flushes = 0
        self.gate_decisions: Counter = Counter()
        # adaptive-L guardrail: a lane may be padded to at most this
        # multiple of its own event-buffer length inside a fused group;
        # beyond it the group splits (one tenant's giant windows must not
        # make the whole fleet's lanes pay giant pads). None disables.
        self.max_pad_ratio = max_pad_ratio

    # ------------------------------------------------------------ seams

    def a1_scan(self, args):
        # (etypes[M,N], tlo, thi, ev_t[L], ev_tt[L], s[M,N,C], ptr, c, ovf)
        # — event length L deliberately absent from the key (adaptive L
        # re-bucketing: lanes pad to the group max at flush)
        m, n = args[0].shape
        mb = bucket_size(m, 8)
        key = ("a1", mb, n, args[5].shape[-1])
        return self._submit(_Request("a1", key, args, _PAD_A1, None, m, mb))

    def a2_scan(self, args):
        # (etypes[M,N], tlo, thi, ev_t[L], ev_tt[L], s[M,N], c)
        m, n = args[0].shape
        mb = bucket_size(m, 8)
        key = ("a2", mb, n)
        return self._submit(_Request("a2", key, args, _PAD_A2, None, m, mb))

    def mapc_scan(self, args, lcap: int):
        # (wt[Q,L], wtt, etypes[M,N], tlo, thi, tau[Q+1], w[M]) — the
        # segment count Q stays in the key, the window length L does not
        m, n = args[2].shape
        mb = bucket_size(m, 8)
        key = ("mapc", mb, n, args[0].shape[0], lcap)
        return self._submit(_Request("mapc", key, args, _PAD_MAPC, lcap, m, mb))

    def a1_kernel_scan(self, args, n_levels: int, lcap: int, interpret: bool):
        # kernel-layout operands: (et[NP,MP], tlo, thi, ev[3,EP],
        # s[NP,LCAP,MP], po, cnt[8,MP], ovf) — launched at once, never
        # parked (carried kernels do not fuse; see the module docstring)
        key = ("a1k", n_levels, lcap, interpret, tuple(args[0].shape))
        return self._run_single_timed(
            _Request("a1k", key, args, None, (n_levels, lcap, interpret), None, None)
        )

    def a2_kernel_scan(self, args, n_levels: int, interpret: bool):
        # kernel-layout operands: (et[NP,MP], tlo, thi, ev[2,EP], s[NP,MP],
        # cnt[8,MP])
        key = ("a2k", n_levels, interpret, tuple(args[0].shape))
        return self._run_single_timed(
            _Request("a2k", key, args, None, (n_levels, interpret), None, None)
        )

    def mapc_kernel_scan(self, args, n_levels: int, lcap: int, interpret: bool):
        # segmented-kernel operands: (et[NP,MP], tlo, thi, cum[NP,MP],
        # w[8,MP], segs[P,5,LW]) — P stays in the key, LW pads to the
        # group max
        key = ("mapck", n_levels, lcap, interpret, tuple(args[0].shape), args[5].shape[0])
        return self._submit(
            _Request("mapck", key, args, None, (n_levels, lcap, interpret), None, None)
        )

    def mapc_sharded_scan(
        self, args, n_levels: int, lcap: int, interpret: bool, num_devices: int
    ):
        # mesh-sharded segmented launch: same operands as mapc_kernel_scan
        # with the segment axis sharded over ``num_devices`` mesh devices
        # at dispatch. Fused groups vmap over the lane (session) axis
        # inside the shard_map, so the whole fleet's commits run as one
        # per-device launch; P and the device count stay in the key.
        key = (
            "mapcs",
            n_levels,
            lcap,
            interpret,
            tuple(args[0].shape),
            args[5].shape[0],
            num_devices,
        )
        return self._submit(
            _Request(
                "mapcs",
                key,
                args,
                None,
                (n_levels, lcap, interpret, num_devices),
                None,
                None,
            ),
        )

    # --------------------------------------------------- step accounting

    def begin_step(self, session: str | None = None, expected=None) -> str:
        """Register one session step about to run. ``session`` names the
        tenant so its flush-group membership can be predicted from its
        previous step's request keys; ``expected`` (an iterable of
        request keys, duplicates meaning counts) declares the membership
        explicitly and overrides the learned prediction. An anonymous
        step (no session) is a wildcard — every group waits for it to
        park or finish, the old global-barrier behavior."""
        with self._lock:
            sid = session
            if sid is None:
                sid = f"anon-{next(self._anon_ids)}"
                self._anon_pool.append(sid)
            self._alive.add(sid)
            self._seen[sid] = Counter()
            pred = (
                Counter(expected) if expected is not None else self._predicted.get(sid)
            )
            if pred is None:
                self._wildcard.add(sid)
                self._remaining[sid] = Counter()
            else:
                self._wildcard.discard(sid)
                self._remaining[sid] = Counter(pred)
            return sid

    def bind_session(self, session: str) -> None:
        """Tie the calling thread's submissions to ``session``'s step."""
        self._local.sid = session

    def end_step(self, session: str | None = None) -> None:
        """Retire a step: record its submitted keys as the session's next
        prediction and re-check every pending group — a step that ends
        without submitting (early error included) must release any group
        that was waiting on it."""
        with self._lock:
            sid = (session if session is not None else self._thread_sid_locked())
            self._local.sid = None
            if sid is not None:
                self._alive.discard(sid)
                self._wildcard.discard(sid)
                seen = self._seen.pop(sid, None)
                if seen is not None:
                    self._predicted[sid] = seen
                self._remaining.pop(sid, None)
                self._parked.pop(sid, None)
            ready = self._collect_ready_locked()
        self._run_flushes(ready)

    def forget(self, session: str) -> None:
        """Drop an (evicted) session's learned membership prediction."""
        with self._lock:
            self._predicted.pop(session, None)

    def predicted_signature(self, session: str) -> tuple | None:
        """The session's learned shape-group membership as a sortable
        signature (or None before its first completed step). The
        scheduler orders a step's lanes by this so tenants that will
        park on the same flush groups run in the same bounded-width
        chunk — with fewer concurrent lanes than sessions, adjacency is
        what keeps groups filling instead of timing out."""
        with self._lock:
            pred = self._predicted.get(session)
        if not pred:
            return None
        return tuple(sorted(str(k) for k in pred))

    def _thread_sid_locked(self) -> str | None:
        sid = getattr(self._local, "sid", None)
        if sid is not None and sid in self._alive:
            return sid
        if self._anon_pool:  # unbound thread claims an anonymous step
            sid = self._local.sid = self._anon_pool.popleft()
            return sid
        return None

    # ----------------------------------------------------------- engine

    def _submit(self, req: _Request):
        with self._lock:
            sid = self._thread_sid_locked() if self._alive else None
            if sid is not None:
                req.sid = sid
                self._seen[sid][req.key] += 1
                rem = self._remaining.get(sid)
                if rem is not None and rem[req.key] > 0:
                    rem[req.key] -= 1
                self._pending.setdefault(req.key, []).append(req)
                self._parked[sid] += 1
                ready = self._collect_ready_locked()
        if sid is None:
            # no step barrier applies to this thread (counter used outside
            # a scheduled step): degenerate to the direct dispatch
            return self._run_single_timed(req)
        self._run_flushes(ready)
        # the parked time: co-tenant staging skew plus whichever thread
        # executes this group's flush (it completed the group, so it runs
        # the launch while we park).
        with span("batch.barrier_wait", kind=req.kind):
            while not req.event.wait(timeout=self.flush_deadline_s):
                late = []
                with self._lock:
                    if not req.event.is_set() and req.key in self._pending:
                        # a predicted member never showed and never parked
                        # elsewhere — stale prediction; force the flush
                        self.deadline_flushes += 1
                        REGISTRY.counter("batcher_deadline_flush_total").inc()
                        late = self._take_group_locked(req.key)
                self._run_flushes(late)
        if req.run_self:
            # gate chose per-lane dispatch: every owner thread launches
            # its own request concurrently (XLA releases the GIL), which
            # is also the standalone measurement the cost model needs
            return self._run_single_timed(req)
        if req.error is not None:
            raise req.error
        return req.result

    # Flush-readiness, with the lock held. A group may flush when every
    # live step is accounted for: parked on this key, parked on another
    # key (a thread is in one place at a time — if it is expected here
    # too, it joins a later flush of this key instead of wedging two
    # groups against each other), finished, or not predicted to submit
    # this key. Wildcard steps (no prediction) hold every group until
    # they park or end.
    def _group_ready_locked(self, key) -> bool:
        here = {r.sid for r in self._pending[key]}
        for sid in self._alive:
            if sid in here or self._parked[sid] > 0:
                continue
            if sid in self._wildcard or self._remaining[sid][key] > 0:
                return False
        return True

    def _collect_ready_locked(self) -> list[list[_Request]]:
        ready = []
        for key in list(self._pending):
            if self._group_ready_locked(key):
                ready.extend(self._take_group_locked(key))
        return ready

    def _take_group_locked(self, key) -> list[list[_Request]]:
        group = self._pending.pop(key, [])
        if not group:
            return []
        for r in group:
            self._parked[r.sid] -= 1
        self.flush_groups += 1
        REGISTRY.counter("batcher_flush_groups_total").inc()
        return [group]

    def _run_flushes(self, groups: list[list[_Request]]) -> None:
        """Execute flushed groups OUTSIDE the lock: other groups keep
        collecting and flushing concurrently — that overlap (one group's
        device launch against another's host staging) is the point of
        group-scoped flushes."""
        for group in groups:
            for sub in self._split_oversized(group):
                self._dispatch_group(sub)

    def _dispatch_group(self, sub: list[_Request]) -> None:
        key, lanes = sub[0].key, len(sub)
        if lanes == 1:
            decision = "singleton"
        elif not self.fusion_gate:
            decision = "fuse"
        else:
            decision = self.cost_model.decide(key, lanes)
        with self._lock:
            self.gate_decisions[decision] += 1
        REGISTRY.counter("batcher_fusion_gate_total", decision=decision).inc()
        if decision != "fuse":
            # singleton fall-through or measured loss: release every
            # lane to run its own plain dispatch
            for r in sub:
                r.run_self = True
                r.event.set()
            return
        try:
            results = self._run_fused(sub)
            for r, out in zip(sub, results):
                r.result = out
        except Exception as e:  # surface in every parked thread
            for r in sub:
                r.error = e
        for r in sub:
            r.event.set()

    def _split_oversized(self, group: list[_Request]):
        """Cap the adaptive-L pad waste: within one fused group every
        lane's event operands pad to the group max, so a single tenant
        with huge windows would make every small lane pay
        ``max_L / own_L`` wasted machine steps. Sort by event length and
        cut wherever a lane would exceed ``max_pad_ratio`` × the smallest
        length of its (sub)group — each side still fuses (lengths are
        power-of-two buckets, so splits are rare and stable)."""
        if (
            self.max_pad_ratio is None or len(group) < 2 or group[0].kind not in _EV_AXES
        ):
            return [group]
        ev_axes = _EV_AXES[group[0].kind]

        def ev_len(r):
            return max(np.shape(r.args[i])[ax] for i, ax in ev_axes.items())

        order = sorted(group, key=ev_len)
        subs, cur, lo = [], [order[0]], ev_len(order[0])
        for r in order[1:]:
            if ev_len(r) > lo * self.max_pad_ratio:
                subs.append(cur)
                cur, lo = [r], ev_len(r)
            else:
                cur.append(r)
        subs.append(cur)
        if len(subs) > 1:
            with self._lock:
                self.split_groups += len(subs) - 1
            REGISTRY.counter("batcher_split_groups_total").inc(len(subs) - 1)
        return subs

    @staticmethod
    def _slice(req: _Request, out):
        """Cut one fused lane's outputs back to the request's real episode
        count (episode axis is leading for a1/a2 state, trailing for mapc
        tuples)."""
        if req.kind == "mapc":
            return tuple(o[..., :req.m] for o in out)
        return tuple(o[:req.m] for o in out)

    def _run_fused(self, group: list[_Request]):
        kind, key = group[0].kind, group[0].key
        t0 = time.perf_counter()
        with self._lock:
            self.batches += 1
            self.fused_requests += len(group)
        REGISTRY.counter("batcher_batches_total").inc()
        REGISTRY.counter("batcher_fused_requests_total").inc(len(group))
        s = bucket_size(len(group), 1)
        lanes = group + [group[0]] * (s - len(group))  # pad: repeat lane 0
        # adaptive L re-bucketing: lanes with shorter event buffers pad to
        # the group max. Every producer pads to a LANES multiple (and past
        # one chunk, to a DEFAULT_BLOCK_E multiple — see ops.event_brick),
        # so the group max still divides the kernels' chunked event
        # BlockSpec evenly. np.shape: reading a length must not trigger a
        # host→device transfer of the whole buffer.
        ev_axes = _EV_AXES[kind]
        l_to = max(np.shape(r.args[i])[ax] for r in group for i, ax in ev_axes.items())
        with span("batch.pad_fuse", kind=kind, lanes=len(group)):
            waste = sum(
                l_to - max(np.shape(r.args[i])[ax] for i, ax in ev_axes.items())
                for r in group
            )
            with self._lock:
                self.pad_events += waste
                self.pad_lanes += s - len(group)
            REGISTRY.counter("batcher_pad_events_total").inc(waste)
            REGISTRY.counter("batcher_pad_lanes_total").inc(s - len(group))
            lane_args = [_pad_events(kind, r.args, l_to) for r in lanes]
            if kind not in ("mapck", "mapcs"):  # M-axis pad
                lane_args = [_pad_m(p, r.spec, r.mb) for p, r in zip(lane_args, lanes)]
            stacked = tuple(
                jnp.stack([jnp.asarray(p[i]) for p in lane_args])
                for i in range(len(group[0].args))
            )
        with span("batch.device_launch", kind=kind, lanes=len(group)):
            if kind in ("mapck", "mapcs"):
                from repro.kernels import ops as kops
                if kind == "mapcs":
                    d = group[0].static[3]
                    kops.KERNEL_CALLS["a1_mapc_shard"] += len(group) * d
                    out = kops.a1_mapc_sharded_vmapped(*group[0].static)(*stacked)
                else:
                    kops.KERNEL_CALLS["a1_mapc"] += len(group)
                    out = kops.a1_mapc_vmapped(*group[0].static)(*stacked)
                results = [tuple(o[i] for o in out) for i in range(len(group))]
            else:
                if kind == "a1":
                    out = _vmapped_a1()(*stacked)
                elif kind == "a2":
                    out = _vmapped_a2()(*stacked)
                else:
                    out = _vmapped_mapc(group[0].static)(*stacked)
                results = [
                    self._slice(r, tuple(o[i] for o in out)) for i, r in enumerate(group)
                ]
        with self._lock:
            self.cost_model.observe_fused(key, len(group), time.perf_counter() - t0)
        return results

    def _run_single_timed(self, req: _Request):
        """One lane's plain dispatch, in the owning thread, timed for the
        cost model. ``batch.self_launch`` is a span of its own thread, so
        concurrent self-launches do not read as serialized flush work."""
        t0 = time.perf_counter()
        with span("batch.self_launch", kind=req.kind):
            out = self._run_single(req)
        with self._lock:
            self.cost_model.observe_single(req.key, time.perf_counter() - t0)
        return out

    @staticmethod
    def _run_single(req: _Request):
        """Lone request: the plain unpadded dispatch — zero batching tax,
        same jit cache entries a standalone (executor-less) run warms."""
        from repro.core.count_a1 import _a1_carry_scan
        from repro.core.count_a2 import _a2_carry_scan
        if req.kind == "a1":
            return _a1_carry_scan()(*req.args)
        if req.kind == "a2":
            return _a2_carry_scan()(*req.args)
        if req.kind == "a1k":
            from repro.kernels import ops as kops
            n_levels, lcap, interpret = req.static
            return kops.a1_state_call(
                *req.args, n_levels=n_levels, lcap=lcap, interpret=interpret
            )
        if req.kind == "a2k":
            from repro.kernels import ops as kops
            n_levels, interpret = req.static
            return kops.a2_state_call(*req.args, n_levels=n_levels, interpret=interpret)
        if req.kind == "mapck":
            from repro.kernels import ops as kops
            n_levels, lcap, interpret = req.static
            return kops.a1_mapconcat_tuples(
                *req.args, n_levels=n_levels, lcap=lcap, interpret=interpret
            )
        if req.kind == "mapcs":
            from repro.kernels import ops as kops
            n_levels, lcap, interpret, d = req.static
            return kops.a1_mapconcat_sharded_tuples(
                *req.args,
                n_levels=n_levels,
                lcap=lcap,
                interpret=interpret,
                num_devices=d,
            )
        return _map_all_segments(*req.args, req.static)
