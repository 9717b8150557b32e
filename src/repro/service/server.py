"""The multi-tenant real-time mining service: ingest/poll facade.

The chip-on-chip loop, generalized to a fleet: many electrode arrays (or
any event-emitting chips) stream partition windows in; the service mines
them concurrently on shared devices and emits per-window frequent-episode
deltas per session. The pieces:

* ``MiningSession`` (session.py) — per-tenant miner, bounded memory,
  checkpointable state;
* ``CrossSessionBatcher`` (batcher.py) — scans from concurrently stepping
  sessions fused into per-shape-bucket vmapped dispatches;
* ``RoundRobinScheduler`` (scheduler.py) — admission, backpressure,
  fairness, watchdog retry.

Guarantee: per-session outputs are bit-identical to a standalone
``StreamingMiner`` over the same windows — batching and scheduling are
pure throughput optimizations (tests/test_service.py asserts this for
every engine × two-pass combination).

Usage::

    svc = MiningService()
    svc.create_session("array-0", SessionConfig(theta=4, window_ms=2000))
    svc.ingest("array-0", window)          # may raise BackpressureError
    svc.pump()                             # run pending batched steps
    for delta in svc.poll("array-0"):
        ...                                # per-window episode deltas
"""

from __future__ import annotations

import itertools

from repro.core import calibrate
from repro.core.events import EventStream
from repro.obs import REGISTRY, span
from repro.obs.jaxprof import ensure_recompile_listener
from repro.telemetry import MeterBank

from .batcher import CrossSessionBatcher
from .scheduler import RoundRobinScheduler, SchedulerPolicy
from .session import MiningSession, SessionConfig, WindowDelta


class MiningService:
    def __init__(self, policy: SchedulerPolicy | None = None, batching: bool = True):
        policy = policy or SchedulerPolicy()
        if policy.policy_table:
            # install the calibrated dispatch table for this process;
            # a stale/wrong-device file degrades to the heuristic (the
            # outcome is visible in stats()["calibration"]["source"])
            calibrate.install_table(policy.policy_table)
        self.batcher = CrossSessionBatcher(
            fusion_gate=policy.fusion_gate, flush_deadline_s=policy.flush_deadline_s
        ) if batching else None
        self.scheduler = RoundRobinScheduler(policy, self.batcher)
        self._auto_ids = itertools.count()
        # recompilation is a serving SLO hazard (a shape-bucket miss mid-
        # stream stalls every fused tenant); count every one from the start
        ensure_recompile_listener()

    # --------------------------------------------------------- sessions

    def create_session(
        self, session_id: str | None = None, config: SessionConfig | None = None
    ) -> str:
        """Admit a tenant (raises ``AdmissionError`` at capacity)."""
        if session_id is None:
            session_id = f"session-{next(self._auto_ids)}"
        self.scheduler.admit(session_id, config or SessionConfig())
        return session_id

    def close_session(self, session_id: str) -> MiningSession:
        """Drain the session's remaining windows, then remove it."""
        s = self.scheduler.session(session_id)
        while s.queue_depth:
            self.scheduler.step()
        return self.scheduler.evict(session_id)

    def session(self, session_id: str) -> MiningSession:
        return self.scheduler.session(session_id)

    # ------------------------------------------------------ ingest/poll

    def ingest(self, session_id: str, window: EventStream, final: bool = False) -> int:
        """Queue one partition window (raises ``BackpressureError`` when
        the tenant's queue is full — shed or spool upstream). Returns the
        window's session-local index, the ``window_idx`` of its delta."""
        with span("service.ingest", session=session_id):
            return self.scheduler.submit(session_id, window, final=final)

    def pump(self, max_steps: int | None = None) -> int:
        """Run batched scheduler steps until queues drain (or the step
        budget runs out). Returns steps run."""
        return self.scheduler.drain(max_steps=10_000 if max_steps is None else max_steps)

    def poll(self, session_id: str, max_items: int | None = None) -> list[WindowDelta]:
        """Per-window frequent-episode deltas mined since the last poll."""
        return self.scheduler.session(session_id).poll(max_items)

    # ------------------------------------------------------- durability

    def checkpoint_all(self, root, extra=None) -> dict:
        """Checkpoint every session's full state atomically to
        ``root/<session_id>/`` — after quiescing the pipeline.

        Ordering matters: with ``pipeline_depth > 1`` the scheduler may
        hold prepared-but-uncommitted next-step windows that live in
        neither a session's pending queue nor its miner state. They are
        unstaged *first* (``scheduler.quiesce``) so every checkpoint
        captures them as pending work — a restart replays each window
        exactly once, never zero times (lost) and never twice
        (double-counted). ``extra(session_id)`` may contribute
        transport-layer leaves (the wire server's dedup sequence number)
        to the same atomic snapshot. Returns {session_id: path}."""
        self.scheduler.quiesce()
        paths = {}
        with span("service.checkpoint", sessions=len(self.scheduler.sessions)):
            for sid, s in self.scheduler.sessions.items():
                paths[sid] = s.save(root, extra=None if extra is None else extra(sid))
                REGISTRY.counter("service_checkpoints_total").inc()
        return paths

    # ------------------------------------------------------------ stats

    def stats(self) -> dict:
        """Full service health snapshot.

        Per-session sustained events/sec + latency percentiles and the
        cross-session aggregate (the exact meter rows), plus the registry-
        backed operational counters: scheduler queue/heartbeat gauges,
        backpressure/shed/retry counts, batcher fusion and pad-waste
        counters, and the kernel plane's dispatch/fallback/recompile
        tallies. ``metrics`` is the full flat registry snapshot the
        structured fields are drawn from — one set of numbers, whether
        read here, from ``KERNEL_CALLS``, or from ``--metrics-out``."""
        from repro.kernels.tally import KERNEL_CALLS, fallback_counts

        bank = MeterBank()
        for sid, s in self.scheduler.sessions.items():
            bank.meters[sid] = s.meter
        out = bank.summary()
        out["scheduler"] = {
            "steps": self.scheduler.steps,
            "retries": self.scheduler.watchdog.retries,
            "watchdog_retries": int(
                REGISTRY.counter("scheduler_watchdog_retries_total").value
            ),
            "sessions": len(self.scheduler.sessions),
            "pending_windows": self.scheduler.pending_windows,
            "queue_depth": int(REGISTRY.gauge("scheduler_queue_depth").value),
            "heartbeat_ts": float(REGISTRY.gauge("scheduler_heartbeat_ts").value),
            "backpressure": int(REGISTRY.counter("scheduler_backpressure_total").value),
            "admission_rejected": int(
                REGISTRY.counter("scheduler_admission_rejected_total").value
            ),
            "pipeline_overlap_s": self.scheduler.pipeline_overlap_s,
        }
        if self.batcher is not None:
            out["batcher"] = {
                "batches": self.batcher.batches,
                "fused_requests": self.batcher.fused_requests,
                "pad_events": self.batcher.pad_events,
                "pad_lanes": self.batcher.pad_lanes,
                "split_groups": int(REGISTRY.counter("batcher_split_groups_total").value),
                "flush_groups": self.batcher.flush_groups,
                "deadline_flushes": self.batcher.deadline_flushes,
                "fusion_gate": dict(self.batcher.gate_decisions),
            }
        # dispatch-policy health: table provenance + per-engine decision
        # counts (dispatch_policy_total{engine=...,source=...})
        out["calibration"] = calibrate.policy_stats()
        out["wire"] = {
            "connections": int(REGISTRY.gauge("wire_connections").value),
            "connections_total": int(REGISTRY.counter("wire_connections_total").value),
            "frames_rx": int(REGISTRY.counter("wire_frames_total", dir="rx").value),
            "frames_tx": int(REGISTRY.counter("wire_frames_total", dir="tx").value),
            "bytes_rx": int(REGISTRY.counter("wire_bytes_total", dir="rx").value),
            "bytes_tx": int(REGISTRY.counter("wire_bytes_total", dir="tx").value),
            "backpressure": int(REGISTRY.counter("wire_backpressure_total").value),
            "dedup_hits": int(REGISTRY.counter("wire_dedup_hits_total").value),
            "out_of_order": int(REGISTRY.counter("wire_out_of_order_total").value),
            "errors": {
                labels.get("code", "?"): int(m.value)
                for labels, m in REGISTRY.family_items("wire_errors_total")
            },
        }
        out["recovery"] = {
            "cold_boots": int(REGISTRY.counter("recovery_boots_total").value),
            "sessions_restored": int(REGISTRY.counter("recovery_sessions_total").value),
            "windows_requeued": int(
                REGISTRY.counter("recovery_windows_requeued_total").value
            ),
            "checkpoints": int(REGISTRY.counter("service_checkpoints_total").value),
            "checkpoint_bytes": int(REGISTRY.counter("checkpoint_bytes_total").value),
            "quiesced_preps": int(
                REGISTRY.counter("scheduler_quiesced_preps_total").value
            ),
        }
        out["streaming"] = {
            "recount_episodes": int(
                REGISTRY.counter("stream_recount_episodes_total").value
            ),
            "recount_carried": int(
                REGISTRY.counter("stream_recount_carried_total").value
            ),
            "recount_events": int(
                REGISTRY.counter("stream_recount_events_total").value
            ),
        }
        out["daemon"] = {
            "heartbeat_ts": float(REGISTRY.gauge("daemon_heartbeat_ts").value),
            "uptime_s": float(REGISTRY.gauge("daemon_uptime_s").value),
        }
        out["kernel"] = {
            "calls": {
                k: v
                for k, v in sorted(KERNEL_CALLS.items())
                if not k.startswith("fallback:")
            },
            "fallbacks": fallback_counts(),
            "recompiles": {
                labels.get("kernel", "?"): m.value
                for labels, m in REGISTRY.family_items("recompiles")
            },
        }
        out["metrics"] = REGISTRY.snapshot()
        return out
