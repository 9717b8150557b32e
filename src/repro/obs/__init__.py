"""Unified observability plane for the mining service.

Three coordinated layers, all cheap enough to be on by default:

* ``registry`` — a process-global labeled metrics registry
  (:class:`~repro.obs.registry.Counter` /
  :class:`~repro.obs.registry.Gauge` /
  :class:`~repro.obs.registry.Histogram` families with ``snapshot()`` and
  ``delta()``). Every pre-existing telemetry fragment now feeds it: the
  ``kernels.tally.KERNEL_CALLS`` dispatch tally and its
  ``fallback:<site>`` kinds, the scheduler's queue-depth / backpressure /
  shed / watchdog-retry accounting, the batcher's fusion and pad-waste
  counters, and the per-session ``telemetry.ThroughputMeter`` rows. The
  old views (``dict(KERNEL_CALLS)``, ``MeterBank.summary()``) remain as
  thin facades over the same numbers.

* ``trace`` — nested host-side spans threaded through the full window
  lifecycle (``wire.ingest -> schedule.step -> session.mine_window ->
  mine.candidates / stream.counter_init / stream.replay / stream.launch /
  stream.readback / stream.recount -> stream.checkpoint ->
  service.checkpoint (ckpt.state, ckpt.write) -> wire.deliver``; the
  full tree is in ``trace``'s docstring). Spans land in a fixed-size
  ring buffer (O(1) per span, two clock reads, no device sync; evictions
  counted in ``TRACER.dropped``) and export as JSONL or Chrome
  trace-event JSON — load the latter straight into Perfetto /
  ``chrome://tracing``.

* ``jaxprof`` — device-side hooks: ``jax.profiler`` trace annotations
  around the instrumented kernel entry points, an always-on recompilation
  listener feeding a ``recompiles{kernel=...}`` counter, and an optional
  one-step ``jax.profiler`` capture (``mine_serve --profile-dir``).

Import cost discipline: ``registry`` and ``trace`` are pure stdlib (the
dependency-light ``kernels.tally`` imports them); ``jaxprof`` defers its
jax imports to call time.
"""

from . import jaxprof, registry, trace
from .registry import REGISTRY
from .trace import TRACER, span

__all__ = ["REGISTRY", "TRACER", "jaxprof", "registry", "span", "trace"]
