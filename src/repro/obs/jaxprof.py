"""Device-side observability hooks (lazy jax imports).

* ``annotate(name)`` — a ``jax.profiler.TraceAnnotation`` around an
  instrumented kernel entry point, so device timelines captured with
  ``capture_step`` (or any profiler session) carry the dispatch names the
  host spans use. Degrades to a no-op context manager when jax (or the
  profiler) is unavailable.

* ``ensure_recompile_listener()`` — the always-on generalization of the
  TR205 sentinel (``analysis.tracecheck``): a logging handler on jax's
  compile-log channels feeding ``REGISTRY`` counters
  ``recompiles{kernel=<name>}``. Idempotent and self-healing: the TR205
  sentinel's ``finally`` switches ``jax_log_compiles`` back off after an
  audit, so every call re-checks the config flag and re-enables it. The
  two compile-log loggers get ``propagate=False`` (capture, don't spill
  onto the console) — the same containment the sentinel applies
  temporarily, made permanent.

* ``capture_step(profile_dir)`` — one ``jax.profiler`` trace session
  around a block (``mine_serve --profile-dir`` wraps one scheduler step
  in it). Capture failures count into
  ``profiler_capture_errors`` instead of raising: profiling must never
  take down the serving loop.
"""

from __future__ import annotations

import contextlib
import logging
import re

from .registry import REGISTRY

# jax's compile-log line; JAX 0.9 wraps the name as ``jit(<name>)``, older
# releases logged it bare. The TR205 sentinel (``analysis.tracecheck``)
# parses the same lines with this regex.
_COMPILE_RE = re.compile(r"Compiling (?:jit\()?([\w.<>-]+)\)? with global shapes")
_LOGGER_NAMES = ("jax._src.interpreters.pxla", "jax._src.dispatch")

_HANDLER: _RecompileHandler | None = None


class _RecompileHandler(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.WARNING)

    def emit(self, record):
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            REGISTRY.counter("recompiles", kernel=m.group(1)).inc()


def ensure_recompile_listener() -> bool:
    """Install (or re-arm) the recompilation listener. Returns True when
    the listener is active. Safe to call per dispatch — after the first
    install it is one import plus one config-flag read."""
    global _HANDLER
    try:
        import jax
    except ImportError:
        return False
    if _HANDLER is None:
        _HANDLER = _RecompileHandler()
        for name in _LOGGER_NAMES:
            lg = logging.getLogger(name)
            lg.addHandler(_HANDLER)
            lg.setLevel(logging.WARNING)
            lg.propagate = False
    if not jax.config.jax_log_compiles:
        jax.config.update("jax_log_compiles", True)
    return True


def annotate(name: str):
    """Context manager naming the enclosed dispatch on the device
    timeline; also keeps the recompile listener armed (the entry points
    are the one place every engine passes through)."""
    ensure_recompile_listener()
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return contextlib.nullcontext()
    return TraceAnnotation(name)


@contextlib.contextmanager
def capture_step(profile_dir):
    """Capture one ``jax.profiler`` trace of the enclosed block into
    ``profile_dir`` (TensorBoard/Perfetto-readable). Never raises out of
    the capture machinery itself."""
    started = False
    try:
        import jax
        jax.profiler.start_trace(str(profile_dir))
        started = True
    except Exception:
        REGISTRY.counter("profiler_capture_errors").inc()
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                REGISTRY.counter("profiler_capture_errors").inc()
