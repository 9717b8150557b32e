"""Pass 2 — trace-time hot-path auditor (rules TR201–TR205).

Jit-traces the public counting entry points on small shapes and audits
what the compiler will actually run, catching hot-path regressions that
no unit test asserts on:

  TR201  host callback primitive (``pure_callback`` / ``io_callback`` /
         ``debug_callback``) inside a counting jaxpr — a device→host
         round-trip per call.
  TR202  non-integer (or 64-bit) dtype in a counting jaxpr.  The whole
         counting plane is i32/bool by contract; a float or x64 value
         means a weak-type promotion crept in (doubling VMEM traffic).
  TR203  host custom-call in the compiled HLO (the compiled-artifact
         twin of TR201, via ``launch/hlo_analysis``).
  TR204  carried-scan jit factory without buffer donation — a
         long-running stream then reallocates its machine state every
         chunk on accelerator backends.
  TR205  jit cache misses over a scripted multi-window streaming session
         exceed the per-entry-point budget.  Shape-bucketing exists so
         streaming compiles each entry point once or twice (one steady
         bucket + one flush shape); compile churn is a real latency tax
         the service bench cannot attribute.

Unlike Pass 1/3 this pass imports jax and the engines; run it under
``REPRO_KERNEL_INTERPRET=1`` on CPU hosts so the kernel residency paths
are traced too.
"""

from __future__ import annotations

import inspect
import logging

import numpy as np

from repro.obs.jaxprof import _COMPILE_RE

from .findings import Finding

# dtypes the counting plane may touch (TR202)
_ALLOWED_DTYPES = {"int32", "bool"}

# compile-log names whose recompiles are budgeted (TR205); anything else
# (one-off helpers like convert_element_type) compiles per shape by design
MONITORED_COMPILES = (
    "_a1_scan_core", "_a2_scan_core", "_map_all_segments",
    "a1_count_state_kernel", "a2_count_state_kernel",
    "a1_mapconcat_kernel", "a2_mapconcat_kernel",
)
COMPILE_BUDGET = 2  # per monitored entry point per session



# ---------------------------------------------------------------- jaxpr


def _sub_jaxprs(params: dict):
    import jax.extend.core as jex_core
    kinds = (jex_core.Jaxpr, jex_core.ClosedJaxpr)
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for x in vs:
            if isinstance(x, kinds):
                yield x.jaxpr if isinstance(x, jex_core.ClosedJaxpr) else x


def iter_eqns(jaxpr):
    """All equations of ``jaxpr`` including nested sub-jaxprs (scan/cond
    bodies, pjit calls, pallas kernels)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def audit_jaxpr(name: str, jaxpr) -> list[Finding]:
    """TR201 (callbacks) + TR202 (dtype discipline) over one jaxpr."""
    findings = []
    seen_dtypes = set()
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        if hasattr(v.aval, "dtype"):
            seen_dtypes.add(str(v.aval.dtype))
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if "callback" in prim or "outside_call" in prim:
            findings.append(Finding(
                "TR201", name, 0,
                f"host callback primitive '{prim}' on the hot path"))
        for v in eqn.outvars:
            if hasattr(v.aval, "dtype"):
                seen_dtypes.add(str(v.aval.dtype))
    bad = sorted(d for d in seen_dtypes if d not in _ALLOWED_DTYPES)
    if bad:
        findings.append(Finding(
            "TR202", name, 0,
            f"non-i32 dtypes {bad} in counting jaxpr — weak-type or x64 "
            "promotion on the hot path"))
    return findings


# ------------------------------------------------------ entry registry


def _small_inputs(m=2, n=3, lcap=4, e=8):
    """Tiny episode/stream/state arrays shared by the traced entries."""
    import jax.numpy as jnp
    i32 = jnp.int32
    et = jnp.zeros((m, n), i32)
    tlo = jnp.zeros((m, n - 1), i32)
    thi = jnp.full((m, n - 1), 5, i32)
    ev_t = jnp.zeros((e,), i32)
    ev_tt = jnp.arange(e, dtype=i32)
    return et, tlo, thi, ev_t, ev_tt, m, n, lcap, e


def entry_points():
    """name -> zero-arg thunk returning a ClosedJaxpr of that entry
    traced on small shapes (the per-engine seams of ``count_dispatch``,
    plus the cross-session batcher's vmapped twins)."""
    import jax
    import jax.numpy as jnp
    from repro.core.count_a1 import _a1_scan_core
    from repro.core.count_a2 import _a2_scan_core
    from repro.core.events import TIME_NEG_INF
    from repro.core.mapconcat import _map_all_segments
    from repro.service.batcher import (_vmapped_a1, _vmapped_a2,
                                       _vmapped_mapc)

    i32 = jnp.int32
    et, tlo, thi, ev_t, ev_tt, m, n, lcap, e = _small_inputs()
    s1 = jnp.full((m, n, lcap), TIME_NEG_INF, i32)
    ptr = jnp.zeros((m, n), i32)
    c = jnp.zeros((m,), i32)
    ovf = jnp.zeros((m,), jnp.bool_)
    s2 = jnp.full((m, n), TIME_NEG_INF, i32)
    q = 2  # segments
    wt = jnp.zeros((q, e), i32)
    wtt = jnp.broadcast_to(ev_tt, (q, e))
    tau = jnp.array([0, e // 2, e], i32)
    w = jnp.full((m,), 10, i32)  # per-episode max occurrence span

    a1_args = (et, tlo, thi, ev_t, ev_tt, s1, ptr, c, ovf)
    a2_args = (et, tlo, thi, ev_t, ev_tt, s2, c)
    mapc_args = (wt, wtt, et, tlo, thi, tau, w)
    lane = lambda x: x[None]  # noqa: E731 — one-lane batcher axis

    return {
        "count_a1._a1_scan_core":
            lambda: jax.make_jaxpr(_a1_scan_core)(*a1_args),
        "count_a2._a2_scan_core":
            lambda: jax.make_jaxpr(_a2_scan_core)(*a2_args),
        "mapconcat._map_all_segments":
            lambda: jax.make_jaxpr(
                lambda *a: _map_all_segments(*a, lcap))(*mapc_args),
        "batcher._vmapped_a1":
            lambda: jax.make_jaxpr(_vmapped_a1())(
                *[lane(x) for x in a1_args]),
        "batcher._vmapped_a2":
            lambda: jax.make_jaxpr(_vmapped_a2())(
                *[lane(x) for x in a2_args]),
        "batcher._vmapped_mapc":
            lambda: jax.make_jaxpr(_vmapped_mapc(lcap))(
                *[lane(x) for x in mapc_args]),
    }


def audit_entry_points() -> tuple[list[Finding], dict]:
    """TR201/TR202 over every registered entry point."""
    findings = []
    traced = []
    for name, thunk in entry_points().items():
        findings.extend(audit_jaxpr(name, thunk().jaxpr))
        traced.append(name)
    return findings, {"entry_points_traced": traced}


# ------------------------------------------------------------- TR203/4


def audit_hlo() -> tuple[list[Finding], dict]:
    """Compile the PTPE cores and audit the HLO artifact (TR203), with
    traffic totals from ``launch.hlo_analysis`` in the summary."""
    import jax
    from repro.core.count_a1 import _a1_scan_core
    from repro.core.count_a2 import _a2_scan_core
    from repro.core.events import TIME_NEG_INF
    from repro.launch.hlo_analysis import analyze
    import jax.numpy as jnp

    et, tlo, thi, ev_t, ev_tt, m, n, lcap, e = _small_inputs()
    s1 = jnp.full((m, n, lcap), TIME_NEG_INF, jnp.int32)
    cases = {
        "count_a1._a1_scan_core": (_a1_scan_core, (
            et, tlo, thi, ev_t, ev_tt, s1,
            jnp.zeros((m, n), jnp.int32), jnp.zeros((m,), jnp.int32),
            jnp.zeros((m,), jnp.bool_))),
        "count_a2._a2_scan_core": (_a2_scan_core, (
            et, tlo, thi, ev_t, ev_tt,
            jnp.full((m, n), TIME_NEG_INF, jnp.int32),
            jnp.zeros((m,), jnp.int32))),
    }
    findings, traffic = [], {}
    for name, (fn, args) in cases.items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        if "custom-call" in text and "callback" in text:
            findings.append(Finding(
                "TR203", name, 0,
                "host-callback custom-call in compiled HLO"))
        traffic[name] = dict(analyze(text).__dict__)
    return findings, {"hlo_traffic": traffic}


def audit_donation() -> tuple[list[Finding], dict]:
    """TR204 — the carried-scan factories must configure buffer donation
    (checked on source: the runtime disables it on CPU by design, so the
    jit object itself cannot be inspected portably)."""
    from repro.core.count_a1 import _a1_carry_scan
    from repro.core.count_a2 import _a2_carry_scan
    findings = []
    for fac in (_a1_carry_scan, _a2_carry_scan):
        src = inspect.getsource(fac)
        if "donate_argnums" not in src:
            findings.append(Finding(
                "TR204", f"{fac.__module__}.{fac.__name__}", 0,
                "carried-scan factory without donate_argnums — machine "
                "state reallocates every chunk on accelerators"))
    return findings, {}


# --------------------------------------------------- recompile sentinel


class _CompileLog(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.names: list[str] = []

    def emit(self, record):
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            self.names.append(m.group(1))


def recompile_sentinel(n_windows: int = 10,
                       budget: int = COMPILE_BUDGET):
    """TR205 — run a scripted ``n_windows``-window streaming session per
    engine and fail any monitored entry point compiling more than
    ``budget`` times.  Shape buckets make steady-state windows hit the
    jit cache; a miss per window is the regression this guards."""
    import jax
    from repro.core.episodes import EpisodeBatch
    from repro.core.events import EventStream
    from repro.core.streaming import StreamingCounter

    eps = EpisodeBatch(
        etypes=np.array([[0, 1, 2], [1, 2, 3]], np.int32),
        tlo=np.zeros((2, 2), np.int32),
        thi=np.full((2, 2), 8, np.int32))
    rng = np.random.default_rng(7)

    def windows():
        t0 = 0
        for _ in range(n_windows):
            k = int(rng.integers(40, 90))  # varied sizes, same bucket
            tt = np.sort(rng.integers(t0, t0 + 500, k)).astype(np.int32)
            ty = rng.integers(0, 4, k).astype(np.int32)
            t0 += 500
            yield EventStream(types=ty, times=tt, num_types=4)

    handler = _CompileLog()
    loggers = [logging.getLogger("jax._src.interpreters.pxla"),
               logging.getLogger("jax._src.dispatch")]
    saved = [(lg, lg.level, lg.propagate) for lg in loggers]
    jax.config.update("jax_log_compiles", True)
    for lg in loggers:
        lg.addHandler(handler)
        lg.setLevel(logging.WARNING)
        lg.propagate = False  # capture, don't spill onto the console
    try:
        for engine in ("ptpe", "mapconcatenate"):
            sc = StreamingCounter(eps, engine=engine)
            for win in windows():
                sc.update(win)
            sc.finalize()
    finally:
        jax.config.update("jax_log_compiles", False)
        for lg, lvl, prop in saved:
            lg.removeHandler(handler)
            lg.setLevel(lvl)
            lg.propagate = prop

    counts: dict[str, int] = {}
    for name in handler.names:
        for mon in MONITORED_COMPILES:
            if mon in name:
                counts[mon] = counts.get(mon, 0) + 1
    findings = [
        Finding("TR205", mon, 0,
                f"{c} jit compiles across a {n_windows}-window streaming "
                f"session (budget {budget}) — shape bucketing is not "
                "holding")
        for mon, c in sorted(counts.items()) if c > budget]
    summary = {"recompiles": counts,
               "recompile_budget": budget,
               "compile_events_total": len(handler.names)}
    return findings, summary


def run(sentinel: bool = True):
    """All of Pass 2. Returns (findings, summary)."""
    findings, summary = audit_entry_points()
    for fn in (audit_hlo, audit_donation) + \
            ((recompile_sentinel,) if sentinel else ()):
        f, s = fn()
        findings.extend(f)
        summary.update(s)
    return findings, summary
