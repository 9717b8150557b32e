"""Vectorized A2 counting (Algorithm 3 / Observation 5.1).

With lower bounds relaxed, each episode level needs exactly ONE timestamp of
state (Obs. 5.1), so counting M episodes is a dense ``lax.scan`` over events
with an int32[M, N] state matrix — the paper's "per-thread per-episode"
(PTPE) mapping becomes per-*lane* per-episode on the TPU VPU.

The step function is shared with MapConcatenate (``mapconcat.py``) and the
Pallas kernel oracle (``kernels/ref.py``). It also accepts lower bounds so
the same code path expresses the *single-slot approximation* of A1 (used only
in tests to show why A1 needs lists — the paper's motivation for Obs. 5.1).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tally import KernelDeclined, record_fallback

from .episodes import EpisodeBatch
from .events import TIME_NEG_INF, EventStream, count_level1


def step_single_slot(s, count, etypes, tlo, thi, e, t):
    """One event against M single-slot state machines.

    Args:
      s:      i32[M, N] last-accepted timestamp per level (TIME_NEG_INF = none)
      count:  i32[M]
      etypes: i32[M, N]; tlo/thi: i32[M, N-1]
      e, t:   scalar i32 event type / time (e == PAD_TYPE is a no-op)

    Returns (s', count'). All reads see the pre-event state, which matches the
    sequential top-down level walk (see core/ref.py notes).
    """
    match = etypes == e  # [M, N]; PAD_TYPE never matches (etypes >= 0)
    delta = t - s[:, :-1]  # [M, N-1]
    ok = (delta > tlo) & (delta <= thi)  # [M, N-1]
    # level 0 always records; level i>0 records iff level i-1 witnesses
    advance = jnp.concatenate(
        [jnp.ones_like(match[:, :1]), ok], axis=1) & match  # [M, N]
    complete = advance[:, -1]  # [M]
    # the last level never stores (completion resets instead)
    store = advance.at[:, -1].set(False)
    s_new = jnp.where(store, t, s)
    s_new = jnp.where(complete[:, None], TIME_NEG_INF, s_new)
    return s_new, count + complete.astype(count.dtype)


@dataclasses.dataclass
class A2State:
    """Carry of the M single-slot machines between stream chunks.

    Unlike A1's bounded lists, a single slot per level is *complete* state
    (Obs. 5.1) — carrying it across any chunk boundary is unconditionally
    bit-exact, ties included. After a carried call the passed state may have
    been donated; never reuse it.
    """

    s: jax.Array      # i32[M, N] last-accepted timestamp per level
    count: jax.Array  # i32[M]


def init_a2_state(eps: EpisodeBatch) -> A2State:
    return A2State(
        s=jnp.full(eps.etypes.shape, TIME_NEG_INF, dtype=jnp.int32),
        count=jnp.zeros((eps.M,), dtype=jnp.int32))


def _a2_scan_core(etypes, tlo, thi, ev_types, ev_times, s, c):
    def body(carry, ev):
        s_, c_ = carry
        e, t = ev
        return step_single_slot(s_, c_, etypes, tlo, thi, e, t), None

    carry, _ = jax.lax.scan(body, (s, c), (ev_types, ev_times))
    return carry


@functools.lru_cache(maxsize=None)
def _a2_carry_scan():
    donate = (5, 6) if jax.default_backend() != "cpu" else ()
    return jax.jit(_a2_scan_core, donate_argnums=donate)


@functools.partial(jax.jit, static_argnames=())
def _scan_count(etypes, tlo, thi, ev_types, ev_times):
    m, _ = etypes.shape
    s0 = jnp.full(etypes.shape, TIME_NEG_INF, dtype=jnp.int32)
    c0 = jnp.zeros((m,), dtype=jnp.int32)
    _, count = _a2_scan_core(etypes, tlo, thi, ev_types, ev_times, s0, c0)
    return count


def count_single_slot(stream: EventStream, eps: EpisodeBatch,
                      inclusive_lower: bool = False,
                      state: A2State | None = None,
                      return_state: bool = False,
                      use_kernel: bool = False):
    """Single-slot scan with eps' own bounds (A2 ⇔ bounds already relaxed).

    ``inclusive_lower`` applies Δ ∈ [tlo.., thi] by shifting the exclusive
    integer bound down one tick — see ref.count_a2_sequential for why A2
    needs this on streams with repeated timestamps.

    With ``state``/``return_state`` the machines resume carried state and
    also return the new ``A2State``; cumulative counts over chunks are
    bit-identical to one scan over the concatenation. ``use_kernel`` routes
    the carried chunk through the state-in/state-out Pallas kernel
    (``kernels.ops.a2_count_stateful``) when the dispatch policy allows —
    same bits, on-chip state."""
    if eps.N == 1:
        counts = count_level1(stream, eps.etypes[:, 0])
        if state is not None:
            counts = counts + np.asarray(state.count, np.int64)
        if return_state:
            st = state if state is not None else init_a2_state(eps)
            st = dataclasses.replace(st,
                                     count=jnp.asarray(counts, jnp.int32))
            return counts, st
        return counts
    tlo = jnp.asarray(eps.tlo) - (1 if inclusive_lower else 0)
    if state is None and not return_state:
        count = _scan_count(jnp.asarray(eps.etypes), tlo,
                            jnp.asarray(eps.thi), jnp.asarray(stream.types),
                            jnp.asarray(stream.times))
        return np.asarray(count, dtype=np.int64)
    if use_kernel:
        try:
            from repro.kernels import ops as kops
            counts, new_state = kops.a2_count_stateful(
                stream, eps, state=state, inclusive_lower=inclusive_lower)
            if return_state:
                return counts, new_state
            return counts
        except KernelDeclined:
            record_fallback("a2_stateful")
    st = state if state is not None else init_a2_state(eps)
    s, count = _a2_carry_scan()(
        jnp.asarray(eps.etypes), tlo, jnp.asarray(eps.thi),
        jnp.asarray(stream.types), jnp.asarray(stream.times),
        st.s, st.count)
    new_state = A2State(s=s, count=count)
    counts = np.asarray(count, dtype=np.int64)
    if return_state:
        return counts, new_state
    return counts


def count_a2(stream: EventStream, eps: EpisodeBatch,
             use_kernel: bool = True, state: A2State | None = None,
             return_state: bool = False, segments: int | None = None,
             sharded: bool = False):
    """Paper Algorithm 3: upper-bound counts of the relaxed episodes α'.

    Dispatches to the Pallas kernel path when available (TPU target;
    interpret-mode on CPU is slower than the XLA scan, so default CPU path is
    the scan — see kernels/ops.py for the dispatch policy). Stateful calls
    (``state``/``return_state``) return ``(counts, A2State)`` with
    cumulative counts over everything the carried machines have seen, and
    with ``use_kernel`` run the chunk through the state-in/state-out Pallas
    kernel — the carried single-slot tile stays on-chip.

    ``segments`` routes the one-shot count through the segment-parallel
    kernel (``kernels.ops.a2_mapconcat_count`` — grid = episode tile × time
    segment with the Concatenate fold fused on-chip); with ``sharded`` the
    segment axis additionally shards over the mesh ``data`` devices — one
    segmented launch per device, per-device tuples all-gathered and folded
    replicated (``a2_mapconcat_sharded_count``; single-device hosts take
    the plain segmented launch). Episodes whose tuples fail to stitch are
    recounted by the exact single-slot scan, so the result is *the* A2
    count either way and Theorem 5.1's cull stays sound. Ignored in
    stateful mode (cross-chunk carry is a single sequential scan) and when
    the kernel dispatch declines.
    """
    relaxed = eps.relaxed()
    if state is not None or return_state:
        return count_single_slot(stream, relaxed, inclusive_lower=True,
                                 state=state, return_state=True,
                                 use_kernel=use_kernel)
    if use_kernel and segments is not None and eps.N > 1:
        try:
            from repro.kernels import ops as kops
            if sharded:
                counts, bad = kops.a2_mapconcat_sharded_count(
                    stream, relaxed, num_segments=segments)
            else:
                counts, bad = kops.a2_mapconcat_count(stream, relaxed,
                                                      num_segments=segments)
            if bad.any():
                idx = np.nonzero(bad)[0]
                counts = counts.copy()
                counts[idx] = count_single_slot(stream, relaxed.select(idx),
                                                inclusive_lower=True)
            return counts
        except KernelDeclined:
            record_fallback("a2_segments")
    if use_kernel:
        try:
            from repro.kernels import ops as kops
            return kops.a2_count(stream, relaxed)
        except KernelDeclined:
            record_fallback("a2_count")
    return count_single_slot(stream, relaxed, inclusive_lower=True)
