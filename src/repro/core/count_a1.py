"""Vectorized A1 counting (Algorithm 1) with bounded per-level lists.

Algorithm 1's state is a list of recent timestamps per episode level; the
list walk is data-dependent control flow — the exact thing the paper pays for
on the GPU in registers/local-memory/divergence (§5.3, Fig. 10) and that a
TPU pays for in un-vectorizable gathers. We bound each list to ``LCAP`` slots
kept in a circular buffer, turning the walk into a masked reduction over a
dense i32[M, N, LCAP] tile.

Correctness containment: bounding can only *undercount* (a live witness may
be evicted while newer entries fail the lower bound). We detect possibly-live
evictions exactly — an evicted level-i entry ``v`` is dead iff
``t - v > thi[i]`` (its only consumer is level i+1 within ``thi[i]``) — and
flag the episode. Flagged episodes are recounted by the sequential oracle
(``ref.count_a1_sequential``), so the public ``count_a1`` is always exact.
Tests sweep LCAP and assert the flag ⇒ recount path restores oracle equality.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tally import KernelDeclined, record_fallback

from . import ref
from .episodes import EpisodeBatch
from .events import TIME_NEG_INF, EventStream, count_level1

DEFAULT_LCAP = 4


def step_bounded_list(s, ptr, count, ovf, etypes, tlo, thi, e, t,
                      dup=False):
    """One event against M bounded-list (A1) state machines.

    Args:
      s:    i32[M, N, L] circular timestamp buffers (TIME_NEG_INF = empty)
      ptr:  i32[M, N] next write slot per level
      count: i32[M]; ovf: bool[M] possibly-live-eviction flag
      etypes i32[M, N]; tlo/thi i32[M, N-1]; e/t scalar i32
      dup:  scalar bool — a later event shares this timestamp. Needed for
            exact eviction accounting: a fresh entry at time t covers an
            evicted one for consumers at t' > t, but not at t' == t
            (the (0, thi] lower bound is strict).

    Returns (s', ptr', count', ovf').
    """
    m, n, cap = s.shape
    match = etypes == e  # [M, N]
    delta = t - s[:, :-1, :]  # [M, N-1, L]
    witness = (delta > tlo[:, :, None]) & (delta <= thi[:, :, None])
    ok = witness.any(axis=-1)  # [M, N-1]
    advance = jnp.concatenate(
        [jnp.ones_like(match[:, :1]), ok], axis=1) & match  # [M, N]
    complete = advance[:, -1]  # [M]
    store = advance.at[:, -1].set(False)  # last level never stores
    store = store & ~complete[:, None]  # completion short-circuits the walk

    # circular append at ptr where store
    onehot = jax.nn.one_hot(ptr, cap, dtype=jnp.bool_)  # [M, N, L]
    write = store[:, :, None] & onehot
    # live-eviction detection: evicted value v still matters iff t-v <= thi[i]
    # (level N-1 has no outgoing edge; it never stores anyway)
    evicted = jnp.where(write, s, TIME_NEG_INF)  # [M, N, L]
    v = evicted.max(axis=-1)  # [M, N] value being overwritten (or NEG_INF)
    thi_out = jnp.concatenate(  # outgoing-edge upper bound per level
        [thi, jnp.zeros_like(thi[:, :1])], axis=1)  # [M, N]
    tlo_out = jnp.concatenate(
        [tlo, jnp.zeros_like(tlo[:, :1])], axis=1)  # [M, N]
    # Obs 5.1: with a zero lower bound the newest entry dominates — eviction
    # is provably safe for strictly-later consumers; only a real lower bound
    # (or a same-timestamp successor event) can make an old witness live.
    live = (v > TIME_NEG_INF) & (t - v <= thi_out) & ((tlo_out > 0) | dup)
    ovf_new = ovf | live.any(axis=-1)

    s_new = jnp.where(write, t, s)
    ptr_new = jnp.where(store, (ptr + 1) % cap, ptr)
    # completion: full reset
    s_new = jnp.where(complete[:, None, None], TIME_NEG_INF, s_new)
    ptr_new = jnp.where(complete[:, None], 0, ptr_new)
    return s_new, ptr_new, count + complete.astype(count.dtype), ovf_new


def dup_flags(ev_types, ev_times):
    """bool[n]: a later *real* event shares this event's timestamp.
    (Events are time-sorted, so it suffices to look at the successor.)"""
    from .events import PAD_TYPE
    nxt_same = jnp.concatenate(
        [(ev_times[1:] == ev_times[:-1]) & (ev_types[1:] != PAD_TYPE),
         jnp.zeros((1,), jnp.bool_)])
    return nxt_same


@dataclasses.dataclass
class A1State:
    """Carry of the M bounded-list machines between stream chunks.

    Device arrays; thread the state returned by one chunk's scan into the
    next chunk's call — after a carried call the *passed* state may have been
    donated (its buffers reused), so never touch it again. ``ovf`` is sticky:
    once an episode's bounded lists may have evicted a live witness, every
    later count for it must be restored by an oracle recount over the full
    concatenated history (``StreamingCounter`` does this automatically).
    """

    s: jax.Array      # i32[M, N, L] circular timestamp buffers
    ptr: jax.Array    # i32[M, N] next write slot
    count: jax.Array  # i32[M] completions so far
    ovf: jax.Array    # bool[M] possibly-live-eviction flag (sticky)


def init_a1_state(eps: EpisodeBatch, lcap: int = DEFAULT_LCAP) -> A1State:
    """Fresh (empty-list) machines for ``eps``."""
    return A1State(
        s=jnp.full((eps.M, eps.N, lcap), TIME_NEG_INF, dtype=jnp.int32),
        ptr=jnp.zeros((eps.M, eps.N), dtype=jnp.int32),
        count=jnp.zeros((eps.M,), dtype=jnp.int32),
        ovf=jnp.zeros((eps.M,), dtype=jnp.bool_))


def _a1_scan_core(etypes, tlo, thi, ev_types, ev_times, s, ptr, c, ovf):
    dups = dup_flags(ev_types, ev_times)

    def body(carry, ev):
        s_, ptr_, c_, ovf_ = carry
        e, t, d = ev
        return step_bounded_list(s_, ptr_, c_, ovf_, etypes, tlo, thi, e, t,
                                 d), None

    carry, _ = jax.lax.scan(body, (s, ptr, c, ovf),
                            (ev_types, ev_times, dups))
    return carry


@functools.lru_cache(maxsize=None)
def _a1_carry_scan():
    """jit'd carried scan; donates the state buffers so a long-running
    stream reuses one persistent allocation per shape bucket (donation is a
    no-op warning on backends that don't support it, e.g. CPU)."""
    donate = (5, 6, 7, 8) if jax.default_backend() != "cpu" else ()
    return jax.jit(_a1_scan_core, donate_argnums=donate)


@jax.jit
def _scan_count_a1(etypes, tlo, thi, ev_types, ev_times, s0):
    m, n = etypes.shape
    ptr0 = jnp.zeros((m, n), dtype=jnp.int32)
    c0 = jnp.zeros((m,), dtype=jnp.int32)
    ovf0 = jnp.zeros((m,), dtype=jnp.bool_)
    _, _, count, ovf = _a1_scan_core(etypes, tlo, thi, ev_types, ev_times,
                                     s0, ptr0, c0, ovf0)
    return count, ovf


def count_a1_vectorized(stream: EventStream, eps: EpisodeBatch,
                        lcap: int = DEFAULT_LCAP, state: A1State | None = None,
                        return_state: bool = False):
    """Bounded-list scan. Returns (count i64[M], overflow bool[M]) — plus the
    carried ``A1State`` when ``return_state`` is set.

    With ``state`` the machines resume where the previous chunk left them
    instead of rebuilding per call; chunked counting is then bit-identical to
    one call on the concatenation **provided chunk boundaries never split a
    group of equal timestamps** (the successor-duplicate flags feeding the
    eviction accounting are computed per chunk). ``StreamingCounter`` holds
    back the trailing tie group to guarantee that invariant.
    """
    if eps.N == 1:
        counts = count_level1(stream, eps.etypes[:, 0])
        if state is not None:
            counts = counts + np.asarray(state.count, np.int64)
        if return_state:
            # 1-node machines never store timestamps; only the count moves
            st = state if state is not None else init_a1_state(eps, lcap)
            st = dataclasses.replace(st,
                                     count=jnp.asarray(counts, jnp.int32))
            return counts, np.zeros(eps.M, dtype=bool), st
        return counts, np.zeros(eps.M, dtype=bool)
    if state is None:
        state = init_a1_state(eps, lcap)
    s, ptr, c, ovf = _a1_carry_scan()(
        jnp.asarray(eps.etypes), jnp.asarray(eps.tlo), jnp.asarray(eps.thi),
        jnp.asarray(stream.types), jnp.asarray(stream.times),
        state.s, state.ptr, state.count, state.ovf)
    new_state = A1State(s=s, ptr=ptr, count=c, ovf=ovf)
    counts = np.asarray(c, np.int64)
    ovf_np = np.asarray(ovf)
    if return_state:
        return counts, ovf_np, new_state
    return counts, ovf_np


def count_a1(stream: EventStream, eps: EpisodeBatch,
             lcap: int = DEFAULT_LCAP, use_kernel: bool = True,
             state: A1State | None = None, return_state: bool = False):
    """Exact Algorithm-1 counts: vectorized fast path + oracle fallback for
    episodes whose bounded lists may have evicted a live witness.

    Stateful mode (``state``/``return_state``): the machines resume from the
    carried state and return ``(counts, A1State)`` with *cumulative* counts
    over everything the state has seen. With ``use_kernel`` the chunk runs
    through the state-in/state-out Pallas kernel
    (``kernels.ops.a1_count_stateful``) when the dispatch policy allows,
    falling back to the carried XLA scan otherwise — bit-identical either
    way. The oracle fallback cannot run here — the caller sees only this
    chunk, so exactness for ``state.ovf``-flagged episodes must be restored
    by recounting the concatenated history (``StreamingCounter.counts``
    does).
    """
    if state is not None or return_state:
        if use_kernel and eps.N > 1:
            try:
                from repro.kernels import ops as kops
                counts, _, new_state = kops.a1_count_stateful(
                    stream, eps, state=state, lcap=lcap)
                return counts, new_state
            except KernelDeclined:
                record_fallback("a1_stateful")
        out = count_a1_vectorized(stream, eps, lcap=lcap, state=state,
                                  return_state=True)
        counts, _, new_state = out
        return counts, new_state
    if use_kernel:
        try:
            from repro.kernels import ops as kops
            counts, ovf = kops.a1_count(stream, eps, lcap=lcap)
        except KernelDeclined:
            record_fallback("a1_count")
            counts, ovf = count_a1_vectorized(stream, eps, lcap=lcap)
    else:
        counts, ovf = count_a1_vectorized(stream, eps, lcap=lcap)
    if ovf.any():
        idx = np.nonzero(ovf)[0]
        exact = ref.count_a1_sequential(stream, eps.select(idx))
        counts = counts.copy()
        counts[idx] = exact
    return counts
