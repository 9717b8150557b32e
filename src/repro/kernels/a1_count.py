"""Pallas TPU kernel: A1 (bounded-list) episode counting.

Same computation-to-core mapping as ``a2_count`` (episodes on lanes, levels
on sublanes, events chunked on an ``arbitrary`` second grid axis with the
machine state carried in the revisited output blocks) plus a bounded witness
list per level: state is an (NP, LCAP, BM) timestamp brick with a one-hot
(NP, LCAP, BM) write-pointer mask. Inside the kernel the brick is held as
LCAP (NP, BM) slot rows and the mask as a slot index, so the paper's
data-dependent list walk becomes an OR/select over the slot rows and the
circular append a pointer increment — no gathers, no scatters, pure VPU
ops on whole vregs (this is the TPU answer to the divergence/local-memory
costs the paper profiles in Fig. 10).

Outputs: counts AND a live-eviction flag per episode (see
core/count_a1.py — flagged episodes are recounted exactly by the host).

Event stream layout: i32[3, EP] = (types; times; dup) where dup marks a
same-timestamp real successor (needed for exact eviction accounting).

State-in/state-out variant (``a1_count_state_kernel``): the ``fori_loop``
carry — the (NP, LCAP, BM) timestamp brick, the one-hot write-pointer
mask (i32 0/1), and the count/ovf rows — becomes kernel I/O, with
``input_output_aliases`` donating each state input to its output so a
long-running stream mutates one persistent on-chip allocation per shape
bucket. Chunked carried calls are bit-identical to one call on the
concatenation provided chunk boundaries never split a tie group (the dup
row is computed per chunk; ``core.streaming.StreamingCounter`` holds back
the trailing tie group to guarantee that). Layout contract (pack/unpack
between this brick layout and ``core.count_a1.A1State``'s episode-major
[M, N, L] arrays) lives in ``ops.a1_state_layout`` / ``a1_state_unpack``.

Segment-parallel variant (``a1_mapconcat_kernel``): MapConcatenate
(§5.2.2) on-chip — grid = (episode tile × time segment × event chunk);
each segment runs K = N phase-shifted bounded-list machines (carried
across its chunks in VMEM scratch) and emits the (a, count, b)
tuple (Fig. 5), with the Concatenate stage fused into the launch (the
stitched tuple carries in revisited output blocks, folded per segment via
``core.mapconcat.fold_pair_unrolled``; the ``unmatched`` flag and the
per-phase live-eviction flags feed the host's exact-recount fallback).
Phase starts and stitch zones are shared with the XLA Map step
(``core.mapconcat.phase_cum`` / ``stitch_zones``) so the paths cannot
drift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.events import TIME_NEG_INF
from repro.core.mapconcat import stitch_zones

from .a2_count import (DEFAULT_BLOCK_E, LANES, ROW_OVF, SEG_DUP, SEG_TIME,
                       SEG_TYPE, SEQ_GRID, SUBLANES, _block_e, _gate_phase,
                       _mapc_begin, _mapc_end, _prev_level, mapc_call)


def _a1_step(n_levels: int, lcap: int, et, tlo, thi, level, e, t, dup,
             s, ptr):
    """One event through the bounded-list machines of one episode tile.

    ``s`` is a tuple of ``lcap`` (NP, BM) slot rows and ``ptr`` the
    (NP, BM) circular write pointer. Returns the advanced (s, ptr), the
    (1, BM) completion row and the (NP, BM) live-eviction mask — shared by
    the carried and the segmented kernels."""
    last = n_levels - 1
    ok = functools.reduce(
        jnp.logical_or, [(t - sl > tlo) & (t - sl <= thi) for sl in s])
    advance = (et == e) & _prev_level(ok, level)            # (NP, BM)
    complete = advance[last:last + 1, :]                    # (1, BM)
    store = advance & (level != last) & ~complete
    # live-eviction: evicted witness may still have a same-tick or
    # lower-bounded consumer (see core/count_a1.py docstring)
    v = jnp.full(et.shape, TIME_NEG_INF, jnp.int32)
    for slot, sl in enumerate(s):
        v = jnp.where(store & (ptr == slot), sl, v)
    live = (v > TIME_NEG_INF) & (t - v <= thi) & ((tlo > 0) | dup)
    s = tuple(jnp.where(complete, TIME_NEG_INF,
                        jnp.where(store & (ptr == slot), t, sl))
              for slot, sl in enumerate(s))
    ptr = jnp.where(store, jnp.where(ptr == lcap - 1, 0, ptr + 1), ptr)
    ptr = jnp.where(complete, 0, ptr)
    return s, ptr, complete, live


def _any_level(x):
    """(NP, BM) bool → (1, BM) i32: whether any level row is set."""
    return jnp.max(x.astype(jnp.int32), axis=0, keepdims=True)


def _a1_body(n_levels: int, lcap: int, et, tlo, thi, ev_ref):
    """Per-event step over the (s, ptr, cnt, ovf) carry of the carried
    kernel; the events are read as scalars from the SMEM chunk."""
    level = jax.lax.broadcasted_iota(jnp.int32, et.shape, 0)

    def body(j, carry):
        s, ptr, cnt, ovf = carry
        s, ptr, complete, live = _a1_step(
            n_levels, lcap, et, tlo, thi, level, ev_ref[0, j],
            ev_ref[1, j], ev_ref[2, j] != 0, s, ptr)
        return (s, ptr, cnt + complete.astype(jnp.int32),
                ovf | _any_level(live))

    return body


def _a1_state_kernel(n_levels: int, lcap: int, et_ref, tlo_ref, thi_ref,
                     ev_ref, sin_ref, poin_ref, cin_ref, oin_ref,
                     cnt_ref, ovf_ref, sout_ref, poout_ref):
    """One (episode tile × event chunk) grid step: resume the machines from
    the carried output blocks (seeded from the state inputs at chunk 0) and
    advance them past this chunk's events. Inside the kernel the
    (NP, LCAP, BM) bricks are held as ``lcap`` (NP, BM) slot rows and the
    one-hot pointer mask as a slot index."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        sout_ref[...] = sin_ref[...]
        poout_ref[...] = poin_ref[...]
        cnt_ref[...] = cin_ref[...]
        ovf_ref[...] = oin_ref[...]

    s = tuple(sout_ref[:, slot, :] for slot in range(lcap))
    ptr = jnp.zeros(s[0].shape, jnp.int32)
    for slot in range(1, lcap):
        ptr = jnp.where(poout_ref[:, slot, :] != 0, slot, ptr)
    body = _a1_body(n_levels, lcap, et_ref[...], tlo_ref[...], thi_ref[...],
                    ev_ref)
    s, ptr, cnt, ovf = jax.lax.fori_loop(
        0, ev_ref.shape[1], body,
        (s, ptr, cnt_ref[0:1, :], ovf_ref[0:1, :]))
    cnt_ref[...] = jnp.broadcast_to(cnt, cnt_ref.shape)
    ovf_ref[...] = jnp.broadcast_to(ovf, ovf_ref.shape)
    for slot in range(lcap):
        sout_ref[:, slot, :] = s[slot]
        poout_ref[:, slot, :] = (ptr == slot).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n_levels", "lcap", "block_m", "block_e",
                              "interpret"))
def a1_count_kernel(etypes, tlo, thi, events, *, n_levels: int,
                    lcap: int = 4, block_m: int = LANES,
                    block_e: int = DEFAULT_BLOCK_E,
                    interpret: bool = False):
    """pallas_call wrapper (fresh machines). See a2_count_kernel; events
    here are i32[3, EP] (types; times; dup). Returns
    (counts i32[8, M], ovf i32[8, M]), row 0 meaningful. Delegates to the
    state-carried launch with empty machines so the one-shot API shares the
    chunked event ``BlockSpec`` (no whole-stream broadcast) — the final
    state bricks it emits are discarded, a conscious HBM-write trade for
    one kernel body across both call styles."""
    np_, m = etypes.shape
    s0 = jnp.full((np_, lcap, m), TIME_NEG_INF, jnp.int32)
    po0 = jnp.zeros((np_, lcap, m), jnp.int32).at[:, 0, :].set(1)
    c0 = jnp.zeros((SUBLANES, m), jnp.int32)
    o0 = jnp.zeros((SUBLANES, m), jnp.int32)
    cnt, ovf, _, _ = a1_count_state_kernel(
        etypes, tlo, thi, events, s0, po0, c0, o0, n_levels=n_levels,
        lcap=lcap, block_m=block_m, block_e=block_e, interpret=interpret)
    return cnt, ovf


@functools.partial(
    jax.jit, static_argnames=("n_levels", "lcap", "block_m", "block_e",
                              "interpret"))
def a1_count_state_kernel(etypes, tlo, thi, events, s, po, cnt, ovf, *,
                          n_levels: int, lcap: int = 4,
                          block_m: int = LANES,
                          block_e: int = DEFAULT_BLOCK_E,
                          interpret: bool = False):
    """State-in/state-out pallas_call wrapper.

    State operands (all i32, kernel brick layout — see ``ops``):
      s    (NP, LCAP, M)  circular timestamp brick (TIME_NEG_INF = empty)
      po   (NP, LCAP, M)  one-hot write-pointer mask (0/1)
      cnt  (8, M)         cumulative counts, row 0 meaningful
      ovf  (8, M)         sticky live-eviction flags, row 0 meaningful

    Returns (cnt, ovf, s, po) advanced past ``events``; each state input is
    aliased onto its output (donated), so never reuse the passed arrays.
    Events are walked in ``block_e`` chunks on the second (``arbitrary``)
    grid axis with the state carried on-chip between chunks.
    """
    np_, m = etypes.shape
    ep = events.shape[1]
    be = _block_e(ep, block_e)
    grid = (m // block_m, ep // be)
    kernel = functools.partial(_a1_state_kernel, n_levels, lcap)
    tile = lambda i, j: (0, i)  # noqa: E731 — episode tile, chunk-invariant
    tile3 = lambda i, j: (0, 0, i)  # noqa: E731
    out_shape = [jax.ShapeDtypeStruct((SUBLANES, m), jnp.int32),
                 jax.ShapeDtypeStruct((SUBLANES, m), jnp.int32),
                 jax.ShapeDtypeStruct((np_, lcap, m), jnp.int32),
                 jax.ShapeDtypeStruct((np_, lcap, m), jnp.int32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((events.shape[0], be), lambda i, j: (0, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((np_, lcap, block_m), tile3),
            pl.BlockSpec((np_, lcap, block_m), tile3),
            pl.BlockSpec((SUBLANES, block_m), tile),
            pl.BlockSpec((SUBLANES, block_m), tile),
        ],
        out_specs=[pl.BlockSpec((SUBLANES, block_m), tile),
                   pl.BlockSpec((SUBLANES, block_m), tile),
                   pl.BlockSpec((np_, lcap, block_m), tile3),
                   pl.BlockSpec((np_, lcap, block_m), tile3)],
        out_shape=out_shape,
        input_output_aliases={6: 0, 7: 1, 4: 2, 5: 3},
        compiler_params=SEQ_GRID,
        interpret=interpret,
    )(etypes, tlo, thi, events, s, po, cnt, ovf)


# --------------------------------------------------------------------------
# Segment-parallel MapConcatenate (paper §5.2.2) — bounded-list machines
# --------------------------------------------------------------------------


def _a1_mapc_kernel(n_levels: int, lcap: int, et_ref, tlo_ref, thi_ref,
                    cum_ref, w_ref, ev_ref, a_ref, c_ref, b_ref, f_ref,
                    ovf_ref, s_ref, ptr_ref, rows_ref):
    """One (episode tile × time segment × event chunk) grid step: advance
    the segment's K = N phase-shifted bounded-list machines (slot rows
    ``s_ref`` (K·LCAP, NP, BM) and pointers ``ptr_ref`` (K, NP, BM)
    carried across chunks in VMEM scratch) past this chunk, and fold the
    segment's tuple onto the carried Concatenate state after its last
    chunk (kernel analogue of ``core.mapconcat._segment_scan``'s scan
    body)."""
    k = n_levels

    def reset_state():
        s_ref[...] = jnp.full(s_ref.shape, TIME_NEG_INF, jnp.int32)
        ptr_ref[...] = jnp.zeros(ptr_ref.shape, jnp.int32)

    tau_lo, tau_hi, starts, rows = _mapc_begin(k, cum_ref, ev_ref, rows_ref,
                                               reset_state)
    et = et_ref[...]
    tlo = tlo_ref[...]
    thi = thi_ref[...]
    w_row = w_ref[0:1, :]                     # per-episode max span
    level = jax.lax.broadcasted_iota(jnp.int32, et.shape, 0)

    def body(j, phases):
        e = ev_ref[0, SEG_TYPE, j]
        t = ev_ref[0, SEG_TIME, j]
        dup = ev_ref[0, SEG_DUP, j] != 0
        zones = stitch_zones(t, tau_lo, tau_hi, w_row)
        out = []
        for start, (s, ptr, rows) in zip(starts, phases):
            s2, ptr2, raw_complete, live_ev = _a1_step(
                n_levels, lcap, et, tlo, thi, level, e, t, dup, s, ptr)
            live, rows = _gate_phase(t, e, start, zones, raw_complete, rows)
            ovf = rows[ROW_OVF] | (live & (_any_level(live_ev) != 0)
                                   ).astype(jnp.int32)
            out.append((tuple(jnp.where(live, x2, x)
                              for x2, x in zip(s2, s)),
                        jnp.where(live, ptr2, ptr),
                        rows[:ROW_OVF] + (ovf,)))
        return tuple(out)

    init = tuple((tuple(s_ref[kk * lcap + slot] for slot in range(lcap)),
                  ptr_ref[kk], rows[kk]) for kk in range(k))
    phases = jax.lax.fori_loop(0, ev_ref.shape[2], body, init)
    for kk, (s, ptr, _) in enumerate(phases):
        for slot in range(lcap):
            s_ref[kk * lcap + slot] = s[slot]
        ptr_ref[kk] = ptr
    _mapc_end(k, [r for _, _, r in phases], rows_ref, a_ref, c_ref, b_ref,
              f_ref, ovf_ref)


@functools.partial(
    jax.jit, static_argnames=("n_levels", "lcap", "block_m", "interpret"))
def a1_mapconcat_kernel(etypes, tlo, thi, cum, w, segs, *, n_levels: int,
                        lcap: int = 4, block_m: int = LANES,
                        interpret: bool = False):
    """Segment-parallel bounded-list pallas_call: grid = (episode tile ×
    time segment × event chunk), Map + fused Concatenate in one launch.

    Args as ``a2_mapconcat_kernel`` (``tlo`` unshifted — A1 keeps the
    strict lower bound). Returns (a, c, b, f) each i32[NP, M] — the
    stitched tuple, phase rows 0..N-1 meaningful — plus ovf i32[8, M]
    whose row 0 ORs the live-eviction flags over every (segment, phase).
    Row 0 of ``c`` is the count; an episode needs the host's exact
    fallback iff ``f[0] | ovf[0]``.
    """
    return mapc_call(functools.partial(_a1_mapc_kernel, n_levels, lcap),
                     [(n_levels * lcap,), (n_levels,)], etypes, tlo, thi,
                     cum, w, segs, block_m=block_m, interpret=interpret)
