"""Pallas TPU kernel: A2 (single-slot) episode counting.

Computation-to-core mapping (the TPU re-derivation of the paper's PTPE):
episodes live on the 128-wide **lane** axis, episode levels on the
**sublane** axis, so one VPU op advances 8×128 state machines. The grid's
first axis tiles the episode batch; the second axis blocks the **event
stream** into ``block_e``-sized chunks with ``arbitrary`` (sequential)
grid semantics — each chunk is copied into SMEM per grid step (the body
reads one event at a time as a scalar) while the machine state carries
across steps in the revisited output block, so the stream is never
broadcast whole and nothing caps the events per call (the fresh-state
wrapper shares the chunked launch with the state-carried one).

Layouts (all i32):
  etypes  (NP,  BM)  episode types, level-major  (NP = levels padded to 8k)
  tlo/thi (NP,  BM)  edge bounds, row i = edge i→i+1 (row N-1.. padded)
  events  (2, EP)    row 0 = types, row 1 = times (EP = events padded)
  count   (8, BM)    output; row 0 holds the counts (8 sublanes for tiling)

Event padding uses type = PAD_TYPE (-1); level-row padding uses -2, so a
padded event never matches a padded row. Validated in ``interpret=True``
against ``ref.a2_count_ref`` (tests/test_kernels.py sweeps shapes+dtypes).

State-in/state-out variant (``a2_count_state_kernel``): the single-slot
timestamp tile and the count row become kernel I/O with in-place aliasing,
so chunk-by-chunk streaming stays on-chip. A single slot per level is
complete machine state (Obs. 5.1), so carried chunked counting is
unconditionally bit-exact under any partitioning — no tie-group caveat.
Pack/unpack to ``core.count_a2.A2State`` lives in ``ops.a2_state_layout``
/ ``ops.a2_state_unpack``.

Segment-parallel variant (``a2_mapconcat_kernel``): the paper's
MapConcatenate mapping (§5.2.2) brought on-chip — the grid is
(episode tile × time segment × event chunk); each segment runs K = N
phase-shifted
single-slot machines (start offsets from ``core.mapconcat.phase_cum``,
stitch zones from ``core.mapconcat.stitch_zones``; the machines carry
across the segment's chunks in VMEM scratch) and emits the
(a, count, b) tuple of Fig. 5, with the Concatenate stage fused into the
same launch: the tuple lives in output blocks revisited across the segment
axis and each segment folds onto it with the first-match stitch
(``core.mapconcat.fold_pair_unrolled``, carrying the ``unmatched`` flag
for the host's exact-recount fallback).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.events import PAD_TYPE, TIME_NEG_INF
from repro.core.mapconcat import fold_pair_unrolled, stitch_zones

LANES = 128
SUBLANES = 8
PAD_ROW_TYPE = -2

# event-axis chunk: events per grid step on the ``arbitrary`` grid axis
# (the DMA/double-buffer granularity; also the padding quantum for long
# streams — see ops.event_brick)
DEFAULT_BLOCK_E = 1024

# segmented-kernel event-brick rows (see ops.segment_bricks):
# types, times, successor-duplicate flags, then the segment boundaries
# τ_p / τ_{p+1} broadcast along the row (read as scalars at column 0)
SEG_TYPE, SEG_TIME, SEG_DUP, SEG_TAU_LO, SEG_TAU_HI = range(5)
SEG_ROWS = 5

# episode tiles are independent (parallel); the event-chunk / time-segment
# axis carries machine state or the stitch fold across steps (arbitrary)
SEQ_GRID = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _prev_level(ok, level):
    """Edge gate per level: row i of the result is row i-1 of ``ok`` (the
    edge into level i) and row 0 is always open. A sublane rotate plus an
    iota select — no unaligned sublane slices or concatenations, which
    Mosaic refuses on boolean vectors."""
    prev = pltpu.roll(ok.astype(jnp.int32), 1, ok.ndim - 2)
    return (level == 0) | (prev != 0)


def _a2_body(n_levels: int, et, tlo, thi, ev_ref):
    """Per-event step over the (s, cnt) carry — shared by the fresh-state
    and state-carried kernels. ``s`` is (NP, BM), ``cnt`` (1, BM); the
    events are read as scalars from the SMEM chunk."""
    level = jax.lax.broadcasted_iota(jnp.int32, et.shape, 0)
    last = n_levels - 1

    def body(j, carry):
        s, cnt = carry
        e = ev_ref[0, j]
        t = ev_ref[1, j]
        delta = t - s                                     # (NP, BM)
        ok = (delta > tlo) & (delta <= thi)               # row i: edge i→i+1
        advance = (et == e) & _prev_level(ok, level)      # (NP, BM)
        complete = advance[last:last + 1, :]              # (1, BM)
        s = jnp.where(advance & (level != last), t, s)
        s = jnp.where(complete, TIME_NEG_INF, s)
        return s, cnt + complete.astype(jnp.int32)

    return body


def _a2_state_kernel(n_levels: int, et_ref, tlo_ref, thi_ref, ev_ref,
                     sin_ref, cin_ref, cnt_ref, sout_ref):
    """One (episode tile × event chunk) grid step: resume the machines from
    the carried output blocks (seeded from the state inputs at chunk 0),
    walk this chunk's events, and leave the advanced state in the revisited
    output blocks for the next chunk."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        sout_ref[...] = sin_ref[...]
        cnt_ref[...] = cin_ref[...]

    et = et_ref[...]
    tlo = tlo_ref[...]
    thi = thi_ref[...]
    body = _a2_body(n_levels, et, tlo, thi, ev_ref)
    s, cnt = jax.lax.fori_loop(0, ev_ref.shape[1], body,
                               (sout_ref[...], cnt_ref[0:1, :]))
    cnt_ref[...] = jnp.broadcast_to(cnt, cnt_ref.shape)
    sout_ref[...] = s


def _block_e(ep: int, block_e: int) -> int:
    """Effective event-chunk length: ``block_e`` when it divides the padded
    stream (ops.event_brick pads long streams to a block_e multiple), else
    one whole-stream chunk (short streams — the status-quo single fetch)."""
    return block_e if 0 < block_e < ep and ep % block_e == 0 else ep


@functools.partial(jax.jit,
                   static_argnames=("n_levels", "block_m", "block_e",
                                    "interpret"))
def a2_count_kernel(etypes, tlo, thi, events, *, n_levels: int,
                    block_m: int = LANES, block_e: int = DEFAULT_BLOCK_E,
                    interpret: bool = False):
    """pallas_call wrapper (fresh machines).

    Args:
      etypes/tlo/thi: i32[NP, M] (level-major, padded rows = PAD_ROW_TYPE /
        zero-width intervals); M multiple of ``block_m``.
      events: i32[2, EP] (types; times).
      n_levels: true episode size N (static).
    Returns i32[8, M]; row 0 = counts.

    Delegates to the state-carried launch with empty machines, so the
    non-streaming API pays the same chunked event ``BlockSpec`` (no
    whole-stream broadcast) as the streaming hot path.
    """
    np_, m = etypes.shape
    s0 = jnp.full((np_, m), TIME_NEG_INF, jnp.int32)
    c0 = jnp.zeros((SUBLANES, m), jnp.int32)
    cnt, _ = a2_count_state_kernel(etypes, tlo, thi, events, s0, c0,
                                   n_levels=n_levels, block_m=block_m,
                                   block_e=block_e, interpret=interpret)
    return cnt


@functools.partial(jax.jit,
                   static_argnames=("n_levels", "block_m", "block_e",
                                    "interpret"))
def a2_count_state_kernel(etypes, tlo, thi, events, s, cnt, *, n_levels: int,
                          block_m: int = LANES,
                          block_e: int = DEFAULT_BLOCK_E,
                          interpret: bool = False):
    """State-in/state-out pallas_call wrapper.

    State operands (i32, kernel layout): ``s`` (NP, M) last-accepted
    timestamp per level (TIME_NEG_INF = empty); ``cnt`` (8, M) cumulative
    counts, row 0 meaningful. Returns (cnt, s) advanced past ``events``;
    state inputs are aliased onto the outputs (donated) — never reuse the
    passed arrays. Events are walked in ``block_e`` chunks on the second
    (``arbitrary``) grid axis with the state carried on-chip between
    chunks.
    """
    np_, m = etypes.shape
    ep = events.shape[1]
    be = _block_e(ep, block_e)
    grid = (m // block_m, ep // be)
    kernel = functools.partial(_a2_state_kernel, n_levels)
    tile = lambda i, j: (0, i)  # noqa: E731 — episode tile, chunk-invariant
    out_shape = [jax.ShapeDtypeStruct((SUBLANES, m), jnp.int32),
                 jax.ShapeDtypeStruct((np_, m), jnp.int32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((events.shape[0], be), lambda i, j: (0, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((SUBLANES, block_m), tile),
        ],
        out_specs=[pl.BlockSpec((SUBLANES, block_m), tile),
                   pl.BlockSpec((np_, block_m), tile)],
        out_shape=out_shape,
        input_output_aliases={5: 0, 4: 1},
        compiler_params=SEQ_GRID,
        interpret=interpret,
    )(etypes, tlo, thi, events, s, cnt)


# --------------------------------------------------------------------------
# Segment-parallel MapConcatenate (paper §5.2.2) — single-slot machines
# --------------------------------------------------------------------------


# per-phase row quantities of a segment's K machines, held across the
# event chunks in a (MAPC_ROWS, NP, BM) VMEM scratch, phase k in row k
ROW_CNT, ROW_A, ROW_B, ROW_DONE, ROW_ASET, ROW_OVF = range(6)
MAPC_ROWS = 6

# segmented grid = (episode tile × time segment × event chunk): tiles are
# independent; segments carry the Concatenate fold and chunks the machine
# state, both in order
MAPC_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _phase_rows(x, k: int):
    """Zero the rows >= K of an (NP, BM) phase brick (as i32)."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < k, x.astype(jnp.int32), 0)


def _mapc_begin(k: int, cum_ref, ev_ref, rows_ref, reset_state):
    """On the segment's first event chunk, reset the phase rows (a = τ_p,
    b = τ_{p+1}, the rest 0) and let ``reset_state`` empty the machines.
    Returns (tau_lo, tau_hi, starts, rows): the segment bounds,
    ``starts[k]`` the (1, BM) start time of phase k, and ``rows[k]`` phase
    k's (1, BM) rows loaded from the scratch."""
    tau_lo = ev_ref[0, SEG_TAU_LO, 0]
    tau_hi = ev_ref[0, SEG_TAU_HI, 0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        shape = rows_ref.shape[1:]
        for r in range(MAPC_ROWS):
            rows_ref[r] = jnp.zeros(shape, jnp.int32)
        rows_ref[ROW_A] = jnp.full(shape, tau_lo, jnp.int32)
        rows_ref[ROW_B] = jnp.full(shape, tau_hi, jnp.int32)
        reset_state()

    starts = [tau_lo - cum_ref[kk:kk + 1, :] for kk in range(k)]
    rows = [tuple(rows_ref[r, kk:kk + 1, :] for r in range(MAPC_ROWS))
            for kk in range(k)]
    return tau_lo, tau_hi, starts, rows


def _mapc_end(k: int, rows, rows_ref, a_ref, c_ref, b_ref, f_ref, ovf_ref):
    """Store the per-phase rows back to the scratch; after the segment's
    last event chunk, fold its tuple into the outputs."""
    for kk, phase in enumerate(rows):
        for r, x in enumerate(phase):
            rows_ref[r, kk:kk + 1, :] = x

    p = pl.program_id(1)  # read outside pl.when: interpret mode needs it

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        _mapc_fold_and_emit(k, p, rows_ref, a_ref, c_ref, b_ref, f_ref,
                            ovf_ref)


def _mapc_fold_and_emit(k: int, p, rows_ref, a_ref, c_ref, b_ref, f_ref,
                        ovf_ref):
    """Fused Concatenate: fold segment ``p``'s tuple onto the carried tuple
    held in the revisited output blocks (shared by the A1 and A2 segmented
    kernels). The ``ovf`` row ORs the segment's per-phase flags."""
    a = rows_ref[ROW_A]
    cnt = rows_ref[ROW_CNT]
    b = rows_ref[ROW_B]
    ovf_any = jnp.max(_phase_rows(rows_ref[ROW_OVF], k), axis=0,
                      keepdims=True)
    ovf_row = jnp.broadcast_to(ovf_any, ovf_ref.shape)

    @pl.when(p == 0)
    def _():
        a_ref[...] = _phase_rows(a, k)
        c_ref[...] = _phase_rows(cnt, k)
        b_ref[...] = _phase_rows(b, k)
        f_ref[...] = jnp.zeros(f_ref.shape, jnp.int32)
        ovf_ref[...] = ovf_row

    @pl.when(p > 0)
    def _():
        carry = (a_ref[...], c_ref[...], b_ref[...], f_ref[...] != 0)
        zf = jnp.zeros(a.shape, jnp.bool_)
        a2, c2, b2, f2 = fold_pair_unrolled(carry, (a, cnt, b, zf), k)
        a_ref[...] = _phase_rows(a2, k)
        c_ref[...] = _phase_rows(c2, k)
        b_ref[...] = _phase_rows(b2, k)
        f_ref[...] = _phase_rows(f2, k)
        ovf_ref[...] = ovf_ref[...] | ovf_row


def _gate_phase(t, e, start, zones, raw_complete, rows):
    """Zone gating of one phase machine for event (t, e): whether the
    machine may consume the event, and its rows (cnt, a, b, done, a_set,
    ovf) after a gated completion. Zones from
    ``core.mapconcat.stitch_zones`` (single source of truth)."""
    seg_z, a_z, live_z, cross_z = zones
    cnt, a, b, done, a_set, ovf = rows
    in_window = (t > start) & live_z & (done == 0)
    complete = raw_complete & in_window
    in_seg = complete & seg_z
    rec_a = in_seg & (a_set == 0) & a_z
    crossing = complete & cross_z
    rows = (cnt + in_seg.astype(jnp.int32), jnp.where(rec_a, t, a),
            jnp.where(crossing, t, b), done | crossing.astype(jnp.int32),
            a_set | rec_a.astype(jnp.int32), ovf)
    return in_window & (e != PAD_TYPE), rows


def _a2_mapc_kernel(n_levels: int, et_ref, tlo_ref, thi_ref, cum_ref, w_ref,
                    ev_ref, a_ref, c_ref, b_ref, f_ref, ovf_ref, s_ref,
                    rows_ref):
    """One (episode tile × time segment × event chunk) grid step: advance
    the segment's K phase-shifted single-slot machines (state carried
    across chunks in the VMEM scratch ``s_ref``) past this chunk, and fold
    the segment's tuple onto the carried Concatenate state after its last
    chunk. The kernel analogue of ``core.mapconcat._segment_scan`` with
    Obs. 5.1 state."""
    k = n_levels
    last = n_levels - 1

    def reset_state():
        s_ref[...] = jnp.full(s_ref.shape, TIME_NEG_INF, jnp.int32)

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
        match = et == e                                     # (NP, BM)
        zones = stitch_zones(t, tau_lo, tau_hi, w_row)
        out = []
        for start, (s, rows) in zip(starts, phases):
            delta = t - s
            ok = (delta > tlo) & (delta <= thi)
            advance = match & _prev_level(ok, level)
            raw_complete = advance[last:last + 1, :]        # (1, BM)
            s2 = jnp.where(advance & (level != last), t, s)
            s2 = jnp.where(raw_complete, TIME_NEG_INF, s2)
            live, rows = _gate_phase(t, e, start, zones, raw_complete, rows)
            out.append((jnp.where(live, s2, s), rows))
        return tuple(out)

    phases = jax.lax.fori_loop(
        0, ev_ref.shape[2], body,
        tuple((s_ref[kk], rows[kk]) for kk in range(k)))
    for kk, (s, _) in enumerate(phases):
        s_ref[kk] = s
    _mapc_end(k, [r for _, r in phases], rows_ref, a_ref, c_ref, b_ref,
              f_ref, ovf_ref)


def mapc_call(kernel, scratch, etypes, tlo, thi, cum, w, segs, *,
              block_m: int, interpret: bool):
    """Segmented pallas_call shared by the A1 and A2 kernels: grid =
    (episode tile × time segment × event chunk). Each step reads one
    (5, chunk) slice of the segment brick into SMEM, so the events are
    scalar loads and the brick length does not bound VMEM. ``scratch``
    lists the leading shapes of the kernel's (…, NP, BM) machine-state
    scratch, followed by the phase-row scratch."""
    np_, m = etypes.shape
    p, _, lw = segs.shape
    ch = _block_e(lw, DEFAULT_BLOCK_E)
    grid = (m // block_m, p, lw // ch)
    tile = lambda i, j, c: (0, i)  # noqa: E731
    out_shape = ([jax.ShapeDtypeStruct((np_, m), jnp.int32)] * 4
                 + [jax.ShapeDtypeStruct((SUBLANES, m), jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((np_, block_m), tile),
            pl.BlockSpec((SUBLANES, block_m), tile),
            pl.BlockSpec((1, SEG_ROWS, ch), lambda i, j, c: (j, 0, c),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=([pl.BlockSpec((np_, block_m), tile)] * 4
                   + [pl.BlockSpec((SUBLANES, block_m), tile)]),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape + (np_, block_m), jnp.int32)
                        for shape in scratch]
                       + [pltpu.VMEM((MAPC_ROWS, np_, block_m), jnp.int32)],
        compiler_params=MAPC_GRID,
        interpret=interpret,
    )(etypes, tlo, thi, cum, w, segs)


@functools.partial(jax.jit,
                   static_argnames=("n_levels", "block_m", "interpret"))
def a2_mapconcat_kernel(etypes, tlo, thi, cum, w, segs, *, n_levels: int,
                        block_m: int = LANES, interpret: bool = False):
    """Segment-parallel single-slot pallas_call: grid = (episode tile ×
    time segment × event chunk), Map + fused Concatenate in one launch.

    Args (see ``ops.mapconcat_layout`` / ``ops.segment_bricks``):
      etypes/tlo/thi: i32[NP, M] level-major bricks (``tlo`` already
        shifted for the inclusive lower bound — A2 counts the relaxed
        batch);
      cum: i32[NP, M] phase-start offsets (row k = Σ_{i<k} thi);
      w: i32[8, M] per-episode max span, row 0 meaningful;
      segs: i32[P, 5, LW] per-segment event windows
        (types/times/dup/τ_p/τ_{p+1}); LW a multiple of
        ``DEFAULT_BLOCK_E`` once it exceeds it.
    Returns (a, c, b, f) each i32[NP, M] — the stitched tuple, phase rows
    0..N-1 meaningful — plus ovf i32[8, M] (always zero for A2; kept for
    output-shape parity with the A1 variant). Row 0 of ``c`` is the count,
    row 0 of ``f`` the unmatched flag.
    """
    return mapc_call(functools.partial(_a2_mapc_kernel, n_levels),
                     [(n_levels,)], etypes, tlo, thi, cum, w, segs,
                     block_m=block_m, interpret=interpret)
