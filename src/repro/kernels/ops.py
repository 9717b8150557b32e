"""jit'd public wrappers for the episode-counting kernels.

Handles host→kernel layout (episode-major → level-major, lane/sublane
padding), dispatch policy, and result unpacking — including the
state-in/state-out layout contract for the carried (streaming) kernels:

  * ``a1_state_layout`` / ``a1_state_unpack`` convert between
    ``core.count_a1.A1State``'s episode-major [M, N, L] arrays and the
    kernel's level-major (NP, LCAP, MP) brick + one-hot write-pointer
    mask + (8, MP) count/ovf rows;
  * ``a2_state_layout`` / ``a2_state_unpack`` do the single-slot analogue;
  * ``a1_state_call`` / ``a2_state_call`` dispatch one carried chunk in
    kernel layout (the streaming hot path keeps state resident in this
    layout — no per-window repacking);
  * ``a1_count_stateful`` / ``a2_count_stateful`` are the one-shot-chunk
    conveniences used by ``count_a1``/``count_a2`` stateful modes (host
    layout in, host layout out);
  * ``mapconcat_layout`` / ``segment_bricks`` pack the segmented kernels'
    operands (phase-start cumsum + span rows, per-segment
    types/times/dup/τ bricks), and ``a1_mapconcat_tuples`` /
    ``a2_mapconcat_tuples`` / ``a1_mapconcat_count`` /
    ``a2_mapconcat_count`` dispatch the in-kernel MapConcatenate (grid =
    episode tile × time segment, Concatenate fold fused on-chip).

Dispatch policy:
  * on TPU — compiled Pallas kernel;
  * anywhere with ``REPRO_INTERPRET_KERNELS=1`` / ``REPRO_KERNEL_INTERPRET=1``
    (or ``force="interpret"``) — ``interpret=True`` (kernel body executed by
    XLA CPU; used by tests and the CI kernel job);
  * otherwise — raise ``KernelDeclined`` so callers (core/count_*.py) fall
    back to the XLA-scan engine, which is the fast CPU path. Only this
    decline reroutes: a lowering or compile error of a kernel propagates.

``KERNEL_CALLS`` tallies host-side kernel dispatches per kind ("a1", "a2",
"a1_state", "a2_state", "a1_mapc", "a2_mapc", and the per-device
"a1_mapc_shard"/"a2_mapc_shard" of the mesh-sharded MapConcatenate
dispatch) — the interpret-mode
instrumentation tests use it to assert the Pallas path actually executed
(the bug this module's stateful API fixes was exactly a silent bypass that
no test could see).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.count_a1 import A1State, DEFAULT_LCAP, init_a1_state
from repro.core.count_a2 import A2State, init_a2_state
from repro.core.episodes import EpisodeBatch
from repro.core.events import (PAD_TYPE, TIME_NEG_INF, EventStream,
                               count_level1)

from repro.core.mapconcat import (data_mesh, make_segments, phase_cum,
                                  shard_device_count)

from .a1_count import (a1_count_kernel, a1_count_state_kernel,
                       a1_mapconcat_kernel)
from .a2_count import (DEFAULT_BLOCK_E, LANES, PAD_ROW_TYPE, SEG_ROWS,
                       SUBLANES, a2_count_kernel, a2_count_state_kernel,
                       a2_mapconcat_kernel)
from repro.obs.jaxprof import annotate

from .tally import KERNEL_CALLS, KernelDeclined, interpret_requested
from .tally import record_fallback, reset_kernel_calls  # noqa: F401

# Largest per-segment event-window length (LW) the segmented-kernel
# dispatch admits. The kernels stream each window through SMEM in
# DEFAULT_BLOCK_E-event chunks, so LW does not bound VMEM; this caps the
# per-launch brick (P × 5 × LW i32 packed on the host and copied to HBM)
# at the corner ``tests/test_tpu_compile.py`` compiles for v5e. Beyond it
# the dispatch declines (KernelDeclined) and callers take the XLA
# MapConcatenate; ``repro.analysis.vmem`` holds it to the chunk quantum.
MAX_SEG_BRICK_LW = 1 << 17


def _mode(force: str | None) -> bool:
    """Returns interpret flag, or raises KernelDeclined to decline."""
    if force == "compiled":
        return False
    if force == "interpret":
        return True
    if jax.default_backend() == "tpu":
        return False
    if interpret_requested():
        return True
    raise KernelDeclined("no TPU and interpret mode not requested")


def kernel_mode(force: str | None = None) -> bool:
    """Public dispatch probe: the interpret flag the kernels should run
    with, or KernelDeclined when the caller should use the XLA-scan
    engine instead. Streaming counters probe once at construction."""
    return _mode(force)


def _round_up(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


def episode_layout(eps: EpisodeBatch, inclusive_lower: bool,
                   block_m: int = LANES):
    """(M,N) episode-major → (NP, MP) level-major kernel layout."""
    m, n = eps.etypes.shape
    np_ = _round_up(max(n, 1), SUBLANES)
    mp = _round_up(m, block_m)
    et = np.full((np_, mp), PAD_ROW_TYPE, np.int32)
    et[:n, :m] = eps.etypes.T
    # row i of tlo/thi = edge i→i+1; padded rows get empty intervals (0, 0]
    tlo = np.zeros((np_, mp), np.int32)
    thi = np.zeros((np_, mp), np.int32)
    tlo[: n - 1, :m] = eps.tlo.T - (1 if inclusive_lower else 0)
    thi[: n - 1, :m] = eps.thi.T
    return jnp.asarray(et), jnp.asarray(tlo), jnp.asarray(thi)


def _event_rows(types, times, with_dup: bool, length: int | None):
    types = np.asarray(types, np.int32)
    times = np.asarray(times, np.int32)
    n = types.shape[0]
    if length is None:
        ep = _round_up(max(n, 1), LANES)
        if ep > DEFAULT_BLOCK_E:
            ep = _round_up(ep, DEFAULT_BLOCK_E)
    else:
        ep = length
    rows = 3 if with_dup else 2
    ev = np.zeros((rows, ep), np.int32)
    ev[0, :] = PAD_TYPE
    ev[0, :n] = types
    last = times[-1] if n else 0
    ev[1, :] = last
    ev[1, :n] = times
    if with_dup and n > 1:
        ev[2, : n - 1] = ((times[1:] == times[:-1])
                          & (types[1:] != PAD_TYPE)).astype(np.int32)
    return ev


def event_brick(types, times, with_dup: bool):
    """Raw event arrays → padded i32[2 or 3, EP] kernel brick
    (types; times; [dup]): EP rounds up to 128, and for streams longer
    than one event chunk to ``DEFAULT_BLOCK_E`` so the kernels' chunked
    event ``BlockSpec`` divides the brick evenly."""
    return jnp.asarray(_event_rows(types, times, with_dup, None))


def event_layout(stream: EventStream, with_dup: bool):
    """Events → i32[2 or 3, EP] (types; times; [dup]), EP padded to 128."""
    return event_brick(stream.types, stream.times, with_dup)


def a2_count(stream: EventStream, eps: EpisodeBatch,
             force: str | None = None) -> np.ndarray:
    """Kernel-backed Algorithm 3 (inclusive-lower strengthening built in).
    ``eps`` must already be relaxed (tlo == 0). Returns int64[M]."""
    interpret = _mode(force)
    if eps.N == 1:
        return count_level1(stream, eps.etypes[:, 0])
    et, tlo, thi = episode_layout(eps, inclusive_lower=True)
    ev = event_layout(stream, with_dup=False)
    KERNEL_CALLS["a2"] += 1
    with annotate("kernel:a2"):
        out = a2_count_kernel(et, tlo, thi, ev, n_levels=eps.N,
                              interpret=interpret)
    return np.asarray(out[0, : eps.M], dtype=np.int64)


def a1_count(stream: EventStream, eps: EpisodeBatch, lcap: int = 4,
             force: str | None = None):
    """Kernel-backed bounded-list Algorithm 1.
    Returns (counts int64[M], ovf bool[M]); see core.count_a1 for the
    exactness-restoring fallback on flagged episodes."""
    interpret = _mode(force)
    if eps.N == 1:
        return count_level1(stream, eps.etypes[:, 0]), \
            np.zeros(eps.M, dtype=bool)
    et, tlo, thi = episode_layout(eps, inclusive_lower=False)
    ev = event_layout(stream, with_dup=True)
    KERNEL_CALLS["a1"] += 1
    with annotate("kernel:a1"):
        cnt, ovf = a1_count_kernel(et, tlo, thi, ev, n_levels=eps.N,
                                   lcap=lcap, interpret=interpret)
    return (np.asarray(cnt[0, : eps.M], dtype=np.int64),
            np.asarray(ovf[0, : eps.M], dtype=bool))


# --------------------------------------------------------------------------
# State-carried (streaming) dispatch: pack/unpack + instrumented kernel calls
# --------------------------------------------------------------------------


def a1_state_layout(state: A1State, block_m: int = LANES):
    """``A1State`` ([M, N, L] episode-major) → kernel brick layout.

    Returns (s, po, cnt, ovf):
      s    i32(NP, L, MP)  s[lvl, slot, m] = state.s[m, lvl, slot]
      po   i32(NP, L, MP)  one-hot of state.ptr (padded lanes: slot 0 hot)
      cnt  i32(8, MP)      row 0 = state.count
      ovf  i32(8, MP)      row 0 = state.ovf
    """
    s_host = np.asarray(state.s)
    m, n, lcap = s_host.shape
    np_ = _round_up(max(n, 1), SUBLANES)
    mp = _round_up(m, block_m)
    s = np.full((np_, lcap, mp), TIME_NEG_INF, np.int32)
    s[:n, :, :m] = s_host.transpose(1, 2, 0)
    ptr = np.zeros((np_, mp), np.int32)
    ptr[:n, :m] = np.asarray(state.ptr).T
    po = (np.arange(lcap, dtype=np.int32)[None, :, None]
          == ptr[:, None, :]).astype(np.int32)
    cnt = np.zeros((SUBLANES, mp), np.int32)
    cnt[0, :m] = np.asarray(state.count)
    ovf = np.zeros((SUBLANES, mp), np.int32)
    ovf[0, :m] = np.asarray(state.ovf)
    return (jnp.asarray(s), jnp.asarray(po), jnp.asarray(cnt),
            jnp.asarray(ovf))


def a1_state_unpack(s, po, cnt, ovf, m: int, n: int) -> A1State:
    """Inverse of ``a1_state_layout`` (kernel brick → episode-major)."""
    s_host = np.asarray(s)[:n, :, :m].transpose(2, 0, 1)
    ptr = np.argmax(np.asarray(po)[:n, :, :m], axis=1).T.astype(np.int32)
    return A1State(
        s=jnp.asarray(s_host),
        ptr=jnp.asarray(ptr),
        count=jnp.asarray(np.asarray(cnt)[0, :m]),
        ovf=jnp.asarray(np.asarray(ovf)[0, :m] != 0))


def a2_state_layout(state: A2State, block_m: int = LANES):
    """``A2State`` ([M, N] episode-major) → kernel (s, cnt) layout."""
    s_host = np.asarray(state.s)
    m, n = s_host.shape
    np_ = _round_up(max(n, 1), SUBLANES)
    mp = _round_up(m, block_m)
    s = np.full((np_, mp), TIME_NEG_INF, np.int32)
    s[:n, :m] = s_host.T
    cnt = np.zeros((SUBLANES, mp), np.int32)
    cnt[0, :m] = np.asarray(state.count)
    return jnp.asarray(s), jnp.asarray(cnt)


def a2_state_unpack(s, cnt, m: int, n: int) -> A2State:
    """Inverse of ``a2_state_layout``."""
    return A2State(
        s=jnp.asarray(np.asarray(s)[:n, :m].T),
        count=jnp.asarray(np.asarray(cnt)[0, :m]))


def a1_state_call(et, tlo, thi, ev, s, po, cnt, ovf, *, n_levels: int,
                  lcap: int, interpret: bool):
    """One carried A1 chunk in kernel layout (instrumented). Returns
    (cnt, ovf, s, po); the passed state arrays are donated."""
    KERNEL_CALLS["a1_state"] += 1
    with annotate("kernel:a1_state"):
        return a1_count_state_kernel(et, tlo, thi, ev, s, po, cnt, ovf,
                                     n_levels=n_levels, lcap=lcap,
                                     interpret=interpret)


def a2_state_call(et, tlo, thi, ev, s, cnt, *, n_levels: int,
                  interpret: bool):
    """One carried A2 chunk in kernel layout (instrumented). Returns
    (cnt, s); the passed state arrays are donated."""
    KERNEL_CALLS["a2_state"] += 1
    with annotate("kernel:a2_state"):
        return a2_count_state_kernel(et, tlo, thi, ev, s, cnt,
                                     n_levels=n_levels, interpret=interpret)


# Fixed launch shape of the carried kernels on the streaming path. A
# served session's candidate batches change size every window, and its
# windows change length; launching every carried chunk as
# STREAM_BLOCK_M episode lanes × STREAM_BLOCK_E events (lane blocks and
# event chunks of one counter are separate launches) leaves one compiled
# kernel per level N, whatever the batch and window sizes. Interpret mode
# (the CPU tests) runs the same blocking at one lane tile × 128 events:
# its cost grows with the padded shape and it compiles nothing for a chip.
STREAM_BLOCK_M = 512
STREAM_BLOCK_E = 2048


def stream_block(interpret: bool) -> tuple[int, int]:
    """(episode lanes, events) of one carried streaming launch."""
    return (LANES, LANES) if interpret else (STREAM_BLOCK_M, STREAM_BLOCK_E)


def lane_blocks(arrays, width: int) -> list[tuple]:
    """Split kernel-layout arrays (lane axis last, a multiple of
    ``width``) into per-block tuples of ``width``-lane device arrays."""
    host = [np.asarray(a) for a in arrays]
    return [tuple(jnp.asarray(a[..., i: i + width]) for a in host)
            for i in range(0, host[0].shape[-1], width)]


def join_lanes(blocks: list[tuple]) -> tuple:
    """Inverse of ``lane_blocks``: host arrays with the blocks' lanes
    concatenated."""
    return tuple(np.concatenate([np.asarray(b[k]) for b in blocks], axis=-1)
                 for k in range(len(blocks[0])))


def event_chunks(types, times, with_dup: bool, width: int) -> list:
    """Events → ``width``-event device bricks, the last one padded. The
    dup row is computed over the whole feed, so carrying the machines
    through the chunks equals one launch over the concatenation."""
    n = int(np.shape(types)[0])
    ev = _event_rows(types, times, with_dup, _round_up(max(n, 1), width))
    return [jax.device_put(ev[:, i: i + width])
            for i in range(0, ev.shape[1], width)]


# --------------------------------------------------------------------------
# Segment-parallel (MapConcatenate) dispatch: layout + instrumented calls
# --------------------------------------------------------------------------


def mapconcat_layout(eps: EpisodeBatch, inclusive_lower: bool,
                     block_m: int = LANES):
    """Episode layout for the segmented kernels: the usual level-major
    bricks plus the phase-start offsets and per-episode span.

    Returns (et, tlo, thi, cum, w):
      cum  i32(NP, MP)  row k = Σ_{i<k} thi (``core.mapconcat.phase_cum``)
                        — machine k of the segment starts that far before
                        the boundary; rows >= N zero (never read)
      w    i32(8, MP)   row 0 = per-episode max occurrence span
    """
    et, tlo, thi = episode_layout(eps, inclusive_lower, block_m)
    m, n = eps.etypes.shape
    np_ = _round_up(max(n, 1), SUBLANES)
    mp = _round_up(m, block_m)
    cum = np.zeros((np_, mp), np.int32)
    cum[:n, :m] = np.asarray(phase_cum(eps.thi), np.int32).T
    w = np.zeros((SUBLANES, mp), np.int32)
    w[0, :m] = np.asarray(eps.max_span, np.int32)
    return et, tlo, thi, jnp.asarray(cum), jnp.asarray(w)


def segment_bricks(wt, wtt, tau, length: int | None = None):
    """Per-segment event windows → i32[P, 5, LW] kernel bricks.

    Rows: (types, times, dup, τ_p, τ_{p+1}) — the boundary rows are
    broadcast along the window (the kernel reads them as scalars at column
    0 of every chunk). ``dup`` marks a same-timestamp real successor *within the window*,
    matching the per-window ``core.count_a1.dup_flags`` semantics the XLA
    Map step uses. ``length`` overrides the round-up-to-128 window padding
    (the cross-session batcher re-buckets to the fused group's max);
    past one chunk the default pads to a ``DEFAULT_BLOCK_E`` multiple so
    the kernels' chunked SMEM ``BlockSpec`` divides the window.
    """
    wt = np.asarray(wt, np.int32)
    wtt = np.asarray(wtt, np.int32)
    p, lw = wt.shape
    if length is None:
        lwp = _round_up(max(lw, 1), LANES)
        if lwp > DEFAULT_BLOCK_E:
            lwp = _round_up(lwp, DEFAULT_BLOCK_E)
    else:
        lwp = length
    if lwp > MAX_SEG_BRICK_LW:
        # beyond the admitted corner; decline so the caller's
        # graceful-degradation path takes the XLA engine
        raise KernelDeclined(
            f"segment brick LW={lwp} exceeds the admitted "
            f"MAX_SEG_BRICK_LW={MAX_SEG_BRICK_LW}")
    ev = np.zeros((p, SEG_ROWS, lwp), np.int32)
    ev[:, 0, :] = PAD_TYPE
    ev[:, 0, :lw] = wt
    ev[:, 1, :lw] = wtt
    if lw > 1:
        ev[:, 2, : lw - 1] = ((wtt[:, 1:] == wtt[:, :-1])
                              & (wt[:, 1:] != PAD_TYPE)).astype(np.int32)
    tau = np.asarray(tau, np.int64)
    ev[:, 3, :] = tau[:-1, None].astype(np.int32)
    ev[:, 4, :] = tau[1:, None].astype(np.int32)
    return jnp.asarray(ev)


def a1_mapconcat_tuples(et, tlo, thi, cum, w, segs, *, n_levels: int,
                        lcap: int, interpret: bool):
    """One segmented A1 launch in kernel layout (instrumented). Returns the
    stitched (a, c, b, f) bricks plus the ovf rows."""
    KERNEL_CALLS["a1_mapc"] += 1
    with annotate("kernel:a1_mapc"):
        return a1_mapconcat_kernel(et, tlo, thi, cum, w, segs,
                                   n_levels=n_levels, lcap=lcap,
                                   interpret=interpret)


def a2_mapconcat_tuples(et, tlo, thi, cum, w, segs, *, n_levels: int,
                        interpret: bool):
    """One segmented A2 launch in kernel layout (instrumented)."""
    KERNEL_CALLS["a2_mapc"] += 1
    with annotate("kernel:a2_mapc"):
        return a2_mapconcat_kernel(et, tlo, thi, cum, w, segs,
                                   n_levels=n_levels, interpret=interpret)


def _mapc_inputs(stream: EventStream, eps: EpisodeBatch, num_segments: int,
                 inclusive_lower: bool):
    """Host side of a one-shot segmented launch: segment the stream
    (``core.mapconcat.make_segments`` — same boundaries as the XLA path)
    and pack the kernel bricks."""
    w_max = int(np.asarray(eps.max_span).max())
    tau, wt, wtt = make_segments(stream, num_segments, w_max)
    layout = mapconcat_layout(eps, inclusive_lower=inclusive_lower)
    return layout + (segment_bricks(wt, wtt, tau),)


def a1_mapconcat_count(stream: EventStream, eps: EpisodeBatch,
                       num_segments: int = 8, lcap: int = DEFAULT_LCAP,
                       force: str | None = None):
    """Kernel-backed MapConcatenate: one launch runs the segment Map and
    the fused Concatenate fold. Returns (counts int64[M], bad bool[M]);
    ``bad`` marks episodes needing the caller's exact fallback (unmatched
    stitch or possibly-live eviction — same containment as
    ``core.mapconcat.mapconcatenate``)."""
    interpret = _mode(force)
    if eps.N == 1:
        return (count_level1(stream, eps.etypes[:, 0]),
                np.zeros(eps.M, dtype=bool))
    if len(stream) == 0:
        return np.zeros(eps.M, np.int64), np.zeros(eps.M, dtype=bool)
    et, tlo, thi, cum, w, segs = _mapc_inputs(stream, eps, num_segments,
                                              inclusive_lower=False)
    _, c, _, f, ovf = a1_mapconcat_tuples(et, tlo, thi, cum, w, segs,
                                          n_levels=eps.N, lcap=lcap,
                                          interpret=interpret)
    counts = np.asarray(c[0, : eps.M], dtype=np.int64)
    bad = np.asarray((f[0, : eps.M] != 0) | (ovf[0, : eps.M] != 0))
    return counts, bad


def a2_mapconcat_count(stream: EventStream, eps: EpisodeBatch,
                       num_segments: int = 8, force: str | None = None):
    """Kernel-backed segmented A2 (single-slot) counting of ``eps`` under
    its own bounds with the inclusive-lower strengthening (callers pass the
    relaxed batch). Returns (counts int64[M], bad bool[M]); ``bad`` = the
    stitch's unmatched flag (single-slot machines cannot overflow)."""
    interpret = _mode(force)
    if eps.N == 1:
        return (count_level1(stream, eps.etypes[:, 0]),
                np.zeros(eps.M, dtype=bool))
    if len(stream) == 0:
        return np.zeros(eps.M, np.int64), np.zeros(eps.M, dtype=bool)
    et, tlo, thi, cum, w, segs = _mapc_inputs(stream, eps, num_segments,
                                              inclusive_lower=True)
    _, c, _, f, _ = a2_mapconcat_tuples(et, tlo, thi, cum, w, segs,
                                        n_levels=eps.N, interpret=interpret)
    counts = np.asarray(c[0, : eps.M], dtype=np.int64)
    bad = np.asarray(f[0, : eps.M] != 0)
    return counts, bad


# --------------------------------------------------------------------------
# Multi-device (mesh-sharded) MapConcatenate dispatch
# --------------------------------------------------------------------------


# shard_device_count is re-exported from core.mapconcat (the single
# source of truth for the sharded dispatch's device-set policy).


@functools.lru_cache(maxsize=None)
def _stream_mesh(d: int):
    """Cached 1-D ``("data",)`` mesh over the first ``d`` devices
    (``core.mapconcat.data_mesh`` — same builder the XLA fallback and
    ``launch.mesh.make_stream_mesh`` use)."""
    return data_mesh(d)


@functools.lru_cache(maxsize=None)
def _mapc_sharded_fn(kind: str, n_levels: int, lcap: int, interpret: bool,
                     d: int, lanes: bool):
    """The sharded segmented launch over the first ``d`` devices (cached
    per shape key); see ``mapc_sharded_launch``."""
    return mapc_sharded_launch(_stream_mesh(d), kind, n_levels, lcap,
                               interpret, lanes)


def mapc_sharded_launch(mesh, kind: str, n_levels: int, lcap: int,
                        interpret: bool, lanes: bool):
    """Build the sharded segmented launch: a ``shard_map`` over the
    ``mesh``'s ``data`` axis where each device runs ONE segmented Pallas
    launch on its contiguous segment group (grid = episode tile × local
    segments, in-group Concatenate fused on-chip), then all-gathers the
    O(P·N) per-device (a, count, b, f) tuples and folds them replicated —
    the cross-device half of the paper's MapConcatenate (§5.2.2), sound
    because the tuple fold is associative across arbitrary cut points.

    ``lanes`` adds a leading session axis (the cross-session batcher's
    fused variant): the per-device kernel is vmapped over lanes while the
    segment axis shards over devices. Returns a jitted callable with the
    same (a, c, b, f, ovf) output contract as ``a1_mapconcat_kernel``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.mapconcat import fold_pair

    d = mesh.shape["data"]
    if kind == "a1":
        base = functools.partial(a1_mapconcat_kernel, n_levels=n_levels,
                                 lcap=lcap, interpret=interpret)
    else:
        base = functools.partial(a2_mapconcat_kernel, n_levels=n_levels,
                                 interpret=interpret)
    call = jax.vmap(base) if lanes else base
    k = n_levels

    def dev_fn(et, tlo, thi, cum, w, segs):
        # one kernel launch over this device's P/d-segment group
        a, c, b, f, ovf = call(et, tlo, thi, cum, w, segs)
        tup = jnp.stack([a, c, b, f], axis=-3)     # [..., 4, NP, M]
        g = jax.lax.all_gather(tup, "data")        # [d, ..., 4, NP, M]
        og = jax.lax.all_gather(ovf, "data")       # [d, ..., 8, M]

        def tup_at(i):
            s = g[i]
            return (s[..., 0, :k, :], s[..., 1, :k, :],
                    s[..., 2, :k, :], s[..., 3, :k, :] != 0)

        # replicated left fold across the device axis (Fig. 6; d is small
        # and static, so the unrolled loop is one fused XLA computation)
        carry = tup_at(0)
        for i in range(1, d):
            carry = fold_pair(carry, tup_at(i))
        np_ = a.shape[-2]

        def pad_rows(x):
            x = x.astype(jnp.int32)
            if np_ == k:
                return x
            zshape = x.shape[:-2] + (np_ - k, x.shape[-1])
            return jnp.concatenate([x, jnp.zeros(zshape, jnp.int32)],
                                   axis=-2)

        a2_, c2_, b2_, f2_ = (pad_rows(x) for x in carry)
        return a2_, c2_, b2_, f2_, og.max(axis=0)

    seg_spec = P(None, "data") if lanes else P("data")
    in_specs = (P(), P(), P(), P(), P(), seg_spec)
    return jax.jit(jax.shard_map(dev_fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=(P(),) * 5, check_vma=False))


def a1_mapconcat_sharded_tuples(et, tlo, thi, cum, w, segs, *,
                                n_levels: int, lcap: int, interpret: bool,
                                num_devices: int):
    """One mesh-sharded segmented A1 launch (instrumented): ``segs``'s
    leading segment axis must be divisible by ``num_devices``. Same output
    contract as ``a1_mapconcat_tuples`` — the stitched (a, c, b, f) bricks
    plus the ovf rows OR'd over devices."""
    KERNEL_CALLS["a1_mapc_shard"] += num_devices
    fn = _mapc_sharded_fn("a1", n_levels, lcap, interpret, num_devices,
                          lanes=False)
    with annotate("kernel:a1_mapc_shard"):
        return fn(et, tlo, thi, cum, w, segs)


def a2_mapconcat_sharded_tuples(et, tlo, thi, cum, w, segs, *,
                                n_levels: int, interpret: bool,
                                num_devices: int):
    """Single-slot analogue of ``a1_mapconcat_sharded_tuples``."""
    KERNEL_CALLS["a2_mapc_shard"] += num_devices
    fn = _mapc_sharded_fn("a2", n_levels, 0, interpret, num_devices,
                          lanes=False)
    with annotate("kernel:a2_mapc_shard"):
        return fn(et, tlo, thi, cum, w, segs)


def _sharded_segments(stream: EventStream, eps: EpisodeBatch,
                      num_segments: int, d: int):
    """Segment the stream for a d-device launch: at least one segment per
    device, total divisible by d. Returns (tau, wt, wtt) or None when the
    stream is too short to give every device a stitch-safe (> W) segment —
    the caller then takes the single-device path."""
    w_max = int(np.asarray(eps.max_span).max())
    tau, wt, wtt = make_segments(stream, max(num_segments, d), w_max)
    p = wt.shape[0]
    if p < d or p % d:
        return None
    return tau, wt, wtt


def a1_mapconcat_sharded_count(stream: EventStream, eps: EpisodeBatch,
                               num_segments: int = 8,
                               lcap: int = DEFAULT_LCAP,
                               num_devices: int | None = None,
                               force: str | None = None):
    """Mesh-sharded MapConcatenate: one segmented kernel launch per device
    with the per-device tuples all-gathered and folded replicated. Returns
    (counts int64[M], bad bool[M]) exactly like ``a1_mapconcat_count``;
    delegates to the single-device launch when fewer than two devices are
    usable or the stream is too short to shard stitch-safely."""
    interpret = _mode(force)
    if eps.N == 1:
        return (count_level1(stream, eps.etypes[:, 0]),
                np.zeros(eps.M, dtype=bool))
    d = shard_device_count() if num_devices is None else num_devices
    made = (_sharded_segments(stream, eps, num_segments, d)
            if d >= 2 and len(stream) else None)
    if made is None:
        return a1_mapconcat_count(stream, eps, num_segments=num_segments,
                                  lcap=lcap, force=force)
    tau, wt, wtt = made
    et, tlo, thi, cum, w = mapconcat_layout(eps, inclusive_lower=False)
    segs = segment_bricks(wt, wtt, tau)
    _, c, _, f, ovf = a1_mapconcat_sharded_tuples(
        et, tlo, thi, cum, w, segs, n_levels=eps.N, lcap=lcap,
        interpret=interpret, num_devices=d)
    counts = np.asarray(c[0, : eps.M], dtype=np.int64)
    bad = np.asarray((f[0, : eps.M] != 0) | (ovf[0, : eps.M] != 0))
    return counts, bad


def a2_mapconcat_sharded_count(stream: EventStream, eps: EpisodeBatch,
                               num_segments: int = 8,
                               num_devices: int | None = None,
                               force: str | None = None):
    """Mesh-sharded segmented A2 counting (relaxed batch, inclusive-lower
    strengthening) — see ``a2_mapconcat_count`` for the contract."""
    interpret = _mode(force)
    if eps.N == 1:
        return (count_level1(stream, eps.etypes[:, 0]),
                np.zeros(eps.M, dtype=bool))
    d = shard_device_count() if num_devices is None else num_devices
    made = (_sharded_segments(stream, eps, num_segments, d)
            if d >= 2 and len(stream) else None)
    if made is None:
        return a2_mapconcat_count(stream, eps, num_segments=num_segments,
                                  force=force)
    tau, wt, wtt = made
    et, tlo, thi, cum, w = mapconcat_layout(eps, inclusive_lower=True)
    segs = segment_bricks(wt, wtt, tau)
    _, c, _, f, _ = a2_mapconcat_sharded_tuples(
        et, tlo, thi, cum, w, segs, n_levels=eps.N, interpret=interpret,
        num_devices=d)
    counts = np.asarray(c[0, : eps.M], dtype=np.int64)
    bad = np.asarray(f[0, : eps.M] != 0)
    return counts, bad


def a1_mapc_sharded_vmapped(n_levels: int, lcap: int, interpret: bool,
                            num_devices: int):
    """Fused-lane variant of the sharded segmented launch: the per-device
    kernel is vmapped over a leading session axis while the segment axis
    shards over the mesh — the cross-session batcher's multi-device
    MapConcatenate seam. Operands carry a leading lane axis; ``segs`` is
    [S, P, 5, LW] with P divisible by ``num_devices``."""
    return _mapc_sharded_fn("a1", n_levels, lcap, interpret, num_devices,
                            lanes=True)


@functools.lru_cache(maxsize=None)
def a1_mapc_vmapped(n_levels: int, lcap: int, interpret: bool):
    """vmap of the segmented A1 kernel over a leading session axis (the
    cross-session batcher's fused MapConcatenate launch)."""
    f = functools.partial(a1_mapconcat_kernel, n_levels=n_levels, lcap=lcap,
                          interpret=interpret)
    return jax.jit(jax.vmap(f))


def a1_count_stateful(stream: EventStream, eps: EpisodeBatch,
                      state: A1State | None = None,
                      lcap: int = DEFAULT_LCAP, force: str | None = None):
    """Kernel-backed carried A1 chunk (host layout in/out).

    Returns (counts int64[M], ovf bool[M], new ``A1State``) cumulative over
    everything the carried machines have seen. ``eps.N`` must be >= 2
    (callers shortcut N == 1 to the histogram). Exactness caveats are the
    scan engine's: chunk boundaries must not split tie groups, and
    ``ovf``-flagged episodes need a host recount over the concatenated
    history (``StreamingCounter`` automates both).
    """
    interpret = _mode(force)
    if state is None:
        state = init_a1_state(eps, lcap)
    lcap = int(state.s.shape[-1])  # the brick's static capacity
    et, tlo, thi = episode_layout(eps, inclusive_lower=False)
    ev = event_layout(stream, with_dup=True)
    s, po, cnt, ovf = a1_state_layout(state)
    cnt, ovf, s, po = a1_state_call(et, tlo, thi, ev, s, po, cnt, ovf,
                                    n_levels=eps.N, lcap=lcap,
                                    interpret=interpret)
    new_state = a1_state_unpack(s, po, cnt, ovf, eps.M, eps.N)
    return (np.asarray(cnt[0, : eps.M], dtype=np.int64),
            np.asarray(ovf[0, : eps.M] != 0), new_state)


def a2_count_stateful(stream: EventStream, eps: EpisodeBatch,
                      state: A2State | None = None,
                      inclusive_lower: bool = True,
                      force: str | None = None):
    """Kernel-backed carried single-slot chunk (host layout in/out).

    Counts ``eps`` under its *own* bounds (the A2 use passes the relaxed
    batch with ``inclusive_lower=True``, matching ``count_single_slot``).
    Returns (counts int64[M], new ``A2State``); unconditionally bit-exact
    under any chunking (Obs. 5.1). ``eps.N`` must be >= 2.
    """
    interpret = _mode(force)
    if state is None:
        state = init_a2_state(eps)
    et, tlo, thi = episode_layout(eps, inclusive_lower=inclusive_lower)
    ev = event_layout(stream, with_dup=False)
    s, cnt = a2_state_layout(state)
    cnt, s = a2_state_call(et, tlo, thi, ev, s, cnt, n_levels=eps.N,
                           interpret=interpret)
    new_state = a2_state_unpack(s, cnt, eps.M, eps.N)
    return (np.asarray(cnt[0, : eps.M], dtype=np.int64), new_state)
