"""MapConcatenate (paper §5.2.2) — segment-parallel counting.

The stream is split into P (power-of-two) time segments; each segment runs
K = N phase-shifted A1 machines per episode — machine k starts
``Σ_{i≤k} thi^i`` *before* the segment boundary, covering the k-events-before
/ (N-k)-after boundary splits of Fig. 4. Each machine emits a tuple
``(a, count, b)`` (Fig. 5):

  a — end time of its first completion in ``(τ_p, τ_p + W]``  (else τ_p)
  count — completions with end time in ``(τ_p, τ_{p+1}]``
  b — end time of its first completion after τ_{p+1}, found by crossing into
      the next segment up to ``τ_{p+1} + W`` inclusive  (else τ_{p+1})
      — inclusive because an occurrence spanning exactly W from a first
      event exactly on the boundary completes at ``τ + W``; excluding that
      tick made both sides blind to the straddler and the stitch silently
      continued with the wrong phase machine (no flag, undercount)

Machines reset on every completion (non-overlap), which makes them memoryless
at completion points — that is what lets a log₂(P) Concatenate tree stitch
adjacent tuples by matching ``b_left == a_right`` (Fig. 6).

The paper argues (but does not prove) that one of the N phases always
reproduces the reference trajectory; we additionally track an ``unmatched``
flag through the tree and recount flagged episodes with the single-scan
engine, so the public API is exact even on adversarial streams.

Distribution: ``mapconcatenate_sharded`` shard_maps the XLA Map step over
the mesh ``data`` (= segment) axis; the (a, count, b) tuples are O(P·N)
scalars, so the Concatenate tree runs replicated after an ``all_gather`` —
the TPU analogue of the paper's single-kernel-launch concatenate.
``mapconcatenate_sharded_kernel`` is the production form: one segmented
*Pallas* launch per device (its contiguous segment group, in-group fold
fused on-chip) with only the pre-stitched per-device tuples all-gathered
for the replicated final fold (``kernels.ops.a1_mapconcat_sharded_count``).

On-chip: ``mapconcatenate_kernel`` routes the whole computation into one
Pallas launch (``kernels/a1_count.a1_mapconcat_kernel``) whose grid is
(episode tile × time segment) with the Concatenate fold fused across the
segment axis — the literal single-kernel-launch form. The shared pieces
that keep the kernel and XLA paths from drifting live here:
``phase_cum`` (machine start offsets), ``stitch_zones`` (the
boundary-inclusive a/b/count zones), and ``fold_pair_unrolled`` (the
gather-free first-match stitch, bit-identical to ``fold_pair``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tally import KernelDeclined, record_fallback

from .count_a1 import DEFAULT_LCAP, count_a1 as _count_a1_exact, \
    dup_flags, step_bounded_list
from .episodes import EpisodeBatch
from .events import PAD_TYPE, TIME_NEG_INF, EventStream, count_level1


# ---------------------------------------------------------------- Map step


def phase_cum(thi):
    """Per-phase start offsets: ``cum[m, k] = Σ_{i<k} thi[m, i]`` — machine
    ``k`` of episode ``m`` starts that many ticks before the segment
    boundary (Fig. 4's k-before/(N-k)-after split coverage). Single source
    of truth for the XLA Map step, the sharded Map step, and the Pallas
    segmented kernels' ``cum`` brick (``kernels.ops.mapconcat_layout``)."""
    thi = jnp.asarray(thi)
    return jnp.cumsum(
        jnp.concatenate([jnp.zeros_like(thi[:, :1]), thi], axis=1), axis=1)


def stitch_zones(t, tau_lo, tau_hi, w):
    """Boundary-inclusive tuple zones for one event time ``t`` against the
    segment ``(tau_lo, tau_hi]`` with per-episode max span ``w``.

    Returns (in_seg, a_zone, live_zone, crossing):
      in_seg   — completion counts toward this segment's ``count``
      a_zone   — completion may be recorded as the tuple's first-``a``
      live_zone — the segment's machines may still consume this event
      crossing — completion is a ``b``-crossing into the next segment

    The ``a``/``live`` zones are inclusive at ``tau + w``: an occurrence
    spanning exactly W whose first event sits exactly on the boundary
    completes at ``tau + W``, and both sides of the stitch must see it (the
    PR 1 silent-undercount fix). Shared by ``_segment_scan`` and the Pallas
    segmented kernels (``kernels/a1_count._a1_mapc_body`` /
    ``a2_count._a2_mapc_body``) so the two paths cannot drift.
    """
    in_seg = (t > tau_lo) & (t <= tau_hi)
    a_zone = t <= tau_lo + w
    live_zone = t <= tau_hi + w
    crossing = t > tau_hi
    return in_seg, a_zone, live_zone, crossing


def _segment_scan(ev_types, ev_times, etypes, tlo, thi, starts, tau_lo,
                  tau_hi, w, lcap):
    """Run K phase machines × M episodes over one segment's event window.

    Args:
      ev_types/ev_times: i32[Lw] (padded with PAD_TYPE)
      etypes: i32[M, N]; tlo/thi: i32[M, N-1]
      starts: i32[K, M] phase start times (machine ignores events at t<=start)
      tau_lo, tau_hi: scalar i32 segment boundaries (τ_p, τ_{p+1}]
      w: i32[M] max occurrence span per episode
      lcap: static list capacity

    Returns (a, count, b, ovf): each [K, M] (ovf bool).
    """
    k, m = starts.shape
    n = etypes.shape[1]
    # derive inits from tau so carries are device-varying under shard_map
    # (numerically a no-op: vzero == 0, vfalse == False)
    vzero = (tau_lo * 0).astype(jnp.int32)
    vfalse = tau_lo != tau_lo
    s0 = jnp.full((k, m, n, lcap), TIME_NEG_INF, jnp.int32) + vzero
    ptr0 = jnp.zeros((k, m, n), jnp.int32) + vzero
    cnt0 = jnp.zeros((k, m), jnp.int32) + vzero
    ovf0 = jnp.zeros((k, m), jnp.bool_) | vfalse
    a0 = jnp.full((k, m), tau_lo, jnp.int32)
    b0 = jnp.full((k, m), tau_hi, jnp.int32)
    done0 = jnp.zeros((k, m), jnp.bool_) | vfalse
    a_set0 = jnp.zeros((k, m), jnp.bool_) | vfalse

    step = jax.vmap(  # over phases; episode dim handled inside the step
        step_bounded_list,
        in_axes=(0, 0, 0, 0, None, None, None, None, None, None))
    dups = dup_flags(ev_types, ev_times)

    def body(carry, ev):
        s, ptr, cnt, ovf, a, b, done, a_set = carry
        e, t, d = ev
        # zone predicates shared with the Pallas segmented kernels (see
        # stitch_zones for the tau + W inclusivity that PR 1 fixed)
        seg_z, a_z, live_z, cross_z = stitch_zones(t, tau_lo, tau_hi,
                                                   w[None, :])
        in_window = (t > starts) & live_z & ~done  # [K, M]
        # Run the raw machine step, then mask its effects per (phase, episode)
        s2, ptr2, cdelta, ovf2 = step(s, ptr, jnp.zeros_like(cnt), ovf,
                                      etypes, tlo, thi, e, t, d)
        complete = (cdelta > 0) & in_window
        live = in_window & (e != PAD_TYPE)
        s = jnp.where(live[:, :, None, None], s2, s)
        ptr = jnp.where(live[:, :, None], ptr2, ptr)
        ovf = jnp.where(live, ovf2, ovf)
        # bookkeeping on completions
        in_seg = complete & seg_z
        cnt = cnt + in_seg.astype(cnt.dtype)
        rec_a = in_seg & ~a_set & a_z
        a = jnp.where(rec_a, t, a)
        a_set = a_set | rec_a
        crossing = complete & cross_z
        b = jnp.where(crossing, t, b)
        done = done | crossing
        return (s, ptr, cnt, ovf, a, b, done, a_set), None

    carry0 = (s0, ptr0, cnt0, ovf0, a0, b0, done0, a_set0)
    (s, ptr, cnt, ovf, a, b, done, a_set), _ = jax.lax.scan(
        body, carry0, (ev_types, ev_times, dups))
    return a, cnt, b, ovf


# ------------------------------------------------------- Concatenate step


def fold_pair(left, right):
    """Stitch adjacent tuple blocks (paper Fig. 6, one tree level).

    ``left``/``right`` are (a, c, b, flag) with shape [..., K, M] — the K
    axis is the phase-machine axis, any leading axes broadcast (the balanced
    tree passes [P/2, K, M]; the streaming left-fold passes [K, M]). Matches
    left machine k's crossing end-time ``b`` against the right block's first
    in-zone completions ``a`` and returns the merged block. The operation is
    associative, which is what lets the streaming engine replace the
    balanced tree with an incremental left fold over arriving windows.
    """
    al, cl, bl, fl = left
    ar, cr, br, fr = right
    eq = bl[..., :, None, :] == ar[..., None, :, :]  # [..., K, K', M]
    matched = eq.any(axis=-2)  # [..., K, M]
    idx = jnp.argmax(eq, axis=-2)  # [..., K, M] first matching k'
    cr_g = jnp.take_along_axis(cr, idx, axis=-2)
    br_g = jnp.take_along_axis(br, idx, axis=-2)
    fr_g = jnp.take_along_axis(fr, idx, axis=-2)
    return al, cl + cr_g, br_g, fl | fr_g | ~matched


def fold_pair_unrolled(left, right, k: int):
    """``fold_pair`` restricted to [K, M] blocks with the first-match select
    unrolled over the (static, small) phase axis — no ``argmax`` /
    ``take_along_axis`` gathers, so it lowers inside a Pallas kernel.

    Bit-identical to ``fold_pair``: the reversed ``where`` sweep keeps the
    *lowest* matching k' (argmax-of-bool semantics), and an unmatched left
    machine falls through to the k' = 0 entries exactly as ``argmax`` over
    an all-false column does — garbage count, but flagged. The segmented
    kernels' fused Concatenate stage is this fold applied left-to-right
    across the segment grid axis (associativity per ``fold_pair``).
    """
    al, cl, bl, fl = left
    ar, cr, br, fr = right
    matched = jnp.zeros_like(fl)
    cr_g = jnp.broadcast_to(cr[0:1], cl.shape)
    br_g = jnp.broadcast_to(br[0:1], bl.shape)
    fr_g = jnp.broadcast_to(fr[0:1], fl.shape)
    for kp in range(k - 1, -1, -1):
        sel = bl == ar[kp:kp + 1]  # [K, M]
        matched = matched | sel
        cr_g = jnp.where(sel, cr[kp:kp + 1], cr_g)
        br_g = jnp.where(sel, br[kp:kp + 1], br_g)
        fr_g = jnp.where(sel, fr[kp:kp + 1], fr_g)
    return al, cl + cr_g, br_g, fl | fr_g | ~matched


def concatenate_tree(a, c, b, flag):
    """Fold P segments' tuples pairwise, log2(P) levels (paper Fig. 6).

    Args: a/c/b: i32[P, K, M]; flag: bool[P, K, M] (unmatched-so-far).
    Returns (count i32[M], bad bool[M]) for the phase-0 leftmost machine.
    """
    p = a.shape[0]
    while p > 1:
        a, c, b, flag = fold_pair(
            (a[0::2], c[0::2], b[0::2], flag[0::2]),
            (a[1::2], c[1::2], b[1::2], flag[1::2]))
        p //= 2
    return c[0, 0], flag[0, 0]


# ------------------------------------------------------------- public API


def make_segments(stream: EventStream, num_segments: int, w_max: int):
    """Host-side segmentation: boundaries + padded per-segment event windows.

    Segment p covers (τ_p, τ_{p+1}]; its window additionally includes the
    lookback (τ_p - W) and crossing zone (τ_{p+1} + W). Returns
    (tau i64[P+1], types i32[P, Lw], times i32[P, Lw]).
    """
    t0, t1 = stream.span
    total = max(int(t1 - t0), 1)
    p = max(num_segments, 1)
    while p > 1 and total // p <= max(w_max, 1):
        p //= 2  # keep segment length > W so zones don't overlap boundaries
    tau = np.round(np.linspace(t0 - 1, t1, p + 1)).astype(np.int64)
    real = stream.types != PAD_TYPE
    ts = stream.times[real]
    tys = stream.types[real]
    windows = []
    for i in range(p):
        lo = np.searchsorted(ts, tau[i] - w_max, side="right")
        hi = np.searchsorted(ts, tau[i + 1] + w_max, side="right")
        windows.append((lo, hi))
    lw = max(hi - lo for lo, hi in windows) if windows else 1
    wt = np.full((p, lw), PAD_TYPE, np.int32)
    wtt = np.full((p, lw), 0, np.int32)
    for i, (lo, hi) in enumerate(windows):
        wt[i, : hi - lo] = tys[lo:hi]
        wtt[i, : hi - lo] = ts[lo:hi]
    return tau, wt, wtt


@functools.partial(jax.jit, static_argnames=("lcap",))
def _map_all_segments(wt, wtt, etypes, tlo, thi, tau, w, lcap):
    """vmap the Map step over P segments. Returns a/c/b [P,K,M] + ovf."""
    n = etypes.shape[1]
    cum = phase_cum(thi)  # [M, N] — Σ_{i<k} thi^i
    tau32 = tau.astype(jnp.int32)

    def one_segment(ev_t, ev_tt, tau_lo, tau_hi):
        starts = (tau_lo - cum.T).astype(jnp.int32)  # [K=N, M]
        return _segment_scan(ev_t, ev_tt, etypes, tlo, thi, starts, tau_lo,
                             tau_hi, w, lcap)

    return jax.vmap(one_segment)(wt, wtt, tau32[:-1], tau32[1:])


def shard_device_count() -> int:
    """Largest power-of-two device count the segment axis can shard over
    (segment counts are powers of two, so a ragged mesh would idle
    devices); 1 means the sharded paths stand down. Single source of
    truth for every sharded dispatch decision — ``kernels.ops``,
    ``hybrid.shard_devices``, and the mesh builders all delegate here so
    the kernel path, the XLA fallback, and the launcher mesh can never
    disagree on the device set."""
    import jax
    d = jax.device_count()
    p = 1
    while p * 2 <= d:
        p *= 2
    return p


def data_mesh(num_devices: int | None = None):
    """1-D ``("data",)`` mesh over the first ``num_devices`` (default:
    ``shard_device_count()``) devices — the mesh the sharded
    streaming/counting paths shard segments over
    (``launch.mesh.make_stream_mesh`` re-exports this for launchers)."""
    import jax
    from jax.sharding import Mesh

    if num_devices is None:
        num_devices = shard_device_count()
    return Mesh(np.array(jax.devices()[:num_devices]), ("data",))


def mapconcatenate_sharded(stream: EventStream, eps: EpisodeBatch,
                           mesh=None, axis: str = "data",
                           lcap: int = DEFAULT_LCAP,
                           use_kernel: bool = False) -> np.ndarray:
    """Distributed MapConcatenate: the Map step shard_maps over the mesh
    ``axis`` (one segment per device — the paper's one-thread-block-per-
    segment), the O(P·N) tuples are all_gather'd, and the Concatenate tree
    folds replicated. Exactness fallback as in ``mapconcatenate``;
    ``use_kernel`` selects the fallback engine. ``mesh=None`` builds the
    default power-of-two ``data`` mesh (``data_mesh``)."""
    import jax
    from jax.sharding import PartitionSpec as P

    if eps.N == 1:
        return count_level1(stream, eps.etypes[:, 0])
    if mesh is None:
        mesh = data_mesh()
    p = mesh.shape[axis]
    w = eps.max_span
    w_max = int(w.max())
    tau, wt, wtt = make_segments(stream, p, w_max)
    if wt.shape[0] != p:  # stream too short for p segments — fall back
        return mapconcatenate(stream, eps, num_segments=wt.shape[0],
                              lcap=lcap, use_kernel=use_kernel)
    n = eps.N
    cum = np.asarray(phase_cum(eps.thi))  # [M, N]
    taus = np.stack([tau[:-1], tau[1:]], axis=1).astype(np.int32)  # [P, 2]

    def map_step(ev_t, ev_tt, tau_pair):
        # one segment per device; [1, ...] block shapes from shard_map
        ev_t, ev_tt, tau_pair = ev_t[0], ev_tt[0], tau_pair[0]
        starts = (tau_pair[0] - jnp.asarray(cum).T).astype(jnp.int32)
        a, c, b, ovf = _segment_scan(
            ev_t, ev_tt, jnp.asarray(eps.etypes), jnp.asarray(eps.tlo),
            jnp.asarray(eps.thi), starts, tau_pair[0], tau_pair[1],
            jnp.asarray(w, jnp.int32), lcap)
        out = jnp.stack([a, c, b, ovf.astype(jnp.int32)])[None]  # [1,4,K,M]
        return jax.lax.all_gather(out, axis, axis=0, tiled=True)

    fn = jax.shard_map(map_step, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(None), check_vma=False)
    gathered = np.asarray(jax.jit(fn)(
        jnp.asarray(wt), jnp.asarray(wtt), jnp.asarray(taus)))  # [P,4,K,M]
    a, c, b, ovf = (jnp.asarray(gathered[:, i]) for i in range(4))
    flag0 = jnp.zeros(a.shape, jnp.bool_)
    count, bad = concatenate_tree(a, c, b, flag0)
    count = np.asarray(count, np.int64)
    bad = np.asarray(bad) | np.asarray(ovf.astype(bool).any(axis=(0, 1)))
    if bad.any():
        idx = np.nonzero(bad)[0]
        count = count.copy()
        count[idx] = _count_a1_exact(stream, eps.select(idx), lcap=lcap,
                                     use_kernel=use_kernel)
    return count


def mapconcatenate(stream: EventStream, eps: EpisodeBatch,
                   num_segments: int = 8,
                   lcap: int = DEFAULT_LCAP,
                   use_kernel: bool = False) -> np.ndarray:
    """Exact A1 counts via segment-parallel Map + Concatenate tree.

    Falls back to the single-scan engine for episodes whose tuples failed to
    stitch or whose bounded lists flagged a live eviction; ``use_kernel``
    controls whether that fallback may take the Pallas kernel path (plumbed
    from ``hybrid.count_dispatch`` so hybrid/mapconcatenate callers steer the
    fallback the same way ptpe callers do).
    """
    if eps.N == 1:
        return count_level1(stream, eps.etypes[:, 0])
    w = eps.max_span
    w_max = int(w.max())
    tau, wt, wtt = make_segments(stream, num_segments, w_max)
    a, c, b, ovf = _map_all_segments(
        jnp.asarray(wt), jnp.asarray(wtt), jnp.asarray(eps.etypes),
        jnp.asarray(eps.tlo), jnp.asarray(eps.thi), jnp.asarray(tau),
        jnp.asarray(w, dtype=jnp.int32), lcap)
    flag0 = jnp.zeros(a.shape, jnp.bool_)
    count, bad = concatenate_tree(a, c, b, flag0)
    count = np.asarray(count, np.int64)
    bad = np.asarray(bad) | np.asarray(ovf.any(axis=(0, 1)))
    if bad.any():
        idx = np.nonzero(bad)[0]
        count = count.copy()
        count[idx] = _count_a1_exact(stream, eps.select(idx), lcap=lcap,
                                     use_kernel=use_kernel)
    return count


def mapconcatenate_sharded_kernel(stream: EventStream, eps: EpisodeBatch,
                                  num_segments: int = 8,
                                  lcap: int = DEFAULT_LCAP,
                                  use_kernel: bool = True,
                                  num_devices: int | None = None
                                  ) -> np.ndarray:
    """Mesh-sharded in-kernel MapConcatenate — the cross-device half of
    the paper's mapping: the committed span is cut into one contiguous
    segment group per mesh ``data`` device, each device runs ONE segmented
    Pallas launch (grid = episode tile × local segments, in-group
    Concatenate fused on-chip — the same ``a1_mapconcat_kernel`` brick the
    single-device path uses), the O(P·N) per-device (a, count, b) tuples
    are all-gathered, and the final stitch folds replicated
    (``fold_pair`` is associative across arbitrary cut points, which is
    what makes the device boundaries invisible in the counts).

    Exactness containment is identical to ``mapconcatenate``: unmatched
    stitches and possibly-live evictions are recounted by the exact
    single-scan engine. Degrades gracefully — kernel dispatch declined
    (CPU without interpret mode) falls to the XLA shard_map Map step when
    a multi-device mesh exists and to plain ``mapconcatenate`` otherwise;
    fewer than two usable devices (or a stream too short to give every
    device a stitch-safe segment) falls to the single-device kernel. Same
    counts on every path.
    """
    if eps.N == 1:
        return count_level1(stream, eps.etypes[:, 0])
    try:
        from repro.kernels import ops as kops
        count, bad = kops.a1_mapconcat_sharded_count(
            stream, eps, num_segments=num_segments, lcap=lcap,
            num_devices=num_devices)
    except KernelDeclined:
        record_fallback("mapc_sharded")
        d = shard_device_count() if num_devices is None else num_devices
        if d >= 2:
            return mapconcatenate_sharded(stream, eps, mesh=data_mesh(d),
                                          lcap=lcap, use_kernel=use_kernel)
        return mapconcatenate(stream, eps, num_segments=num_segments,
                              lcap=lcap, use_kernel=use_kernel)
    if bad.any():
        idx = np.nonzero(bad)[0]
        count = count.copy()
        count[idx] = _count_a1_exact(stream, eps.select(idx), lcap=lcap,
                                     use_kernel=use_kernel)
    return count


def mapconcatenate_kernel(stream: EventStream, eps: EpisodeBatch,
                          num_segments: int = 8,
                          lcap: int = DEFAULT_LCAP,
                          use_kernel: bool = True) -> np.ndarray:
    """In-kernel MapConcatenate: one Pallas launch whose grid is
    (episode tile × time segment) runs the Map step's K = N phase machines
    per segment *and* the Concatenate fold on-chip
    (``kernels.a1_count.a1_mapconcat_kernel``), so the time axis is a grid
    axis instead of one long serial ``fori_loop`` and each segment's event
    window is DMA'd per grid step instead of the whole stream being
    broadcast-resident.

    Exactness containment is identical to ``mapconcatenate``: episodes whose
    tuples failed to stitch (``unmatched``) or whose bounded lists flagged a
    live eviction are recounted by the exact single-scan engine. When the
    kernel dispatch policy declines (CPU without interpret mode), falls back
    to the XLA ``mapconcatenate`` — same counts either way.
    """
    if eps.N == 1:
        return count_level1(stream, eps.etypes[:, 0])
    try:
        from repro.kernels import ops as kops
        count, bad = kops.a1_mapconcat_count(stream, eps,
                                             num_segments=num_segments,
                                             lcap=lcap)
    except KernelDeclined:
        record_fallback("mapc_kernel")
        return mapconcatenate(stream, eps, num_segments=num_segments,
                              lcap=lcap, use_kernel=use_kernel)
    if bad.any():
        idx = np.nonzero(bad)[0]
        count = count.copy()
        count[idx] = _count_a1_exact(stream, eps.select(idx), lcap=lcap,
                                     use_kernel=use_kernel)
    return count
