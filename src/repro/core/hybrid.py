"""Hybrid dispatcher — paper Algorithm 2 / Eq. 2.

Choose PTPE (episode-parallel single scan) when there are enough episodes to
saturate the machine, else MapConcatenate (segment-parallel). The paper's
utilization bound ``S > MP × B_MP × T_B × f(N)`` translates on TPU to
"enough episode lanes per core": our unit of episode parallelism is a
VPU lane tile (128 episodes), and segment parallelism is worth its
concatenate overhead only below ``U × f(N)`` episodes with the paper's
empirically fitted ``f(N) = a/N + b`` (Fig. 8 — the *reciprocal* fit beat
the linear one; we re-fit a, b on this host in benchmarks/fig8).
"""

from __future__ import annotations

from repro.kernels.tally import KernelDeclined, record_fallback

from .count_a1 import A1State, DEFAULT_LCAP, count_a1 as _count_a1
from .mapconcat import (
    mapconcatenate as _mapconcatenate,
    mapconcatenate_kernel as _mapconcatenate_kernel,
    mapconcatenate_sharded_kernel as _mapconcatenate_sharded_kernel)
from .episodes import EpisodeBatch
from .events import EventStream

# Re-fit by benchmarks/fig8_crossover.py (written into EXPERIMENTS.md §Paper);
# defaults follow the paper's shape: crossover shrinks with episode size.
FN_A = 420.0
FN_B = 40.0

# Auto-selection of the in-kernel MapConcatenate by stream length: streams
# at least this long amortize the segmented grid's launch/layout overhead
# (the serial per-segment event walk shrinks to ~n/P while PTPE's stays n).
MAPC_KERNEL_MIN_EVENTS = 2048
# ... and by episode count: below one VPU lane tile, episode parallelism
# cannot fill even a single core's lanes, so the time axis must supply the
# parallelism — the paper's low-M regime where MapConcatenate wins (Fig. 7)
MAPC_KERNEL_MAX_EPISODES = 128


# Probe result cached per process: the answer (TPU present / interpret
# mode) cannot change mid-process, and the old per-dispatch re-probe both
# re-imported the kernel plane on every call and tallied
# ``fallback:hybrid_mapc_probe`` once per *dispatch* on CPU hosts —
# inflating the fallback family and taxing the hybrid's hot path.
_PROBE_CACHE: bool | None = None


def _mapc_kernel_available() -> bool:
    """Whether the segmented-kernel dispatch would actually engage (TPU or
    interpret mode) — the hybrid upgrade must not silently reroute plain
    CPU runs onto the slower XLA MapConcatenate.  Probed once per
    process; the degradation is tallied once, not per dispatch."""
    global _PROBE_CACHE
    if _PROBE_CACHE is None:
        try:
            from repro.kernels import ops as kops
            kops.kernel_mode()
            _PROBE_CACHE = True
        except KernelDeclined:
            record_fallback("hybrid_mapc_probe")
            _PROBE_CACHE = False
    return _PROBE_CACHE


def _reset_probe_cache() -> None:
    """Test hook: forget the cached probe (e.g. after flipping the
    interpret-mode environment)."""
    global _PROBE_CACHE
    _PROBE_CACHE = None


def shard_devices() -> int:
    """Power-of-two device count the segment axis can shard over (1 on a
    single-device host — the sharded mapping then stands down)."""
    from .mapconcat import shard_device_count
    return shard_device_count()


def parallel_units() -> int:
    """Segment-parallel capacity — the paper's MP×B_MP×T_B term is the
    machine's parallel slots; ours is the device count the Map step can
    shard over. On a single device MapConcatenate has no hardware to use
    (fig7: PTPE wins at every M there, with up to 10× dispatcher regret
    under a mis-tuned constant — hence capacity-aware, not fixed)."""
    import jax
    return jax.device_count()


def f_of_n(n: int, a: float = FN_A, b: float = FN_B) -> float:
    return a / max(n, 1) + b


def crossover(n: int) -> int:
    """#episodes above which PTPE wins (Eq. 2 RHS).

    The capacity term is the machine's *segment-parallel* slots beyond
    the one PTPE always gets.  On a single-device host that difference
    is 0 — but only honestly so when the segmented kernel cannot engage:
    with the kernel available, one device still runs the (episode tile ×
    time segment) grid, so the segment axis has one real unit of its own
    and the crossover is ``f(N)`` rather than a degenerate 0 that
    declares episode-parallel the winner at every M regardless of
    ``f(N)``.  (The calibrated policy supersedes this entirely when a
    table is installed.)"""
    units = parallel_units()
    if units <= 1:
        units = 2 if _mapc_kernel_available() else 1
    return int((units - 1) * f_of_n(n))


def count_dispatch(stream: EventStream, eps: EpisodeBatch,
                   engine: str = "hybrid", use_kernel: bool = True,
                   num_segments: int = 8, lcap: int = DEFAULT_LCAP,
                   state: A1State | None = None,
                   return_state: bool = False):
    """Exact A1 counts through the selected computation-to-core mapping.

    Engines: ``"ptpe"`` (episode-parallel single scan),
    ``"mapconcatenate"`` (segment-parallel XLA Map + Concatenate tree),
    ``"mapconcat_kernel"`` (the in-kernel MapConcatenate — one Pallas
    launch whose grid is episode tile × time segment with the Concatenate
    fold fused on-chip; falls back to the XLA mapping bit-identically when
    the kernel dispatch declines), ``"mapconcat_sharded"`` (the
    multi-device form — one segmented Pallas launch per mesh ``data``
    device with the per-device tuples all-gathered and folded replicated;
    degrades to the single-device kernel, the XLA shard_map Map step, or
    plain ``mapconcatenate``, bit-identically, as devices/kernels become
    unavailable), or ``"hybrid"`` (Eq. 2 dispatcher — which additionally
    upgrades the segment-parallel side to the kernel mapping on streams of
    >= ``MAPC_KERNEL_MIN_EVENTS`` events when ``use_kernel`` is set, and
    to the *sharded* kernel mapping when the mesh has more than one
    usable device).

    ``use_kernel`` and ``lcap`` are plumbed into every mapping — including
    MapConcatenate's exactness fallback — so hybrid/mapconcatenate callers
    control the fallback engine the same way ptpe callers do.

    Stateful mode (``state``/``return_state``) carries the bounded-list
    machines across calls and returns ``(counts, A1State)`` with cumulative
    raw counts (see ``count_a1`` — with ``use_kernel`` the chunk runs
    through the state-in/state-out Pallas kernel when available).
    Cross-window machine carry is inherently a single sequential scan, so
    every engine routes to the carried ptpe step here; segment-parallel
    *streaming* (the tuple-fold analogue of MapConcatenate) lives in
    ``streaming.StreamingCounter``, which callers should prefer for
    window-by-window workloads.
    """
    # validate before the stateful early-return: a bogus engine must raise,
    # not silently count via the carried ptpe path
    if engine not in ("ptpe", "mapconcatenate", "mapconcat_kernel",
                      "mapconcat_sharded", "hybrid"):
        raise ValueError(f"unknown engine {engine!r}")
    if state is not None or return_state:
        return _count_a1(stream, eps, lcap=lcap, use_kernel=use_kernel,
                         state=state, return_state=True)
    if engine == "ptpe":
        return _count_a1(stream, eps, lcap=lcap, use_kernel=use_kernel)
    if engine == "mapconcat_sharded":
        return _mapconcatenate_sharded_kernel(
            stream, eps, num_segments=num_segments, lcap=lcap,
            use_kernel=use_kernel)
    if engine == "mapconcat_kernel":
        return _mapconcatenate_kernel(stream, eps, num_segments=num_segments,
                                      lcap=lcap, use_kernel=use_kernel)
    if engine == "mapconcatenate":
        return _mapconcatenate(stream, eps, num_segments=num_segments,
                               lcap=lcap, use_kernel=use_kernel)
    # hybrid: consult the dispatch policy — the calibrated cost table
    # when one is installed (core.calibrate), else exactly the Eq. 2
    # heuristic above (the policy's heuristic branch replicates it, so
    # behavior without a table is unchanged).  Results are bit-identical
    # across engines; only wall clock rides on this choice.
    from .calibrate import get_policy
    choice = get_policy().choose(
        n_events=len(stream), n_episode=eps.N, m=eps.M,
        use_kernel=use_kernel,
        kernel_ok=use_kernel and _mapc_kernel_available(),
        shard_devices=shard_devices(), default_segments=num_segments)
    if choice.engine == "ptpe":
        return _count_a1(stream, eps, lcap=lcap, use_kernel=use_kernel)
    if choice.engine == "mapconcat_sharded":
        # multi-device: each mesh device takes one segment group —
        # throughput scales with hardware, not just segment count
        return _mapconcatenate_sharded_kernel(
            stream, eps, num_segments=choice.num_segments, lcap=lcap,
            use_kernel=use_kernel)
    if choice.engine == "mapconcat_kernel":
        return _mapconcatenate_kernel(
            stream, eps, num_segments=choice.num_segments, lcap=lcap,
            use_kernel=use_kernel)
    return _mapconcatenate(stream, eps, num_segments=choice.num_segments,
                           lcap=lcap, use_kernel=use_kernel)
