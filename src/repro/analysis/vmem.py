"""Pass 3 — static VMEM budget checker (rules VM301–VM304).

Recomputes, from the layout contracts alone, the VMEM-resident bytes of
each Pallas launch the dispatch policy can admit — the same arithmetic
the BlockSpecs and scratch shapes in ``kernels/a1_count.py`` /
``a2_count.py`` imply — and fails any admitted configuration whose
footprint exceeds the budget. The event and segment bricks stream
through SMEM in chunks of at most ``DEFAULT_BLOCK_E`` events (the kernels
read events as scalars), so no VMEM term grows with the stream or the
segment window; the window length only has to agree with the chunk
quantum (VM303). The compiler has the final say:
``tests/test_tpu_compile.py`` compiles the kernels for a described v5e.

The model is deliberately conservative:
  * every operand block is counted on both the input and output side
    (aliased pairs included — Mosaic still windows both),
  * operand blocks are doubled for the pipeline's double buffering, and
  * the segmented kernels' machine-state scratch (K = N phases × LCAP
    slot rows, K pointer rows, the phase-row brick) is counted once.

Per-block bytes = prod(block shape) × 4 (the counting plane is i32-only,
enforced by the Pass 2 dtype rule).

The *admitted envelope* (``ADMITTED``) is part of the audited policy: it
mirrors what dispatch actually accepts today (N padded to sublanes up to
``MAX_N``, ``lcap`` up to ``MAX_LCAP``, event chunks up to
``DEFAULT_BLOCK_E``, segment windows up to ``MAX_SEG_BRICK_LW``).
Widening the envelope without budget headroom turns the audit red before
it can turn a run red.
"""

from __future__ import annotations

from .findings import Finding

# layout constants — mirrors kernels/a2_count.py (the analysis plane must
# not import the jax kernel stack; audited against it in tests)
LANES = 128
SUBLANES = 8
SEG_ROWS = 5
MAPC_ROWS = 6  # per-phase rows of the segmented kernels' scratch
DEFAULT_BLOCK_E = 1024

ITEM_BYTES = 4    # i32 everywhere in the counting plane
DOUBLE_BUF = 2    # Pallas pipeline double buffering

# ~16 MiB of VMEM per TPU core; leave 1 MiB headroom for Mosaic
# scratch/semaphores the block model cannot see
VMEM_BUDGET_BYTES = 15 * (1 << 20)

# admitted dispatch envelope (see module docstring)
MAX_N = 16
MAX_LCAP = 16

_POLICY_PATH = "repro/kernels/ops.py"  # where the admission policy lives


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def _blocks_bytes(blocks) -> int:
    total = 0
    for shape in blocks:
        n = 1
        for d in shape:
            n *= d
        total += n
    return total * ITEM_BYTES * DOUBLE_BUF


def a1_state_footprint(n_levels: int, lcap: int,
                       block_m: int = LANES) -> int:
    """VMEM bytes of one ``a1_count_state_kernel`` grid step."""
    np_ = _round_up(max(n_levels, 1), SUBLANES)
    ins = ([(np_, block_m)] * 3            # et / tlo / thi
           + [(np_, lcap, block_m)] * 2    # s / po bricks
           + [(SUBLANES, block_m)] * 2)    # cnt / ovf
    outs = ([(SUBLANES, block_m)] * 2      # cnt / ovf
            + [(np_, lcap, block_m)] * 2)  # s / po (aliased)
    return _blocks_bytes(ins + outs)


def a2_state_footprint(n_levels: int, block_m: int = LANES) -> int:
    """VMEM bytes of one ``a2_count_state_kernel`` grid step."""
    np_ = _round_up(max(n_levels, 1), SUBLANES)
    ins = ([(np_, block_m)] * 3            # et / tlo / thi
           + [(np_, block_m)]              # s tile
           + [(SUBLANES, block_m)])        # cnt
    outs = [(SUBLANES, block_m), (np_, block_m)]
    return _blocks_bytes(ins + outs)


def mapconcat_footprint(n_levels: int, lcap: int = MAX_LCAP,
                        block_m: int = LANES) -> int:
    """VMEM bytes of one segmented (MapConcatenate) kernel grid step: the
    windowed operands plus the machine-state scratch of the A1 variant
    (the A2 variant's single slot per phase is smaller)."""
    np_ = _round_up(max(n_levels, 1), SUBLANES)
    ins = ([(np_, block_m)] * 4            # et / tlo / thi / cum
           + [(SUBLANES, block_m)])        # w
    outs = [(np_, block_m)] * 4 + [(SUBLANES, block_m)]
    k = n_levels
    scratch = (k * lcap + k + MAPC_ROWS) * np_ * block_m * ITEM_BYTES
    return _blocks_bytes(ins + outs) + scratch


def check_vmem(max_seg_brick_lw: int,
               budget: int = VMEM_BUDGET_BYTES):
    """Sweep the admitted dispatch envelope against the VMEM budget.

    ``max_seg_brick_lw`` is the admission bound the runtime enforces
    (``ops.MAX_SEG_BRICK_LW`` — passed in so this module stays
    import-light). Returns (findings, summary).
    """
    findings: list[Finding] = []
    worst = {"a1_state": 0, "a2_state": 0, "mapconcat": 0}

    for n in range(2, MAX_N + 1):
        for lcap in (4, 8, MAX_LCAP):
            b = a1_state_footprint(n, lcap)
            worst["a1_state"] = max(worst["a1_state"], b)
            if b > budget:
                findings.append(Finding(
                    "VM301", _POLICY_PATH, 0,
                    f"a1 state launch (N={n}, lcap={lcap}) needs "
                    f"{b / 2**20:.1f} MiB VMEM > budget "
                    f"{budget / 2**20:.1f} MiB"))
        b = a2_state_footprint(n)
        worst["a2_state"] = max(worst["a2_state"], b)
        if b > budget:
            findings.append(Finding(
                "VM301", _POLICY_PATH, 0,
                f"a2 state launch (N={n}) needs {b / 2**20:.1f} MiB "
                f"VMEM > budget {budget / 2**20:.1f} MiB"))
        b = mapconcat_footprint(n)
        worst["mapconcat"] = max(worst["mapconcat"], b)
        if b > budget:
            findings.append(Finding(
                "VM302", _POLICY_PATH, 0,
                f"segmented launch (N={n}, lcap={MAX_LCAP}) needs "
                f"{b / 2**20:.1f} MiB VMEM > budget "
                f"{budget / 2**20:.1f} MiB"))

    if max_seg_brick_lw % DEFAULT_BLOCK_E:
        findings.append(Finding(
            "VM303", _POLICY_PATH, 0,
            f"MAX_SEG_BRICK_LW={max_seg_brick_lw} is not a multiple of "
            f"the {DEFAULT_BLOCK_E}-event SMEM chunk — an admitted window "
            "would be streamed as one whole-window chunk"))

    summary = {f"vmem_worst_{k}_bytes": v for k, v in worst.items()}
    summary["vmem_budget_bytes"] = budget
    return findings, summary


def check_calibration_grid(points, max_seg_brick_lw: int,
                           budget: int = VMEM_BUDGET_BYTES):
    """Sweep the dispatch-calibration grid against the same bounds
    (rule VM304).

    ``points`` is ``core.calibrate.GridSpec.points()`` — (N, M, n, q, W)
    tuples, no jax import.  A grid point whose implied segment brick
    exceeds ``max_seg_brick_lw`` would make ``ops.segment_bricks``
    decline at measurement time, so the calibration pass would record
    the XLA fallback's wall clock under a kernel engine label and the
    fitted policy would dispatch on a lie; a point over the VMEM budget
    is the same admission bug one layer down.  The brick estimate
    mirrors the runtime: ceil(n/q) events per segment plus one event
    per overlap timestep W (same-timestamp pileups are the runtime
    guard's job), rounded up to the lane quantum.
    """
    findings: list[Finding] = []
    worst_lw = worst_bytes = 0
    for (n_ep, m, n_ev, q, w) in points:
        lw = _round_up(-(-n_ev // max(q, 1)) + w, LANES)
        worst_lw = max(worst_lw, lw)
        if lw > max_seg_brick_lw:
            findings.append(Finding(
                "VM304", _POLICY_PATH, 0,
                f"calibration grid point (N={n_ep}, M={m}, n={n_ev}, "
                f"q={q}, W={w}) implies segment brick LW={lw} > admitted "
                f"MAX_SEG_BRICK_LW={max_seg_brick_lw} — the kernel "
                "engines would decline and the fit would mislabel the "
                "XLA fallback"))
            continue
        b = mapconcat_footprint(n_ep)
        worst_bytes = max(worst_bytes, b)
        if b > budget:
            findings.append(Finding(
                "VM304", _POLICY_PATH, 0,
                f"calibration grid point (N={n_ep}, M={m}, n={n_ev}, "
                f"q={q}) needs {b / 2**20:.1f} MiB VMEM > budget "
                f"{budget / 2**20:.1f} MiB — shrink the grid or raise "
                "the admission bound"))
    summary = {"vmem_calibration_points": len(list(points)),
               "vmem_calibration_worst_lw": worst_lw,
               "vmem_calibration_worst_bytes": worst_bytes}
    return findings, summary
