"""CLI for the kernel-contract auditor (``repro.analysis``).

Usage::

    python -m repro.launch.audit [--fail-on-violation] \
        [--root src/repro] [--summary experiments/bench/audit_summary.json] \
        [--json] [--skip-trace] [--skip-sentinel] [--budget-mib 15]

Pass 1 (contract linter) and Pass 3 (VMEM budget) always run; they are
pure source/arithmetic and take milliseconds.  Pass 2 (trace audit +
recompilation sentinel) imports jax and the engines — skip it with
``--skip-trace`` for a fast editor hook, or keep the trace audit but
drop the (slower) streaming sentinel with ``--skip-sentinel``.

Exit status: 0 when no active findings (suppressed waivers don't fail
the audit; they are listed in the report), 1 otherwise — CI gates on
this via ``--fail-on-violation``.  Without the flag the exit status is
always 0, so local runs can be wired into non-blocking tooling.
"""

from __future__ import annotations

import argparse
import pathlib
import sys


def build_report(root: pathlib.Path, trace: bool, sentinel: bool,
                 budget_bytes: int | None = None):
    from repro.analysis import vmem
    from repro.analysis.contracts import lint_tree
    from repro.analysis.findings import Report

    report = Report()
    active, waived, summary = lint_tree(root)
    report.extend(active, waived, **summary)

    budget = budget_bytes or vmem.VMEM_BUDGET_BYTES
    from repro.kernels.ops import MAX_SEG_BRICK_LW
    vf, vs = vmem.check_vmem(MAX_SEG_BRICK_LW, budget=budget)
    report.extend(vf, **vs)
    # the dispatch-calibration grid must stay inside the same
    # admission envelope, or the fitted policy measures fallbacks
    from repro.core.calibrate import GridSpec
    cf, cs = vmem.check_calibration_grid(
        GridSpec().points(), MAX_SEG_BRICK_LW, budget=budget)
    report.extend(cf, **cs)

    if trace:
        from repro.analysis import tracecheck
        tf, ts = tracecheck.run(sentinel=sentinel)
        report.extend(tf, **ts)

    from repro.kernels.tally import KERNEL_CALLS
    report.summary["kernel_calls"] = dict(KERNEL_CALLS)
    return report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.launch.audit",
        description="kernel-contract auditor (see repro.analysis)")
    p.add_argument("--root", default="src/repro",
                   help="source root for the contract linter")
    p.add_argument("--fail-on-violation", action="store_true",
                   help="exit 1 when any active finding remains")
    p.add_argument("--summary", default=None, metavar="PATH",
                   help="also write the JSON report to PATH")
    p.add_argument("--json", action="store_true",
                   help="print the JSON report instead of the human one")
    p.add_argument("--skip-trace", action="store_true",
                   help="skip Pass 2 entirely (no jax import)")
    p.add_argument("--skip-sentinel", action="store_true",
                   help="run Pass 2 without the streaming recompile "
                        "sentinel")
    p.add_argument("--budget-mib", type=float, default=None,
                   help="override the VMEM budget (MiB)")
    args = p.parse_args(argv)

    root = pathlib.Path(args.root)
    if not root.is_dir():
        print(f"audit: source root {root} not found", file=sys.stderr)
        return 2
    budget = int(args.budget_mib * 2**20) if args.budget_mib else None
    report = build_report(root, trace=not args.skip_trace,
                          sentinel=not args.skip_sentinel,
                          budget_bytes=budget)

    print(report.to_json() if args.json else report.format())
    if args.summary:
        out = pathlib.Path(args.summary)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(report.to_json() + "\n")
    return 1 if (args.fail_on_violation and not report.ok) else 0


if __name__ == "__main__":
    sys.exit(main())
