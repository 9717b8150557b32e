"""Analyzer self-tests: every contract rule must fire on a seeded
violation, stay quiet on a clean twin, and report zero false positives
on the real tree; the CLI must gate (exit 0 clean / non-zero with an
injected violation); the VMEM model must pass the shipped policy and
fail an inflated one.
"""

import json
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.analysis.contracts import lint_source, lint_tree
from repro.analysis.findings import (Finding, Report, split_suppressed,
                                     suppressed_rules)
from repro.analysis import vmem

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC_ROOT = REPO / "src" / "repro"


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------- pass 1
# each case: (rule, seeded-violation snippet, clean twin)

CASES = [
    ("KC101",
     "def f(x):\n    return pl.pallas_call(k, interpret=True)(x)\n",
     "def f(x, mode):\n    return pl.pallas_call(k, interpret=mode)(x)\n"),
    ("KC102",
     "def f(ev):\n    return a1_count_kernel(ev, n_levels=3)\n",
     "def f(ev):\n    KERNEL_CALLS['a1'] += 1\n"
     "    return a1_count_kernel(ev, n_levels=3)\n"),
    ("KC103",
     "def f_state(x):\n    return pl.pallas_call(k, grid=(1,))(x)\n",
     "def f_state(x):\n    return pl.pallas_call(\n"
     "        k, grid=(1,), input_output_aliases={0: 0})(x)\n"),
    ("KC104",
     "def f():\n    return pl.BlockSpec((8, 128), lambda i: (0, i))\n",
     "def f():\n"
     "    return pl.BlockSpec((SUBLANES, LANES), lambda i: (0, i))\n"),
    ("KC105",
     "def f(s):\n"
     "    try:\n"
     "        from repro.kernels import ops as kops\n"
     "        return kops.a1_count(s)\n"
     "    except KernelDeclined:\n"
     "        return slow(s)\n",
     "def f(s):\n"
     "    try:\n"
     "        from repro.kernels import ops as kops\n"
     "        return kops.a1_count(s)\n"
     "    except KernelDeclined:\n"
     "        record_fallback('site')\n"
     "        return slow(s)\n"),
    ("KC106",
     "import os\n"
     "FLAG = os.environ.get('REPRO_KERNEL_INTERPRET') == '1'\n",
     "from repro.kernels.tally import interpret_requested\n"
     "FLAG = interpret_requested()\n"),
    ("KC107",
     "def f():\n"
     "    REGISTRY.counter('kernel_calls', kind='a1').inc()\n",
     "def f():\n    KERNEL_CALLS['a1'] += 1\n"),
    ("KC107",
     "def f():\n    KERNEL_CALLS['fallback:site'] += 1\n",
     "def f():\n    record_fallback('site')\n"),
]


@pytest.mark.parametrize("rule,bad,clean", CASES,
                         ids=[c[0] for c in CASES])
def test_rule_fires_once_on_seeded_violation(rule, bad, clean):
    findings = lint_source(bad, "repro/core/fixture.py")
    assert rules_of(findings) == [rule]


@pytest.mark.parametrize("rule,bad,clean", CASES,
                         ids=[c[0] for c in CASES])
def test_rule_quiet_on_clean_twin(rule, bad, clean):
    assert lint_source(clean, "repro/core/fixture.py") == []


@pytest.mark.parametrize("caught", ["NotImplementedError", "ImportError",
                                    "(ImportError, NotImplementedError)"])
def test_kc105_rejects_arm_broader_than_the_decline(caught):
    # a Pallas lowering raises NotImplementedError too: catching it (even
    # with the fallback recorded) would reroute a kernel fault silently
    src = ("def f(s):\n"
           "    try:\n"
           "        return kops.a1_count(s)\n"
           f"    except {caught}:\n"
           "        record_fallback('site')\n"
           "        return slow(s)\n")
    assert rules_of(lint_source(src, "repro/core/x.py")) == ["KC105"]


def test_kernel_def_modules_exempt_from_kc102():
    src = "def wrap(ev):\n    return a1_count_state_kernel(ev)\n"
    assert lint_source(src, "repro/kernels/a1_count.py") == []
    assert rules_of(lint_source(src, "repro/core/x.py")) == ["KC102"]


def test_env_accessor_module_exempt_from_kc106():
    src = "import os\nV = os.environ.get('REPRO_INTERPRET_KERNELS')\n"
    assert lint_source(src, "repro/kernels/tally.py") == []
    assert rules_of(lint_source(src, "repro/core/x.py")) == ["KC106"]


def test_tally_accessor_module_exempt_from_kc107():
    src = ("def record_fallback(site):\n"
           "    REGISTRY.counter('kernel_calls',"
           " kind='fallback:' + site).inc()\n")
    assert lint_source(src, "repro/kernels/tally.py") == []
    assert rules_of(lint_source(src, "repro/core/x.py")) == ["KC107"]


def test_suppression_marker_waives_and_reports():
    bad = ("def f():\n"
           "    return pl.BlockSpec((8, 128), t)  # audit-ok: KC104 why\n")
    findings = lint_source(bad, "repro/core/x.py")
    active, waived = split_suppressed(
        findings, {"repro/core/x.py": bad.splitlines()})
    assert active == [] and rules_of(waived) == ["KC104"]
    assert suppressed_rules("x = 1  # audit-ok: KC101") == {"KC101"}
    assert suppressed_rules("x = 1  # nothing here") == set()


def test_real_tree_is_clean():
    active, _, summary = lint_tree(SRC_ROOT)
    assert active == [], [f.format() for f in active]
    assert summary["files_linted"] > 50


# ---------------------------------------------------------------- pass 3


def test_vmem_policy_fits_budget():
    from repro.kernels.ops import MAX_SEG_BRICK_LW
    findings, summary = vmem.check_vmem(MAX_SEG_BRICK_LW)
    assert findings == [], [f.format() for f in findings]
    assert 0 < summary["vmem_worst_mapconcat_bytes"] \
        <= summary["vmem_budget_bytes"]


def test_vmem_flags_oversized_policy():
    # the segmented launches carry the most VMEM (phase-machine scratch);
    # a budget below their worst case but above every carried launch's
    # must flag exactly them
    from repro.kernels.ops import MAX_SEG_BRICK_LW
    worst_state = max(vmem.a1_state_footprint(vmem.MAX_N, vmem.MAX_LCAP),
                      vmem.a2_state_footprint(vmem.MAX_N))
    budget = worst_state + 1
    assert vmem.mapconcat_footprint(vmem.MAX_N) > budget
    findings, _ = vmem.check_vmem(MAX_SEG_BRICK_LW, budget=budget)
    assert findings and all(f.rule == "VM302" for f in findings)


def test_vmem_flags_unaligned_policy():
    findings, _ = vmem.check_vmem(100)
    assert "VM303" in rules_of(findings)


def test_vmem_footprint_monotone_in_window():
    # the segment window streams through SMEM chunks, so the VMEM
    # footprint grows with the phase-machine scratch (N × LCAP), not LW
    assert vmem.mapconcat_footprint(4, 4) < vmem.mapconcat_footprint(4, 16)
    assert vmem.mapconcat_footprint(4) < vmem.mapconcat_footprint(8)


def test_vmem_constants_match_kernel_layout():
    # the analysis plane mirrors the layout constants instead of
    # importing the jax kernel stack; hold the mirror to the source
    from repro.kernels import a2_count
    assert vmem.LANES == a2_count.LANES
    assert vmem.SUBLANES == a2_count.SUBLANES
    assert vmem.SEG_ROWS == a2_count.SEG_ROWS
    assert vmem.MAPC_ROWS == a2_count.MAPC_ROWS
    assert vmem.DEFAULT_BLOCK_E == a2_count.DEFAULT_BLOCK_E


def test_segment_bricks_enforces_admission_bound():
    import numpy as np
    from repro.kernels import ops
    wt = np.full((1, 128), -1, np.int32)
    wtt = np.zeros((1, 128), np.int32)
    tau = np.array([0, 100], np.int32)
    with pytest.raises(ops.KernelDeclined):
        ops.segment_bricks(wt, wtt, tau, length=ops.MAX_SEG_BRICK_LW * 2)


# ---------------------------------------------------------------- pass 2


def test_trace_audit_clean_on_real_entry_points():
    from repro.analysis import tracecheck
    findings, summary = tracecheck.audit_entry_points()
    assert findings == [], [f.format() for f in findings]
    assert len(summary["entry_points_traced"]) >= 6


def test_jaxpr_audit_flags_callback_and_dtype():
    import jax
    import jax.numpy as jnp
    from repro.analysis.tracecheck import audit_jaxpr

    def leaky(x):
        jax.debug.callback(lambda v: None, x)
        return x.astype(jnp.float32) * 2.0

    jaxpr = jax.make_jaxpr(leaky)(jnp.ones((4,), jnp.int32)).jaxpr
    rules = rules_of(audit_jaxpr("leaky", jaxpr))
    assert "TR201" in rules and "TR202" in rules


def test_donation_audit_passes_current_factories():
    from repro.analysis import tracecheck
    findings, _ = tracecheck.audit_donation()
    assert findings == []


# ------------------------------------------------------------------ CLI


def run_cli(*args, env_extra=None):
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.audit", *args],
        capture_output=True, text=True, env=env, cwd=REPO)


def test_cli_clean_tree_exits_zero(tmp_path):
    out = tmp_path / "summary.json"
    r = run_cli("--fail-on-violation", "--skip-trace",
                "--summary", str(out))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "AUDIT CLEAN" in r.stdout
    data = json.loads(out.read_text())
    assert data["ok"] and data["findings"] == []


@pytest.mark.parametrize("rule,bad,clean", CASES,
                         ids=[c[0] for c in CASES])
def test_cli_injected_violation_exits_nonzero(tmp_path, rule, bad, clean):
    root = tmp_path / "repro"
    shutil.copytree(SRC_ROOT, root)
    (root / "core" / "injected_fixture.py").write_text(bad)
    r = run_cli("--fail-on-violation", "--skip-trace",
                "--root", str(root))
    assert r.returncode == 1, r.stdout + r.stderr
    assert rule in r.stdout


def test_cli_without_fail_flag_never_gates(tmp_path):
    root = tmp_path / "repro"
    shutil.copytree(SRC_ROOT, root)
    (root / "core" / "injected_fixture.py").write_text(CASES[0][1])
    r = run_cli("--skip-trace", "--root", str(root))
    assert r.returncode == 0
    assert "AUDIT FAILED" in r.stdout


# ------------------------------------------------------------- findings


def test_report_roundtrip():
    rep = Report()
    rep.extend([Finding("KC101", "a.py", 3, "msg")],
               [Finding("KC104", "b.py", 9, "waived")], files_linted=2)
    assert not rep.ok
    data = json.loads(rep.to_json())
    assert data["findings"][0]["rule"] == "KC101"
    assert data["suppressed"][0]["line"] == 9
    assert data["summary"]["files_linted"] == 2
    assert "AUDIT FAILED" in rep.format()
