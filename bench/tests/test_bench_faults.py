"""With the timed path broken underneath, a rehearsed run reports
``"correct": false`` and names the number that caught the fault; so does
the control, the cheaper inexact counter that the guarantee rules out."""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CAUGHT_BY = {
    "state_unchanged": ("missed_episodes",),
    "half_batch": ("missed_episodes",),
    "altered_answer": ("count_mismatches",),
    "duplicate_delta": ("duplicate_windows",),
    "control": ("count_mismatches", "missed_episodes"),
}


def test_every_fault_and_the_control_fail_the_comparison():
    procs = {f: subprocess.Popen(
        [sys.executable, str(BENCH / "tests" / "faulty_run.py"), f, "--workload",
         "sym26-single-replay", "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=BENCH.parent, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=dict(os.environ))
        for f in CAUGHT_BY}
    for fault, p in procs.items():
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-3000:]
        line = json.loads(out.strip().splitlines()[-1])
        assert line["correct"] is False, fault
        caught = sum(line["checks"][k]["value"] for k in CAUGHT_BY[fault])
        assert caught > 0, (fault, line["checks"])
