"""The benchmark is driven by data: every cell of ``BENCHMARK.json``
resolves its configuration, traffic and metric files by name, rehearses on
the CPU, and a cell or metric added as files alone is picked up."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_every_cell_resolves_its_files():
    s = spec.load_spec()
    assert s["paths"] == ["bench"] and s["command"] == ["python3", "bench/run.py"]
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and callable(spec.reader(m["name"]))
    for m in s["per_layer"]:
        assert m["moves"] in {e["name"] for e in s["end_to_end"]}
    for w in s["workloads"]:
        assert NAME.match(w["name"])
        cell = spec.resolve(s, w["name"])
        assert cell.traffic["loop"] in ("open", "replay")
        assert {"num_types", "rate_hz", "chains", "interval_ms"} <= set(cell.config)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def _checkout_with_an_added_cell(tmp_path) -> Path:
    """A checkout whose benchmark gains, as files and entries only, the
    open-loop fleet cell (an existing traffic file), an end-to-end metric
    that only it reports, and a per-layer metric."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(".cache"))
    (root / "src").symlink_to(ROOT / "src")
    (root / "bench" / "metrics" / "windows_due.py").write_text(
        "def read(run):\n    return len(run.due())\n")
    (root / "bench" / "metrics" / "delta_latency_p50_ms.py").write_text(
        "from measure import latency_s, percentile\n\n\ndef read(run):\n"
        "    p = percentile(latency_s(run), 50)\n"
        "    return None if p is None else p * 1e3\n")
    s = spec.load_spec(ROOT)
    s["workloads"].append({"name": "sym26-fleet-open", "config": "sym26",
                           "traffic": "open-fleet4", "chips": 1, "why": "x"})
    s["end_to_end"].append({"name": "delta_latency_p50_ms", "unit": "ms",
                            "better": "lower", "bound": 0.25, "source": "host_clock",
                            "workloads": ["sym26-fleet-open"]})
    s["per_layer"].append({"name": "windows_due", "unit": "windows",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator", "moves": "events_per_s",
                           "workloads": ["sym26-fleet-open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    return root


def test_a_cell_and_a_metric_added_as_files_are_picked_up(tmp_path):
    root = _checkout_with_an_added_cell(tmp_path)
    cell = spec.resolve(spec.load_spec(root), "sym26-fleet-open", root / "bench")
    assert cell.traffic["loop"] == "open" and cell.config["num_types"] == 26
    assert [m["name"] for m in cell.per_layer] == ["windows_due"]
    assert "delta_latency_p50_ms" in [m["name"] for m in cell.end_to_end]
    read = spec.reader("windows_due", root / "bench")
    assert read(SimpleNamespace(due=lambda: [1, 2])) == 2


def _rehearse(workload, seed, trace=0, root=ROOT):
    return subprocess.Popen(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(seed), "--seconds", "1", "--trace", str(trace), "--rehearse"],
        cwd=root, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ))


def test_every_cell_rehearses_on_the_cpu(tmp_path):
    """Each cell, and the cell added as files, runs end to end at its
    rehearsal size (every other one traced)."""
    added = _checkout_with_an_added_cell(tmp_path)
    cells = [(w["name"], ROOT) for w in spec.load_spec()["workloads"]]
    cells.append(("sym26-fleet-open", added))
    procs = [_rehearse(w, 2**33 + i, i % 2, root) for i, (w, root) in enumerate(cells)]
    for (w, _), p in zip(cells, procs):
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-3000:]
        line = json.loads(out.strip().splitlines()[-1])
        assert list(line) in (LINE_KEYS, LINE_KEYS[:5] + ["breakdown", "checks"]), w
        assert line["correct"] is True, (w, line["checks"])
        assert line["device"]["platform"] == "cpu"
        assert err.strip().splitlines()[-1].startswith("[bench] check ")


def test_without_a_chip_it_prints_nothing_and_fails():
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "sym26-single-replay",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_without_the_program_it_prints_nothing_and_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(".cache"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sym26-single-replay",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("w", ["sym26-single-replay", "sym26-pair-replay"])
def test_unknown_workload_names_fail(w):
    with pytest.raises(KeyError):
        spec.resolve(spec.load_spec(), w + "-x")
