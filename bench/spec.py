"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json`` hold
them. Every metric is a reader of its own, ``bench/metrics/<name>.py``,
with a ``read(run)`` function that returns a number, or None where it finds
nothing to read. A metric belongs to the cells its ``workloads`` key lists,
or to every cell without one. Adding a cell, a mix or a metric is adding
files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _for(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def resolve(spec: dict, workload: str, bench: Path = BENCH) -> Cell:
    """The cell named ``workload`` with its files read."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((bench.parent / cfg_entry["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(w, config, traffic, _for(spec["end_to_end"], workload),
                _for(spec["per_layer"], workload))


def reader(name: str, bench: Path = BENCH):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
