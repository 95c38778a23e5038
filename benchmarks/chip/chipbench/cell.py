"""What a cell is made of, found by name: the entry of ``BENCHMARK.json``,
its configuration file and its traffic file."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

#: benchmarks/chip
HERE = Path(__file__).resolve().parents[1]
#: the checkout's root, where BENCHMARK.json lies
ROOT = HERE.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, parsed
    traffic: dict           # the traffic file, parsed
    end_to_end: tuple       # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "benchmarks/chip/traffic" / f"{w['traffic']}.json")
        .read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _applies(m, workload)))
