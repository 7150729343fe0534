"""Finds a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load(workload: str, bench_file: Path = ROOT / "BENCHMARK.json") -> dict:
    spec = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file.name}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": cfg, "traffic": mix,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}
