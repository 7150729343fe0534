"""Finds a cell's files by name beside the ``BENCHMARK.json`` given: its
configuration, its mix (``bench/traffic/<mix>.json``), the pair's knee
(``bench/knees/<config>.<mix>.json``) and the configuration's ``arch``."""
from __future__ import annotations

import json
from pathlib import Path

import arch as arch_lib

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load(workload: str, bench_file: Path = ROOT / "BENCHMARK.json") -> dict:
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file.name}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    root = bench_file.parent
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    data, pair = root / BENCH.name, f"{cell['config']}.{cell['traffic']}"
    mix = json.loads((data / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    knee = json.loads((data / "knees" / f"{pair}.json").read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": cfg, "traffic": mix,
            "knee": knee["knee_req_s"], "arch": arch_lib.load(cfg),
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}
