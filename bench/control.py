"""Readings that the ``logit_gap`` limit is set from.

    python3 bench/control.py --workload phi4.tenants --seconds 20 \
        --seeds 101,102,...  --control-seeds 101,102,103

In one process, for each seed: one run of the cell as ``run.py`` makes it
(same build, traffic and window), then the plain reference over what the
program was fed, and the program's ``logit_gap`` and ``logit_err`` (the
lower readings are their largest over the seeds).  For each control seed
also the control: the reference computed with float8 operands
(``reference.py``, ``mode="fp8"``), put in the program's place, read the
same way against the float32 reference at the same (step, row) pairs (the
upper readings are their smallest).  Prints one JSON line per seed and a summary.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as cell_lib  # noqa: E402
import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def readings(workload: str, seconds: float, seeds, control_seeds, *,
             require_chip: bool = True, shrink=None) -> dict:
    spec = cell_lib.load(workload)
    if shrink is not None:
        spec["config"] = shrink(spec["config"])
    prog = run.import_program()
    import jax
    why = run.check_device(jax, spec["cell"])
    if why and require_chip:
        raise SystemExit(f"control: {why}")
    run.compile_cache(jax, prog)
    cfg = spec["config"]
    program, control = {}, {}
    for seed in seeds:
        out = run.execute(jax, prog, spec, seed, seconds, False,
                          time.perf_counter())
        ref, gap, err = run.compare(spec["arch"], seed, cfg, out)
        program[seed] = (float(gap.max()), float(err.max()))
        line = {"seed": seed, "program_gap": program[seed][0],
                "program_err": program[seed][1],
                "pairs": len(out["at"]), "decode_steps":
                int(out["fed_idx"].shape[0])}
        if seed in control_seeds:
            low = reference.logits(spec["arch"], seed, cfg, out["vpad"],
                                   out["slots"], out["rank"], out["fed_tok"],
                                   out["fed_idx"], out["at"], mode="fp8")
            control[seed] = (float(check.gaps(low, ref).max()),
                             float(check.rel_err(low, ref).max()))
            line["control_gap"], line["control_err"] = control[seed]
        print(json.dumps(line), flush=True)
    summary = {"workload": workload, "seconds": seconds}
    for i, name in enumerate(("gap", "err")):
        summary[f"{name}_lower"] = max(v[i] for v in program.values())
        summary[f"{name}_upper"] = (min(v[i] for v in control.values())
                                    if control else None)
    summary.update(program=program, control=control)
    print(json.dumps(summary), flush=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    ctrl = {int(s) for s in a.control_seeds.split(",") if s}
    readings(a.workload, a.seconds, seeds, ctrl)
    return 0


if __name__ == "__main__":
    sys.exit(main())
