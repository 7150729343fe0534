"""The sweep that finds a configuration's knee under a traffic mix.

    python3 bench/knee_sweep.py --workload phi4.saturated --seconds 20 \
        --capacity 1.7 --shares 0.6,0.8,1.0,1.2,1.5,2.0 --trace-seeds 1,2

In one process, one run of the cell per offered rate and trace seed, at
``share x capacity`` requests per second (``capacity`` is a first guess,
rows / (step time x mean output tokens)).  For each run it prints the
tokens per second completed, the mean of the rows busy, and the waiting
queue's mean over the first and the last third of the window.

The knee is the rate whose offered output tokens per second equal the
plateau: the tokens per second completed where every row is busy (the
median over the runs with ``rows - 0.25`` rows busy or more), divided by
the mix's mean output tokens per request.  Above it the queue grows
without end; a short window cannot show that growth, since a request is
served for about ten seconds, but the plateau it reads well.  The knee is
printed, and written with the sweep into ``bench/knees/<config>.<mix>.json``
by hand.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as cell_lib  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

FULL_SLACK = 0.25


def sweep(workload: str, seconds: float, capacity: float, shares,
          trace_seeds, *, require_chip: bool = True, shrink=None) -> dict:
    spec = cell_lib.load(workload)
    if shrink is not None:
        spec["config"] = shrink(spec["config"])
    prog = run.import_program()
    import jax
    why = run.check_device(jax, spec["cell"])
    if why and require_chip:
        raise SystemExit(f"knee_sweep: {why}")
    run.compile_cache(jax, prog)
    rows_n = spec["config"]["serving"]["batch"]
    mean_out = traffic.mean_output(spec["traffic"])
    rows = []
    for trace_seed in trace_seeds:
        for share in shares:
            s = dict(spec, knee=capacity,
                     traffic=dict(spec["traffic"], rate_share_of_knee=share,
                                  trace_seed=trace_seed))
            out = run.execute(jax, prog, s, 1, seconds, False,
                              time.perf_counter())
            w0, w1 = out["win"]
            third = (w1 - w0) / 3
            steps = out["steps"]
            first = [st.waiting for st in steps if st.t0 < w0 + third]
            last = [st.waiting for st in steps if st.t0 >= w1 - third]
            rate = share * capacity
            row = {"rate_req_s": rate, "trace_seed": trace_seed,
                   "offered_tok_s": rate * mean_out,
                   "out_tok_s": out["n_tok"] / seconds,
                   "rows_busy": statistics.fmean(st.running for st in steps),
                   "waiting_first_third": statistics.fmean(first),
                   "waiting_last_third": statistics.fmean(last),
                   "step_ms": 1e3 * (w1 - w0) / len(steps)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    full = [r["out_tok_s"] for r in rows
            if r["rows_busy"] >= rows_n - FULL_SLACK]
    plateau = statistics.median(full) if full else None
    summary = {"workload": workload, "seconds": seconds,
               "mean_output_tokens": mean_out, "plateau_tok_s": plateau,
               "knee_req_s": plateau / mean_out if plateau else None,
               "sweep": rows}
    print(json.dumps(summary), flush=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/knee_sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--capacity", type=float, required=True)
    ap.add_argument("--shares", required=True)
    ap.add_argument("--trace-seeds", default="1")
    a = ap.parse_args(argv)
    sweep(a.workload, a.seconds, a.capacity,
          [float(s) for s in a.shares.split(",")],
          [int(s) for s in a.trace_seeds.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
