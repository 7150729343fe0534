"""One traced run of a cell with the program's own tracer on, and what its
spans, counters and named scopes show.

    python3 bench/span_report.py --workload phi4.tenants --seed 7 --seconds 20

From the root of a checkout, on the chip.  The run is ``run.py``'s
``--trace 1`` run (same build, traffic, window and profiler trace), with
the engine built as ``ServingEngine(..., tracer=Tracer(annotate=True))``,
so that the profiler's trace holds the program's ``serve.*`` spans.  It
does not check the logits (``run.py`` does).  After the window it
compiles the decode step again (from the persistent cache) to map each
traced operation to its model scope (``op_scopes.py``).

It logs to stderr: device idle time split by the innermost ``serve.*``
span, over the window and within the driver's engine steps; the decode
step's device time by scope, with the unscoped remainder and the time
between operations; each step-level span's mean duration and self time;
the queue wait split by what held it (``program_spans.wait_split``); the
tracer's counters.  The last line of
stdout is one JSON object with those and the readings of
``bench/metrics/<name>.py`` for ``METRICS``, next to the run's
``out_tok_s`` and ``itl_mean_ms``, read as ``run.py`` reads them.

``bench/run.py`` builds its engine without a tracer, so these metrics are
not yet in ``BENCHMARK.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as cell_lib  # noqa: E402
import op_scopes  # noqa: E402
import program_spans  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from driver import CompileWatch  # noqa: E402

METRICS = ("queue_wait_p90_ms", "slot_wait_share", "dispatch_ms_per_step",
           "lora_share")


def report(workload: str, seed: int, seconds: float, *,
           require_chip: bool = True, shrink=None) -> dict:
    spec = cell_lib.load(workload)
    if shrink is not None:
        spec["config"] = shrink(spec["config"])
    prog = run.import_program()
    import jax
    import jax.numpy as jnp
    from repro.tracing import Tracer
    why = run.check_device(jax, spec["cell"])
    if why and require_chip:
        raise SystemExit(f"span_report: {why}")
    run.compile_cache(jax, prog)
    watch = CompileWatch(jax)
    tracer = Tracer(annotate=True)
    built = {}

    def engine(cfg, ex):
        # the jitted step and its arguments' shapes, before the driver
        # wraps the call and the run frees the arrays
        idx = jnp.zeros((ex.max_batch,), jnp.int32)
        built["decode"] = ex.decode
        built["args"] = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (ex.params, ex.lora, ex.cache, ex.tokens, idx))
        return prog["ServingEngine"](cfg, ex, tracer=tracer)

    out = run.execute(jax, dict(prog, ServingEngine=engine), spec, seed,
                      seconds, True, T_START)
    in_window = watch.between(*out["win"])
    trace = trace_reduce.reduce(out["trace_dir"])
    pd = jax.profiler.ProfileData.from_file(
        str(trace_reduce.find(out["trace_dir"])))
    shutil.rmtree(out["trace_dir"], ignore_errors=True)

    lo_ns, hi_ns = _window_ns(pd)
    scopes, matched, text = _scopes(jax, built, pd, lo_ns, hi_ns)
    dev = jax.devices()[0]
    ctx = {"counters": {"steps": out["steps"], "ttft_s": out["ttft"],
                        "rows": out["rows"], "cache_len": out["cache_len"],
                        "loads": out["loads"], "fed_idx": out["fed_idx"],
                        "admitted": sum(s.admitted for s in out["steps"])},
           "trace": trace, "config": spec["config"],
           "traffic": spec["traffic"], "rank": out["rank"],
           "device_kind": dev.device_kind,
           "tracer": tracer, "window": out["win"], "scopes": scopes}
    metrics = {name: run.load_reader(name)(ctx) for name in METRICS}
    gaps = out["gaps"]
    return {
        "workload": workload, "seed": seed, "device": dev.device_kind,
        "out_tok_s": out["n_tok"] / seconds,
        "itl_mean_ms": 1e3 * statistics.fmean(gaps) if gaps else None,
        "compiles_in_window": len(in_window),
        "metrics": metrics, "counters": dict(tracer.counters),
        "wait_split_s": program_spans.wait_split(tracer, *out["win"]),
        "spans": program_spans.span_table(tracer, *out["win"]),
        "host_ms_per_step": program_spans.host_ms_per_step(tracer,
                                                           *out["win"]),
        "idle": program_spans.idle_by_span(pd),
        "scopes": None if scopes is None else dict(
            {k: v for k, v in scopes.items()
             if k not in ("op_names", "modules", "unscoped_top")},
            unscoped_top=[(op, s, _op_name(text, op))
                          for op, s in scopes["unscoped_top"]]),
        "op_map_matched_by": matched,
        "tracer": tracer, "window": out["win"]}


def _scopes(jax, built, pd, lo_ns, hi_ns) -> tuple:
    """(scope times, how the op map was matched, the compiled text) of the
    traced decode step; the first try may load the program from the
    persistent cache, the second compiles it afresh."""
    why = "no device plane"
    for cached in (True, False):
        jax.config.update("jax_enable_compilation_cache", cached)
        try:
            compiled = built["decode"].lower(*built["args"]).compile()
            text = compiled.as_text()
            op_map = op_scopes.op_scopes(text)
            scopes = op_scopes.scope_times(pd, op_map, lo_ns, hi_ns)
            if scopes is None:
                return None, why, text
            matched = op_scopes.check(op_map, scopes["op_names"],
                                      scopes["modules"],
                                      op_scopes.fingerprint(compiled))
            return scopes, matched, text
        except Exception as e:  # a report, not a gate: say why, go on
            why = f"{type(e).__name__}: {e}"
            print(f"span_report: op scopes (cache {cached}): {why}",
                  file=sys.stderr, flush=True)
    return None, why, ""


def _op_name(text: str, op: str) -> str:
    m = re.search(rf'^\s+(?:ROOT )?{re.escape(op)} = .*?op_name="([^"]*)"',
                  text, re.M)
    return m.group(1) if m else ""


def _window_ns(pd) -> tuple:
    spans = trace_reduce.host_spans(pd)
    win = [s for s in spans if s[2] == "bench.window"]
    return (win[0][0], win[0][1]) if win else (0, 1 << 62)


def log_tables(rep: dict) -> None:
    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    idle = rep["idle"]
    if idle:
        log(f"device idle by innermost serve.* span: "
            f"{1e3 * idle['idle_s']:.3f} ms idle of "
            f"{1e3 * idle['window_s']:.3f} ms")
        log("  span | idle ms (window) | idle ms (in engine steps)")
        for name, s in idle["by_span"].items():
            log(f"  {name} | {1e3 * s:.3f} | "
                f"{1e3 * idle['in_engine_step'].get(name, 0.0):.3f}")
        log("  longest gaps: " + "; ".join(
            f"{1e3 * g:.3f} ms (" + ", ".join(
                f"{n} {1e3 * v:.3f}" for n, v in parts.items()) + ")"
            for g, parts in idle["longest"]))
    sc = rep["scopes"]
    if sc:
        n = sc["decode_n"]
        log(f"decode step device time by scope over {n} runs: "
            f"{1e3 * sc['decode_s'] / n:.4f} ms a run; operations "
            f"{1e3 * sc['ops_s'] / n:.4f} ms; op map matched by "
            f"{rep['op_map_matched_by']}")
        log("  scope | ms a run | % of the decode step")
        rows = sorted(sc["by_scope"].items(), key=lambda kv: -kv[1])
        rows.append(("between operations", sc["decode_s"] - sc["ops_s"]))
        for name, s in rows:
            log(f"  {name} | {1e3 * s / n:.4f} | "
                f"{100 * s / sc['decode_s']:.3f}")
        log("  longest unscoped: " + "; ".join(
            f"{op} {1e3 * s / n:.4f} ms ({where or 'no op_name'})"
            for op, s, where in sc["unscoped_top"]))
    log(f"step-level spans in the window (host ms per step outside the "
        f"decode call and its wait: {rep['host_ms_per_step']}):")
    log("  span | count | mean ms | mean self ms")
    for name, row in rep["spans"].items():
        log(f"  {name} | {row['n']} | {row['ms']:.4f} | "
            f"{row['self_ms']:.4f}")
    split = rep["wait_split_s"]
    total = sum(split.values())
    log(f"queue wait of the requests admitted in the window: "
        f"{1e3 * total:.3f} ms in all; "
        + ", ".join(f"{k} {100 * v / total:.2f}%" if total else k
                    for k, v in split.items()))
    log(f"counters: {json.dumps(rep['counters'])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/span_report.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    rep = report(a.workload, a.seed, a.seconds)
    log_tables(rep)
    rep = {k: v for k, v in rep.items() if k not in ("tracer", "window")}
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
