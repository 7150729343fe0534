"""Run one cell of the benchmark once, on the chip this process is given.

    python3 bench/run.py --workload phi4.tenants --seed 7 --seconds 20 --trace 0

From the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``bench/configs/<name>.json``, which
names its architecture's module ``bench/arch/<arch>.py``) and a traffic mix
(``bench/traffic/<mix>.json``; the pair's knee is in
``bench/knees/<config>.<mix>.json``).  The run
builds the weights and LoRA bank from the seed, builds the program's ``Model``,
``JaxExecutor`` and ``ServingEngine`` through their public constructors,
warms up the decode step (the executor's constructor compiles and runs
it), drives the mix on the wall clock for ``fill_s`` seconds and then
measures for ``--seconds``.  Once the window has closed it reads the
device's peak memory, frees the program's state and checks the sampled
logits against the plain reference (``reference.py``, ``check.py``).

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from the profiler's trace
of the window and the driver's counts by ``bench/metrics/<name>.py``.

Exits non-zero and prints no result where JAX finds no TPU or fewer chips
than the cell asks for, where the program cannot be imported, where the
decode steps would pass the KV cache's positions, or where anything
compiles inside the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import cell as cell_lib  # noqa: E402
from driver import CompileWatch, Driver, RunFailed  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def percentile(values, pct: int) -> float:
    """The ``pct``-th percentile as ``statistics.quantiles`` cuts it."""
    if len(values) < 2:
        return values[0] if values else math.nan
    return statistics.quantiles(values, n=100)[pct - 1]


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def import_program():
    """The system under test, from this checkout's ``src/``."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import Model, ShardingPlan
    from repro.models.config import ModelConfig
    from repro.serving import EngineConfig, JaxExecutor, ServingEngine
    return dict(enable_compile_cache=enable_compile_cache, Model=Model,
                ShardingPlan=ShardingPlan, ModelConfig=ModelConfig,
                EngineConfig=EngineConfig, JaxExecutor=JaxExecutor,
                ServingEngine=ServingEngine)


def compile_cache(jax, prog) -> str:
    """JAX's persistent compilation cache, in one fixed directory of this
    checkout, whatever the environment names: the program takes the
    directory from ``JAX_COMPILATION_CACHE_DIR``, so the benchmark sets it.
    Every program is cached, however short its compile, and nothing is
    evicted: a cell's few dozen programs are all it holds, and eviction
    reads an access-time file beside each entry that entries written
    without eviction lack, which fails every write."""
    cache_dir = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    prog["enable_compile_cache"]()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def model_config(prog, model: dict):
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in model.items()}
    return prog["ModelConfig"](**fields)


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def free(tree) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def check_device(jax, cell) -> str:
    """Why this process cannot run the cell on a chip, or ''."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return (f"JAX found no TPU (platform {devices[0].platform!r}); "
                "nothing was run")
    if len(devices) < cell["chips"]:
        return (f"cell {cell['name']} needs {cell['chips']} chips, JAX "
                f"found {len(devices)}")
    return ""


def execute(jax, prog, spec, seed: int, seconds: float, trace: bool,
            t_start: float) -> dict:
    """Build the cell from the seed, drive its traffic, and return what the
    window measured, with the program's state freed.  Raises RunFailed."""
    import numpy as np

    import traffic
    import weights
    cfg, mix, arch = spec["config"], spec["traffic"], spec["arch"]
    dev = jax.devices()[0]
    m = cfg["model"]
    rows, cache_len = cfg["serving"]["batch"], cfg["serving"]["cache_len"]
    slots, rank = mix["slots"], mix["rank"]
    model = prog["Model"](model_config(prog, m),
                          prog["ShardingPlan"](mode="decode"))
    key = jax.random.PRNGKey(0)
    want_params = jax.eval_shape(model.init, key)
    want_lora = jax.eval_shape(
        lambda k: model.init_lora(k, slots, rank), key)
    vpad = want_params["embed"]["embed"].shape[0]
    sem = weights.build(arch, seed, m, vpad, slots, rank)
    params, lora = weights.to_program(arch, sem)
    if not (weights.same_layout(params, want_params)
            and weights.same_layout(lora, want_lora)):
        raise RunFailed("the program's parameter layout is not the one "
                        "weights.to_program builds")
    jax.block_until_ready((params, lora))
    t_weights = time.perf_counter()
    ex = prog["JaxExecutor"](model, params, lora, max_batch=rows,
                             cache_len=cache_len)
    engine = prog["ServingEngine"](prog["EngineConfig"](
        kv_capacity_tokens=rows * cache_len, adapter_slots=slots,
        max_running=rows), ex)
    t_built = time.perf_counter()
    planned = traffic.plan(mix, spec["knee"], rows, mix["fill_s"] + seconds)
    driver = Driver(jax, engine, ex, planned, cache_len=cache_len,
                    fill_s=mix["fill_s"], window_s=seconds,
                    sample_steps=mix["sample_steps"], seed=seed,
                    annotate=trace)
    trace_dir = None
    if trace:
        # the profiler starts with the fill, so that its own start-up
        # falls outside the window; host spans come from TraceAnnotation
        # only, since tracing every Python call would slow the host
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    driver.run()
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}

    steps = driver.window_steps()
    samples = driver.sampler.samples()
    at_steps = sorted(samples)
    out = {
        "t_weights": t_weights - t_start, "t_built": t_built - t_weights,
        "setup_s": driver.win0 - t_start,
        "fill_s": driver.win0 - driver.t_start,
        "peak": int(stats.get("peak_bytes_in_use", 0)),
        "bytes_limit": stats.get("bytes_limit"),
        "win": (driver.win0, driver.win1),
        "n_warm": sum(p.warm for p in planned),
        "rate": traffic.rate(mix, spec["knee"]),
        "lateness": driver.lateness,
        "vpad": vpad, "rows": rows, "cache_len": cache_len,
        "slots": slots, "rank": rank,
        "at": [(p, b) for p in at_steps for b in range(rows)],
        "prog_logits": np.stack([np.asarray(samples[s]) for s in at_steps])
        .reshape(len(at_steps) * rows, -1),
        "fed_tok": np.stack([np.asarray(t).reshape(-1)
                             for t in driver.fed_tokens]),
        "fed_idx": np.stack([np.asarray(i).reshape(-1)
                             for i in driver.fed_idx]),
        "pos": int(ex.cache["pos"]),
        "trace_dir": trace_dir,
    }
    uids = driver.window_requests()
    out.update(uids=uids, ttft=driver.ttfts(uids), gaps=driver.gaps(),
               n_tok=driver.window_tokens(), steps=steps,
               loads=driver.loads1 - driver.loads0,
               window_step0=driver.window_step0)
    free((ex.params, ex.lora, ex.cache, sem))
    del samples, driver, ex, engine, params, lora, sem
    gc.collect()
    return out


def compare(arch, seed: int, cfg: dict, out: dict) -> tuple:
    """(reference logits, the program's greedy-token gaps, its relative
    logit errors), one per sampled (step, row) pair."""
    import check
    import reference
    ref = reference.logits(arch, seed, cfg, out["vpad"], out["slots"],
                           out["rank"], out["fed_tok"], out["fed_idx"],
                           out["at"])
    return (ref, check.gaps(out["prog_logits"], ref),
            check.rel_err(out["prog_logits"], ref))


def run(argv=None, *, require_chip: bool = True, shrink=None,
        bench_file: Path = ROOT / "BENCHMARK.json") -> int:
    """One run.  ``require_chip=False`` and ``shrink`` (a function that
    returns a smaller configuration) exist for the CPU tests only."""
    args = parse(argv)
    spec = cell_lib.load(args.workload, bench_file)
    if shrink is not None:
        spec["config"] = shrink(spec["config"])
    cell, cfg, mix = spec["cell"], spec["config"], spec["traffic"]
    prog = import_program()

    import jax
    import numpy as np
    why = check_device(jax, cell)
    if why and require_chip:
        log(f"bench: {why}")
        return 2
    t_devices = time.perf_counter() - T_START
    cache_dir = compile_cache(jax, prog)
    watch = CompileWatch(jax)
    try:
        out = execute(jax, prog, spec, args.seed, args.seconds,
                      bool(args.trace), T_START)
    except RunFailed as e:
        log(f"bench: {e}")
        return 4
    in_window = watch.between(*out["win"])
    if in_window:
        log(f"bench: {len(in_window)} compile events inside the window: "
            f"{sorted({e[3] for e in in_window})}")
        return 5
    devices = jax.devices()
    dev = devices[0]
    ttft, gaps, steps, lat = out["ttft"], out["gaps"], out["steps"], \
        out["lateness"]
    cache_len, n_fed = out["cache_len"], out["fed_idx"].shape[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}; peak bytes {out['peak']} of "
        f"{out['bytes_limit']}")
    log(f"set-up s: {out['setup_s']:.3f} (imports and devices "
        f"{t_devices:.3f}, weights "
        f"{out['t_weights'] - t_devices:.3f}, "
        f"executor + engine {out['t_built']:.3f}, fill {out['fill_s']:.3f})"
        f"; compile or load s {watch.compile_s():.3f}; persistent-cache "
        f"hits {watch.cache_hits}, misses {watch.misses or 'none'}")
    log(f"window: {args.seconds} s, {len(steps)} engine steps, decode steps "
        f"{n_fed} of {cache_len} positions, {out['n_tok']} tokens; offered "
        f"{out['rate']} req/s, requests due {len(out['uids'])} "
        f"(+{out['n_warm']} in flight at the start); "
        f"TTFT samples {len(ttft)}, gaps {len(gaps)}; generator lateness "
        f"mean {1e3 * statistics.fmean(lat):.3f} ms max "
        f"{1e3 * max(lat):.3f} ms")
    if not steps or not out["uids"]:
        log("bench: the window saw no engine step or no request")
        return 6

    trace = None
    if args.trace:
        import trace_reduce
        trace = trace_reduce.reduce(out["trace_dir"])
        shutil.rmtree(out["trace_dir"], ignore_errors=True)

    # -- correct: the sampled logits against the plain reference -------------
    t_ref = time.perf_counter()
    _, gap, err = compare(spec["arch"], args.seed, cfg, out)
    gap_max, err_max = float(gap.max()), float(err.max())
    checks = {
        "logit_gap": {"value": gap_max, "limit": cfg["check"]["gap_limit"]},
        "logit_err": {"value": err_max, "limit": cfg["check"]["err_limit"]},
        # the cache's position must have moved once per decode step fed
        "pos_drift": {"value": abs(out["pos"] - n_fed), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"reference: {len(out['at'])} (step, row) pairs, "
        f"{time.perf_counter() - t_ref:.3f} s; gaps median "
        f"{np.median(gap):.6g} max {gap_max:.6g}; relative logit error "
        f"median {np.median(err):.6g} max {err_max:.6g}")

    # -- metrics --------------------------------------------------------------
    e2e = {
        "out_tok_s": lambda: out["n_tok"] / args.seconds,
        "ttft_p90_ms": lambda: 1e3 * percentile(ttft, 90),
        "itl_mean_ms": lambda: 1e3 * statistics.fmean(gaps),
        "itl_p99_ms": lambda: 1e3 * percentile(gaps, 99),
        "setup_s": lambda: out["setup_s"],
    }
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["peak"]}
    if not args.trace:
        for mt in spec["end_to_end"]:
            metrics[mt["name"]] = {"value": e2e[mt["name"]](),
                                   "unit": mt["unit"]}
    else:
        counters = {"steps": steps, "ttft_s": ttft, "rows": out["rows"],
                    "cache_len": cache_len, "loads": out["loads"],
                    "admitted": sum(s.admitted for s in steps),
                    "fed_idx": out["fed_idx"]}
        ctx = {"counters": counters, "trace": trace, "config": cfg,
               "arch": spec["arch"], "traffic": mix, "rank": out["rank"],
               "device_kind": dev.device_kind}
        for mt in spec["per_layer"]:
            value = load_reader(mt["name"])(ctx)
            if value is not None:
                metrics[mt["name"]] = {"value": value, "unit": mt["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    result = {"correct": bool(correct), "attempted": len(out["uids"]),
              "failed": 0, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = trace["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
