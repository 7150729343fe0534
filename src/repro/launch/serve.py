"""Serving launcher: run the multi-adapter engine with the real JAX executor
under a Poisson multi-adapter workload.

On the chip, the published config (random weights from a seed):

    python -m repro.launch.serve --arch phi4-mini-3.8b --horizon 3

On the CPU, the tiny structurally identical preset:

    JAX_PLATFORMS=cpu python -m repro.launch.serve --arch phi4-mini-3.8b \
        --reduced --adapters 8 --slots 4 --rate 0.5 --horizon 30
"""
from __future__ import annotations

import argparse

import jax

from ..configs import get_config, get_reduced
from ..core.workload import WorkloadSpec, generate_requests, make_adapter_pool
from ..models import Model, ShardingPlan
from ..serving import EngineConfig, JaxExecutor, ServingEngine
from ..serving.metrics import ServingMetrics
from ..serving.policy import SCHED_POLICIES
from .compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface (exposed so tools/check_docs.py can cross-check
    documented flags against the real parser)."""
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config (smoke/demo)")
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--rate", type=float, default=0.5)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--horizon", type=float, default=30.0)
    ap.add_argument("--dataset", default="small")
    ap.add_argument("--kv-tokens", type=int, default=4096)
    ap.add_argument("--sched-policy", default="fcfs",
                    choices=sorted(SCHED_POLICIES),
                    help="admission/preemption scheduling policy")
    return ap


def build_executor(args: argparse.Namespace) -> JaxExecutor:
    """The model, its weights and LoRA bank (random from seed 0), and the
    executor, warmed up so the decode compile stays out of the served
    window.  Weights are built under ``jax.jit`` so the f32 draws that
    ``dense_init`` casts from never sit whole in device memory."""
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg, ShardingPlan(mode="decode"))
    key = jax.random.PRNGKey(0)
    params = jax.jit(model.init)(key)
    lora = jax.jit(model.init_lora, static_argnums=(1, 2))(
        key, max(args.slots, 1), args.rank)
    return JaxExecutor(model, params, lora, max_batch=8, cache_len=512)


def serve(args: argparse.Namespace, executor: JaxExecutor) -> ServingMetrics:
    """Serve the seeded Poisson workload through ``ServingEngine``."""
    pool = make_adapter_pool(args.adapters, [args.rank], [args.rate])
    spec = WorkloadSpec(adapters=pool, dataset=args.dataset,
                        horizon=args.horizon)
    engine = ServingEngine(EngineConfig(
        kv_capacity_tokens=args.kv_tokens, adapter_slots=args.slots,
        sched_policy=args.sched_policy),
        executor)
    return engine.run(generate_requests(spec), horizon=args.horizon)


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    m = serve(args, build_executor(args))
    print(f"served {m.n_finished} requests | throughput={m.throughput:.1f} "
          f"tok/s (ideal {m.ideal_throughput:.1f}) | itl={m.itl * 1e3:.1f}ms "
          f"| ttft={m.ttft * 1e3:.1f}ms "
          f"(p50 {m.ttft_p50 * 1e3:.1f} / p99 {m.ttft_p99 * 1e3:.1f}) "
          f"| preemptions={m.n_preemptions} "
          f"| loads={m.n_loads} | starved={m.starved} "
          f"| starved_reqs={m.n_starved_requests}")
    if m.starved_per_adapter:
        worst = sorted(m.starved_per_adapter.items(),
                       key=lambda kv: -kv[1])[:5]
        print("  starved requests by adapter: "
              + ", ".join(f"{a}:{c}" for a, c in worst))


if __name__ == "__main__":
    main()
