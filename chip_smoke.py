"""Bring-up check of the served path on one TPU chip.

    python chip_smoke.py

Serves a few requests of phi4-mini-3.8b at its published widths (random
weights from seed 0) through the code ``python -m repro.launch.serve``
runs: ``ServingEngine`` -> ``JaxExecutor`` -> ``Model.decode_step`` with
the Pallas LoRA kernel.  Then it checks that a request finished, that the
decode step stayed inside its KV cache, that the logits are finite, that
the compiled decode step calls the Pallas kernel (``tpu_custom_call``), and
that ``ops.lora_apply`` agrees with its reference at full width.

Earlier lines are set-up observations.  The last line is one JSON object
naming the device.  Where JAX finds no TPU, or a check fails, the script
exits non-zero and prints no such line.  Everything runs in this one
process, the only one that touches the chip.
"""
from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

ARCH = "phi4-mini-3.8b"
# virtual seconds of Poisson traffic: enough for requests of the "small"
# profile (27 output tokens) to finish, and few enough decode steps that
# the executor's 512-slot KV cache never fills (writes past it are dropped)
SERVE_ARGS = ["--arch", ARCH, "--horizon", "3"]
TIMED_STEPS = 20
# |pallas - ref| <= 2^-6 * max|ref|: two bf16 roundings (eps 2^-8) apart
LORA_TOL = 2.0 ** -6


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def compile_log(jax) -> dict:
    """Seconds spent compiling per jitted function (a persistent-cache hit
    counts the load only) and the number of such hits, read from JAX's
    own compile events."""
    log: dict = {"cache_hits": 0}

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            log[name] = log.get(name, 0.0) + duration

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            log["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return log


def lora_agreement(jax, jnp, ops, lora, target: str) -> float:
    """Pallas ``lora_apply`` vs ``force="ref"`` on layer 0's bank for one
    target, in the decode layout (B, 1, d) with per-request ids."""
    a = lora["segments"][0]["blocks"][0][f"a_{target}"][0]     # (N, d, r)
    b = lora["segments"][0]["blocks"][0][f"b_{target}"][0]     # (N, r, o)
    n, d, _ = a.shape
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 1, d), jnp.bfloat16)
    idx = (jnp.arange(8, dtype=jnp.int32) % n).at[4].set(-1)
    pallas = jax.jit(ops.lora_apply).lower(x, a, b, idx).compile()
    check("tpu_custom_call" in pallas.as_text(),
          f"lora_apply[{target}] did not take the Pallas kernel")
    got = pallas(x, a, b, idx).astype(jnp.float32)
    want = ops.lora_apply(x, a, b, idx, force="ref").astype(jnp.float32)
    check(got.shape == want.shape == (8, 1, b.shape[-1]),
          f"lora_apply[{target}] shape {got.shape} vs {want.shape}")
    check(bool(jnp.all(got[4] == 0)),
          f"lora_apply[{target}]: base-model row has a nonzero delta")
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    check(err <= LORA_TOL,
          f"lora_apply[{target}] pallas vs ref: {err:.3e} > {LORA_TOL:.3e}")
    return err


def main() -> int:
    # the package comes from this checkout's src/, so the script runs from
    # the repo root; imported before any device is touched, so that a copy
    # outside the checkout fails without taking the chip
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")

    print(f"compile cache: {enable_compile_cache()}")
    compiles = compile_log(jax)

    # -- build and serve through the launcher's own code ----------------
    args = serve.build_parser().parse_args(SERVE_ARGS)
    t0 = time.perf_counter()
    ex = serve.build_executor(args)
    build_s = time.perf_counter() - t0
    cfg = ex.model.cfg
    check(cfg == get_config(ARCH), f"served config {cfg.name} is not {ARCH}")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(ex.params))
    print(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    print(f"param bytes: {param_bytes}")
    print(f"build s (weights + decode compile + warm-up step): {build_s:.3f}")
    print(f"decode_step compile s: {compiles.get('jit(decode_step)', 0.0):.3f} "
          f"(persistent-cache hits so far: {compiles['cache_hits']})")

    m = serve.serve(args, ex)
    steps = int(ex.cache["pos"])
    cache_len = ex.cache["segments"][0]["blocks"][0]["k"].shape[3]
    print(f"served: finished={m.n_finished} decode_steps={steps} "
          f"cache_len={cache_len}")
    check(m.n_finished >= 1, "no request finished")
    check(steps < cache_len, f"{steps} decode steps overran the "
          f"{cache_len}-slot KV cache")

    # -- the decode step from the served cache state ---------------------
    idx = jnp.arange(ex.max_batch, dtype=jnp.int32) % ex.lora_count()
    hlo = ex.decode.lower(ex.params, ex.lora, ex.cache, ex.tokens,
                          idx).compile().as_text()
    check("tpu_custom_call" in hlo,
          "compiled decode step has no tpu_custom_call (Pallas LoRA kernel)")
    check(steps + TIMED_STEPS < cache_len, f"{steps} + {TIMED_STEPS} decode "
          f"steps would overrun the {cache_len}-slot KV cache")
    times = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        # the step donates its cache: carry the returned one to the next
        logits, ex.cache = ex.decode(ex.params, ex.lora, ex.cache,
                                     ex.tokens, idx)
        jax.block_until_ready(logits)
        times.append(time.perf_counter() - t0)
    check(logits.shape[0] == ex.max_batch
          and logits.shape[1] >= cfg.vocab_size,
          f"logits shape {logits.shape}")
    check(bool(jnp.all(jnp.isfinite(logits))), "non-finite logits")
    print("decode step has tpu_custom_call: yes; logits finite: yes "
          f"{tuple(logits.shape)}")
    print(f"decode step ms (median of {TIMED_STEPS}, batch "
          f"{ex.max_batch}): {statistics.median(times) * 1e3:.3f}")

    # -- the LoRA kernel against its reference, on the chip --------------
    for target in cfg.lora_targets:
        err = lora_agreement(jax, jnp, ops, ex.lora, target)
        print(f"lora_apply[{target}] pallas vs ref: max err / max|ref| = "
              f"{err:.3e} (tol {LORA_TOL:.3e})")

    stats = dev.memory_stats() or {}
    print(f"peak bytes in use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
