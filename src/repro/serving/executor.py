"""Executors: supply the step-time components of Eq. (1) to the engine.

``JaxExecutor`` runs a model's decode step with per-request LoRA adapters
through the real JAX code path and reports measured wall times: the
published config on a TPU chip, or a reduced one on the CPU in tests.

``SyntheticExecutor`` reports times from a hidden hardware profile
(defaults calibrated to the paper's H100 + Llama-3.1-8B magnitudes).  It
lets the engine play the role of the paper's *real system* at full scale
(hour-long horizons, hundreds of adapters) on a CPU-only box: the Digital
Twin never sees the profile constants — it must recover them from
benchmark data, exactly as the paper fits its estimators from real
benchmarks.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import numpy as np

from ..tracing import NULL_TRACER
from .scheduler import StepPlan


@dataclasses.dataclass
class StepTiming:
    sched: float
    load: float
    model: float

    @property
    def total(self) -> float:
        return self.sched + self.load + self.model


@dataclasses.dataclass
class HardwareProfile:
    """Hidden ground-truth constants of the synthetic serving node."""
    name: str = "h100-llama8b"
    # Lat_sched = s1*R_run + s2*R_wait + s3*R_wait*(slots/adapters)
    s1: float = 8e-6
    s2: float = 4e-6
    s3: float = 2.5e-5
    sched_base: float = 4e-4
    # Lat_model = m1*R_run + m2*prefill_tokens + m_base
    m1: float = 2.2e-4
    m2: float = 6.5e-6
    m_base: float = 2.4e-2
    # Lat_adapters (multiplicative) = 1 + a1*A_unique (+a0 if any adapter)
    a0: float = 0.06
    a1: float = 0.004
    # loading: seconds per rank unit from cpu / disk
    load_cpu_per_rank: float = 1.1e-3
    load_cpu_base: float = 8e-3
    load_disk_mult: float = 1.7
    # memory model (tokens of KV per device after weights)
    total_kv_tokens: int = 200_000
    kv_tokens_per_rank_slot: float = 220.0
    noise: float = 0.015

    def kv_capacity(self, slots: int, mean_rank: float) -> int:
        cap = self.total_kv_tokens - \
            int(slots * mean_rank / 8.0 * self.kv_tokens_per_rank_slot)
        return max(cap, 0)


class SyntheticExecutor:
    def __init__(self, profile: Optional[HardwareProfile] = None,
                 ranks: Optional[Dict[int, int]] = None,
                 slots: int = 0, n_adapters: int = 1, seed: int = 0):
        self.profile = profile or HardwareProfile()
        self.ranks = ranks or {}
        self.slots = max(slots, 1)
        self.n_adapters = max(n_adapters, 1)
        self.rng = np.random.default_rng(seed)

    def _noise(self) -> float:
        p = self.profile
        return float(1.0 + self.rng.normal(0.0, p.noise)) if p.noise else 1.0

    def step(self, plan: StepPlan, n_waiting: int) -> StepTiming:
        p = self.profile
        r_run = len(plan.running)
        sched = (p.sched_base + p.s1 * r_run + p.s2 * n_waiting
                 + p.s3 * n_waiting * (self.slots / self.n_adapters))
        load = 0.0
        for uid in plan.cold_loads:
            rank = self.ranks.get(uid, 8)
            load += (p.load_cpu_base + p.load_cpu_per_rank * rank)
        model = p.m_base + p.m1 * r_run + p.m2 * plan.prefill_tokens
        a = len(plan.unique_adapters)
        adapters_mult = 1.0 + (p.a0 + p.a1 * a if a > 0 else 0.0)
        model *= adapters_mult
        return StepTiming(sched=sched * self._noise(),
                          load=load * self._noise(),
                          model=model * self._noise())


class JaxExecutor:
    """Runs a real model, one decode step per engine step, on whatever
    device JAX gives it (the TPU chip, or the CPU in tests).

    Uses padded static batch shapes (requests packed into a fixed-capacity
    batch with an active mask) so every step hits the same jit cache entry.

    ``step`` records three spans on ``tracer`` (the engine hands it its
    own): ``serve.prepare`` (the adapter ids, to the device),
    ``serve.dispatch`` (the jitted call until it returns) and
    ``serve.sync`` (the wait for its logits).
    """

    def __init__(self, model, params, lora, max_batch: int = 8,
                 cache_len: int = 256):
        import jax
        import jax.numpy as jnp
        self.jax, self.jnp = jax, jnp
        self.model = model
        self.params = params
        self.lora = lora
        self.max_batch = max_batch
        init_cache = jax.jit(model.init_cache, static_argnums=(0, 1))
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        # the step donates its cache: the caller's buffer becomes the
        # returned cache, which the step writes in place
        self.decode = jax.jit(model.decode_step, donate_argnums=(2,))
        self.tracer = NULL_TRACER
        self._slot_of: Dict[int, int] = {}
        # warm up on a cache of its own, so that the served one starts at
        # position 0, all zeros; drop it first, so that two caches are
        # never held at once
        idx = jnp.zeros((max_batch,), jnp.int32)
        out = self.decode(params, lora, init_cache(max_batch, cache_len),
                          self.tokens, idx)
        jax.block_until_ready(out[0])
        del out
        self.cache = init_cache(max_batch, cache_len)

    def step(self, plan: StepPlan, n_waiting: int) -> StepTiming:
        jnp, tracer = self.jnp, self.tracer
        with tracer.span("serve.prepare"):
            t0 = time.perf_counter()
            idx = np.zeros((self.max_batch,), np.int32)
            for i, req in enumerate(plan.running[: self.max_batch]):
                idx[i] = req.adapter % max(self.lora_count(), 1)
            t_sched = time.perf_counter() - t0
            t1 = time.perf_counter()
            idx = jnp.asarray(idx)
        with tracer.span("serve.dispatch"):
            logits, self.cache = self.decode(
                self.params, self.lora, self.cache, self.tokens, idx)
        with tracer.span("serve.sync"):
            self.jax.block_until_ready(logits)
        # emulate prefill cost: extra decode steps pro-rated by tokens
        t_model = time.perf_counter() - t1
        if plan.prefill_tokens:
            t_model *= 1.0 + plan.prefill_tokens / max(len(plan.running), 1)
        t_load = 0.002 * len(plan.cold_loads)
        return StepTiming(sched=t_sched, load=t_load, model=t_model)

    def lora_count(self) -> int:
        seg = self.lora["segments"][0]["blocks"][0]
        for v in seg.values():
            return v.shape[1]
        return 1
