"""The served decode step updates its KV cache in place.

``JaxExecutor`` donates the cache to the jitted step, and the step writes
one position per layer into each global-attention block's stacked cache.
These tests check that the compiled step aliases the cache to its result
and moves no copy of it, that the donated steps give the undonated step's
logits, and that the served cache starts empty.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import Model, ShardingPlan
from repro.serving import JaxExecutor, Request
from repro.serving.scheduler import StepPlan
from tests.hlo_ops import written

KEY = jax.random.PRNGKey(5)
BATCH, CACHE_LEN, STEPS = 3, 40, 6

# (arch, int8 cache): each kind of block whose state the step carries
CASES = {
    "global": ("phi4_mini_3p8b", False),
    "int8": ("phi4_mini_3p8b", True),
    "local": ("gemma3_1b", False),
    "ssd": ("mamba2_2p7b", False),
    "rglru": ("recurrentgemma_9b", False),
}


def _executor(case, dtype=None):
    arch, quant = CASES[case]
    cfg = get_reduced(arch)
    if dtype:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    model = Model(cfg, ShardingPlan(mode="decode", kv_quant=quant))
    params = model.init(KEY)
    lora = model.init_lora(KEY, 4, 4)
    return JaxExecutor(model, params, lora, max_batch=BATCH,
                       cache_len=CACHE_LEN)


def _cache_bytes(cache):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_cache_starts_at_position_zero_all_zeros(case):
    ex = _executor(case)
    assert int(ex.cache["pos"]) == 0
    for leaf in jax.tree.leaves(ex.cache["segments"]):
        assert not np.asarray(leaf).any()


@pytest.mark.parametrize("case", sorted(CASES))
def test_donated_steps_match_the_undonated_step(case):
    ex = _executor(case)
    ref_step = jax.jit(ex.model.decode_step)
    ref_cache = ex.model.init_cache(BATCH, CACHE_LEN)
    ex.tokens = jax.random.randint(KEY, (BATCH, 1), 0,
                                   ex.model.cfg.vocab_size)
    got = []
    decode = ex.decode

    def keep(*args):
        out = decode(*args)
        got.append(np.asarray(out[0]))
        return out

    ex.decode = keep
    running = [Request(uid=i, adapter=i + 1, arrival=0.0, prompt_len=4,
                       output_len=STEPS) for i in range(BATCH)]
    plan = StepPlan(admitted=[], preempted=[], cold_loads=[],
                    running=running)
    for step in range(STEPS):
        donated = ex.cache
        ex.step(plan, 0)
        assert all(a.is_deleted() for a in jax.tree.leaves(donated))
        idx = jnp.array([r.adapter % ex.lora_count() for r in running],
                        jnp.int32)
        want, ref_cache = ref_step(ex.params, ex.lora, ref_cache, ex.tokens,
                                   idx)
        np.testing.assert_array_equal(got[step], np.asarray(want))
    assert int(ex.cache["pos"]) == STEPS
    for a, b in zip(jax.tree.leaves(ex.cache), jax.tree.leaves(ref_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_step_aliases_the_whole_cache(case):
    ex = _executor(case)
    idx = jnp.zeros((BATCH,), jnp.int32)
    compiled = ex.decode.lower(ex.params, ex.lora, ex.cache, ex.tokens,
                               idx).compile()
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert alias >= _cache_bytes(ex.cache), alias


def test_dense_step_writes_no_cache_sized_array_but_the_update():
    # float32, as the CPU compiler widens a bf16 update to float32 and back
    ex = _executor("global", dtype="float32")
    idx = jnp.zeros((BATCH,), jnp.int32)
    text = ex.decode.lower(ex.params, ex.lora, ex.cache, ex.tokens,
                           idx).compile().as_text()
    for name in ("k", "v"):
        stack = ex.cache["segments"][0]["blocks"][0][name]
        assert written(text, stack.shape) == [], name
