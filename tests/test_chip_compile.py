"""Compiles of the served path for a described TPU v5e chip.

Interpret mode runs the Pallas kernel bodies on the CPU but never checks
Mosaic's tiling or fast-memory limits; the TPU compiler, which is
installed even where no chip is attached, does.  These tests compile the
main path's kernels and phi4-mini-3.8b's whole decode step at published
widths for one chip of a described ``v5e:2x2`` topology.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep every such compile in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.bgmv import bgmv
from repro.kernels.sgmv import sgmv
from repro.models import Model, ShardingPlan
from tests.hlo_ops import written

PHI4 = get_config("phi4-mini-3.8b")
D = PHI4.d_model
OUT = {"q": PHI4.n_heads * PHI4.resolved_head_dim,
       "v": PHI4.n_kv_heads * PHI4.resolved_head_dim}
N_SLOTS, RANK, BATCH, CACHE_LEN = 4, 8, 8, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # loading the TPU library otherwise writes driver logs to a fixed
        # directory shared by every process on the machine
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("target", ["q", "v"])
def test_bgmv_compiles_at_phi4_widths(one_chip, target):
    x, a, b, idx = _on(one_chip, (
        jax.ShapeDtypeStruct((BATCH, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((N_SLOTS, D, RANK), jnp.bfloat16),
        jax.ShapeDtypeStruct((N_SLOTS, RANK, OUT[target]), jnp.bfloat16),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32)))
    compiled = jax.jit(bgmv).lower(x, a, b, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sgmv_compiles_at_prefill_size(one_chip):
    t = 2048
    x, a, b, idx = _on(one_chip, (
        jax.ShapeDtypeStruct((t, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((N_SLOTS, D, RANK), jnp.bfloat16),
        jax.ShapeDtypeStruct((N_SLOTS, RANK, OUT["q"]), jnp.bfloat16),
        jax.ShapeDtypeStruct((t,), jnp.int32)))
    compiled = jax.jit(sgmv).lower(x, a, b, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _phi4_decode_args(one_chip, monkeypatch):
    # jax.default_backend() is the CPU here: steer the model's LoRA
    # dispatch onto the TPU branch the chip takes
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    model = Model(PHI4, ShardingPlan(mode="decode"))
    key = jax.random.PRNGKey(0)
    return model, _on(one_chip, (
        jax.eval_shape(model.init, key),
        jax.eval_shape(lambda k: model.init_lora(k, N_SLOTS, RANK), key),
        jax.eval_shape(lambda: model.init_cache(BATCH, CACHE_LEN)),
        jax.ShapeDtypeStruct((BATCH, 1), jnp.int32),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32)))


def test_phi4_decode_step_compiles_with_pallas_lora(one_chip, monkeypatch):
    model, args = _phi4_decode_args(one_chip, monkeypatch)
    compiled = jax.jit(model.decode_step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # weights + KV cache + step temporaries fit a 16 GB v5e chip
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used


def test_phi4_donated_decode_step_updates_its_cache_in_place(one_chip,
                                                             monkeypatch):
    # jitted as JaxExecutor jits it: the cache, argument 2, is donated
    model, args = _phi4_decode_args(one_chip, monkeypatch)
    compiled = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        *args).compile()
    stack = args[2]["segments"][0]["blocks"][0]["k"]
    kv_bytes = 2 * stack.size * stack.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= kv_bytes, mem.alias_size_in_bytes
    # no copy of the stacked cache, nor of one layer of it, as temporaries
    assert mem.temp_size_in_bytes < kv_bytes / 4, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert written(text, stack.shape) == []
    assert written(text, stack.shape[1:]) == []
