"""Multi-device equivalence cases, run in a subprocess with 8 host devices.

Usage: python tests/sharded_cases.py <case>   (exit 0 = pass)
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models import Model, ShardingPlan, make_plan  # noqa: E402
from repro.models.transformer import pad_cache  # noqa: E402

KEY = jax.random.PRNGKey(2)


def put(tree, specs, mesh):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))


def repl(mesh, tree):
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(*([None] * x.ndim)))), tree)


def case_train(arch):
    mesh = make_test_mesh(2, 4)
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    ref_model = Model(cfg, ShardingPlan(mode="train"))
    params = ref_model.init(KEY)
    batch = {"tokens": jax.random.randint(KEY, (4, 33), 0, cfg.vocab_size)}
    loss_ref = jax.jit(ref_model.train_loss)(params, batch)
    plan = make_plan(cfg, mesh, "train", global_batch=4)
    model = Model(cfg, plan)
    params_sh = put(params, plan.param_specs(params), mesh)
    batch_sh = {"tokens": jax.device_put(
        batch["tokens"], NamedSharding(mesh, P("data", None)))}
    with jax.set_mesh(mesh):
        loss_sh = jax.jit(model.train_loss)(params_sh, batch_sh)
    # MoE aux-balance loss is estimated per data shard under EP (different
    # token subsets), so allow a slightly looser budget for MoE families.
    tol = 5e-3 if cfg.n_experts else 5e-4
    assert abs(float(loss_ref) - float(loss_sh)) < tol, \
        (float(loss_ref), float(loss_sh))


def case_grad(arch):
    """Sharded gradients match single-device gradients."""
    mesh = make_test_mesh(2, 4)
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    ref_model = Model(cfg, ShardingPlan(mode="train"))
    params = ref_model.init(KEY)
    batch = {"tokens": jax.random.randint(KEY, (4, 33), 0, cfg.vocab_size)}
    g_ref = jax.jit(jax.grad(ref_model.train_loss))(params, batch)
    plan = make_plan(cfg, mesh, "train", global_batch=4)
    model = Model(cfg, plan)
    params_sh = put(params, plan.param_specs(params), mesh)
    batch_sh = {"tokens": jax.device_put(
        batch["tokens"], NamedSharding(mesh, P("data", None)))}
    with jax.set_mesh(mesh):
        g_sh = jax.jit(jax.grad(model.train_loss))(params_sh, batch_sh)
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                           / (np.max(np.abs(np.asarray(a))) + 1e-6)),
        g_ref, g_sh)
    worst = max(jax.tree.leaves(errs))
    assert worst < 5e-3, worst


def case_decode(arch, batch=4):
    mesh = make_test_mesh(2, 4)
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    m_pre = Model(cfg, ShardingPlan(mode="prefill"))
    m_dec = Model(cfg, ShardingPlan(mode="decode"))
    params = m_pre.init(KEY)
    lora = m_pre.init_lora(KEY, 4, 4)
    b, s = batch, 32
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    idx = jnp.arange(b, dtype=jnp.int32) % 4
    _, cache = jax.jit(m_pre.prefill)(params, lora, tokens[:, :-1], idx)
    cache = pad_cache(cache, 1)
    logits_ref, _ = jax.jit(m_dec.decode_step)(params, lora, cache,
                                               tokens[:, -1:], idx)
    plan = make_plan(cfg, mesh, "decode", global_batch=b)
    model = Model(cfg, plan)
    params_sh = put(params, plan.param_specs(params), mesh)
    cache_sh = put(cache, plan.cache_specs(cache), mesh)
    dp = plan.batch_axes if plan.batch_axes else None
    tok_sh = jax.device_put(tokens[:, -1:],
                            NamedSharding(mesh, P(dp, None)))
    idx_sh = jax.device_put(idx, NamedSharding(mesh, P(dp)))
    with jax.set_mesh(mesh):
        logits_sh, _ = jax.jit(model.decode_step)(
            params_sh, repl(mesh, lora), cache_sh, tok_sh, idx_sh)
    err = float(jnp.max(jnp.abs(logits_ref - logits_sh)))
    rel = err / (float(jnp.max(jnp.abs(logits_ref))) + 1e-9)
    assert rel < 1e-4, rel


def case_compression():
    """int8 ring all-reduce over 8 shards approximates exact psum."""
    mesh = make_test_mesh(8, 1)
    from repro.training.compression import quantized_psum
    x = jax.random.normal(KEY, (8, 128), jnp.float32)

    def body(xl):
        return quantized_psum(xl[0], "data", 8)

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                      out_specs=P(None), check_vma=False)
    got = np.asarray(f(x))
    want = np.asarray(x.sum(0))
    scale = np.abs(x).max() / 127.0
    assert np.max(np.abs(got - want)) < 8 * scale, \
        (np.max(np.abs(got - want)), scale)


CASES = {
    "train_dense": lambda: case_train("gemma3_1b"),
    "train_moe": lambda: case_train("olmoe_1b_7b"),
    "train_ssm": lambda: case_train("mamba2_2p7b"),
    "train_hybrid": lambda: case_train("recurrentgemma_9b"),
    "grad_dense": lambda: case_grad("phi4_mini_3p8b"),
    "decode_dense": lambda: case_decode("phi4_mini_3p8b"),
    "decode_gqa1": lambda: case_decode("gemma3_1b"),
    "decode_moe": lambda: case_decode("olmoe_1b_7b"),
    "decode_b1": lambda: case_decode("mamba2_2p7b", batch=1),
    "compression": case_compression,
}

if __name__ == "__main__":
    CASES[sys.argv[1]]()
    print(f"{sys.argv[1]} OK")
