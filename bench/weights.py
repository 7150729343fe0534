"""Weights and LoRA bank of a cell, drawn from the seed by the benchmark.

The benchmark, not the program, makes the weights, so that the plain
reference can make the same ones again without taking anything the program
made.  ``build`` draws every tensor of one configuration on the device in
one jitted call, in the dtype it is served in, under names of the
benchmark's own (``semantic``), layer group by layer group of the
configuration's module (``bench/arch/``); ``to_program`` places them in
the program's parameter tree.  The reference calls ``build`` again with
the same seed and reads the semantic names.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# name -> stream id; a tensor's draw depends on the seed and this id alone
# (a layer tensor's on its layer too); the modules' ids start at 10
_STREAM = {"embed": 1, "unembed": 2, "final_norm": 3}


def _draw(key, name, shape, dtype, streams=_STREAM):
    """Normal draws with a per-tensor scale: 1/sqrt(fan in) for matrices,
    0.02 for the embedding, 1 + 0.1 N(0, 1) for norm scales."""
    k = jax.random.fold_in(key, streams[name])
    z = jax.random.normal(k, shape, jnp.float32)
    if name.startswith("norm") or name == "final_norm":
        return (1.0 + 0.1 * z).astype(dtype)
    if name == "embed":
        return (0.02 * z).astype(dtype)
    return (z / math.sqrt(shape[-2])).astype(dtype)


def _stack(key, name, first, n, shape, dtype, streams):
    # one draw per layer, keyed by the layer's index in the whole stack, so
    # a layer's values never depend on how many layers are drawn beside it
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(first, first + n))
    return jax.lax.map(lambda k: _draw(k, name, shape, dtype, streams), keys)


@functools.partial(jax.jit, static_argnames=("arch", "m_items", "vpad",
                                             "slots", "rank"))
def _build(key, arch, m_items, vpad, slots, rank):
    m = dict(m_items)
    dt = jnp.dtype(m["dtype"])
    d = m["d_model"]
    out = {"embed": _draw(key, "embed", (vpad, d), dt),
           "final_norm": _draw(key, "final_norm", (d,), dt),
           "groups": []}
    first = 0
    for g in arch.groups(m, slots, rank):
        out["groups"].append({
            part: {k: _stack(key, k, first, g["n"], s, dt, arch.STREAMS)
                   for k, s in g[shapes].items()}
            for part, shapes in (("layers", "shapes"), ("lora", "lora"))})
        first += g["n"]
    if not m["tie_embeddings"]:
        out["unembed"] = _draw(key, "unembed", (d, vpad), dt)
    return out


def frozen(model: dict) -> tuple:
    """A configuration as a hashable static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def build(arch, seed: int, model: dict, vpad: int, slots: int,
          rank: int) -> dict:
    """Every tensor of the cell, keyed by the benchmark's names.

    ``arch`` is the configuration's module (``arch.load``); ``model`` the
    configuration file's ``model`` object; ``vpad`` the number of embedding
    rows the program holds (its padded vocabulary)."""
    ids = list(arch.STREAMS.values())
    if min(ids) < 10 or len(set(ids)) < len(ids):
        raise ValueError(f"{arch.__name__}.STREAMS: ids must differ, >= 10")
    # "rbg" draws with the chip's random-bit generator: a whole model's
    # weights in a second or two, where threefry takes several
    key = jax.random.key(seed % (2 ** 32), impl="rbg")
    key = jax.random.fold_in(key, seed // (2 ** 32))
    return _build(key, arch, frozen(model), vpad, slots, rank)


def to_program(arch, sem: dict) -> tuple:
    """(params, lora) in the layout of ``Model.init`` / ``Model.init_lora``:
    the module's stack, with the embedding and the final norm."""
    params, lora = arch.to_program(sem)
    embed = {"embed": sem["embed"]}
    if "unembed" in sem:
        embed["unembed"] = sem["unembed"]
    params = {"embed": embed, "final_norm": {"scale": sem["final_norm"]},
              **params}
    return params, lora


def same_layout(a, b) -> bool:
    """True where two trees have one structure, shapes and dtypes."""
    sa = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), a)
    sb = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), b)
    return (jax.tree.structure(a) == jax.tree.structure(b)
            and jax.tree.leaves(sa) == jax.tree.leaves(sb))
