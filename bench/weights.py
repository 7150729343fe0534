"""Weights and LoRA bank of a cell, drawn from the seed by the benchmark.

The benchmark, not the program, makes the weights, so that the plain
reference can make the same ones again without taking anything the program
made.  ``build`` draws every tensor of one configuration on the device in
one jitted call, in the dtype it is served in, under names of the
benchmark's own (``semantic``); ``to_program`` places them in the
parameter tree that the program's ``Model`` reads.  The reference calls
``build`` again with the same seed and reads the semantic names.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# name -> stream id; a tensor's draw depends on the seed and this id alone
_STREAM = {"embed": 1, "unembed": 2, "final_norm": 3, "norm1": 10,
           "wq": 11, "wk": 12, "wv": 13, "wo": 14, "norm2": 15,
           "w_gate": 16, "w_up": 17, "w_down": 18,
           "a_q": 30, "b_q": 31, "a_v": 32, "b_v": 33}


def layer_shapes(m: dict) -> dict:
    d, ff = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return {"norm1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "norm2": (d,), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d)}


def lora_shapes(m: dict, slots: int, rank: int) -> dict:
    d = m["d_model"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return {"a_q": (slots, d, rank), "b_q": (slots, rank, q),
            "a_v": (slots, d, rank), "b_v": (slots, rank, kv)}


def _draw(key, name, shape, dtype):
    """Normal draws with a per-tensor scale: 1/sqrt(fan in) for matrices,
    0.02 for the embedding, 1 + 0.1 N(0, 1) for norm scales."""
    k = jax.random.fold_in(key, _STREAM[name])
    z = jax.random.normal(k, shape, jnp.float32)
    if name.startswith("norm") or name == "final_norm":
        return (1.0 + 0.1 * z).astype(dtype)
    if name == "embed":
        return (0.02 * z).astype(dtype)
    return (z / math.sqrt(shape[-2])).astype(dtype)


def _stack(key, name, n, shape, dtype):
    # one draw per leading index, so a layer's values never depend on
    # how many layers are drawn beside it
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    return jax.lax.map(lambda k: _draw(k, name, shape, dtype), keys)


@functools.partial(jax.jit, static_argnames=("m_items", "vpad", "slots",
                                             "rank"))
def _build(key, m_items, vpad, slots, rank):
    m = dict(m_items)
    dt = jnp.dtype(m["dtype"])
    d, n = m["d_model"], m["n_layers"]
    out = {"embed": _draw(key, "embed", (vpad, d), dt),
           "final_norm": _draw(key, "final_norm", (d,), dt),
           "layers": {k: _stack(key, k, n, s, dt)
                      for k, s in layer_shapes(m).items()},
           "lora": {k: _stack(key, k, n, s, dt)
                    for k, s in lora_shapes(m, slots, rank).items()}}
    if not m["tie_embeddings"]:
        out["unembed"] = _draw(key, "unembed", (d, vpad), dt)
    return out


def frozen(model: dict) -> tuple:
    """A configuration as a hashable static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def build(seed: int, model: dict, vpad: int, slots: int, rank: int) -> dict:
    """Every tensor of the cell, keyed by the benchmark's names.

    ``model`` is the configuration file's ``model`` object; ``vpad`` the
    number of embedding rows the program holds (its padded vocabulary)."""
    # "rbg" draws with the chip's random-bit generator: a whole model's
    # weights in a second or two, where threefry takes several
    key = jax.random.key(seed % (2 ** 32), impl="rbg")
    key = jax.random.fold_in(key, seed // (2 ** 32))
    return _build(key, frozen(model), vpad, slots, rank)


def to_program(sem: dict) -> tuple:
    """(params, lora) in the layout of ``Model.init`` / ``Model.init_lora``
    for a one-segment stack of ``global`` attention blocks."""
    lay = sem["layers"]
    block = {"norm1": {"scale": lay["norm1"]}, "wq": lay["wq"],
             "wk": lay["wk"], "wv": lay["wv"], "wo": lay["wo"],
             "norm2": {"scale": lay["norm2"]},
             "mlp": {"w_gate": lay["w_gate"], "w_up": lay["w_up"],
                     "w_down": lay["w_down"]}}
    embed = {"embed": sem["embed"]}
    if "unembed" in sem:
        embed["unembed"] = sem["unembed"]
    params = {"embed": embed, "final_norm": {"scale": sem["final_norm"]},
              "segments": [{"blocks": (block,)}]}
    lora = {"segments": [{"blocks": (dict(sem["lora"]),)}]}
    return params, lora


def same_layout(a, b) -> bool:
    """True where two trees have one structure, shapes and dtypes."""
    sa = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), a)
    sb = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), b)
    return (jax.tree.structure(a) == jax.tree.structure(b)
            and jax.tree.leaves(sa) == jax.tree.leaves(sb))
