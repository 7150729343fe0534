"""The plain reference: a decoder forward pass in float32 jax.numpy.

It imports nothing of the program.  It makes the weights again from the
seed with the benchmark's own ``weights.build``, and replays what the
program was fed: at decode step ``p`` every batch row ``b`` took token
``tokens[p, b]`` at position ``p`` with adapter ``idx[p, b]`` (the program
keeps one position for the whole batch).  So row ``b`` is a causal sequence
of ``P`` tokens, and the reference runs it as one full forward pass, layer
by layer and a few rows at a time, so that it fits beside nothing else.

The embedding, then each layer group of the architecture's module
(``bench/arch/``) in order, through the module's ``layer``; then the final
RMSNorm and the (tied or untied) unembedding.

``mode="fp8"`` is the control: every matmul's operands are rounded to
float8 e4m3 (a per-tensor scale for weights, a per-row scale for
activations) before a float32 product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def q8(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(a, w, fp8: bool):
    """a (..., k) @ w (k, n) in float32."""
    if fp8:
        a, w = q8(a, -1), q8(w, None)
    return jnp.einsum("...k,kn->...n", a, w, precision=HI)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, pos, theta):
    """x (P, H, D), pos (P,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def lora_delta(h, a, b, ids, fp8):
    """h (P, d); a (N, d, r); b (N, r, o); ids (P,) -> (P, o)."""
    def one(acc, n):
        y = mm(mm(h, a[n], fp8), b[n], fp8)
        return acc + jnp.where((ids == n)[:, None], y, 0.0), None
    out = jnp.zeros((h.shape[0], b.shape[-1]), jnp.float32)
    return jax.lax.scan(one, out, jnp.arange(a.shape[0]))[0]


@functools.partial(jax.jit, static_argnames=("arch", "group", "m_items",
                                             "fp8", "rows"))
def _layer(tensors, layer, x, ids, arch, group, m_items, fp8, rows):
    m = dict(m_items)
    w = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
         .astype(jnp.float32)
         for k, v in {**tensors["layers"], **tensors["lora"]}.items()}
    b, p_len, d = x.shape
    xb = x.reshape(b // rows, rows, p_len, d)
    ib = ids.reshape(b // rows, rows, p_len)
    out = jax.lax.map(
        lambda a: jax.vmap(
            lambda xx, ii: arch.layer(m, group, w, xx, ii, fp8))(*a),
        (xb, ib))
    return out.reshape(b, p_len, d)


@functools.partial(jax.jit, static_argnames=("m_items", "fp8", "vocab"))
def _logits(sem, x, rows, cols, m_items, fp8, vocab):
    m = dict(m_items)
    h = rms(x[rows, cols], sem["final_norm"].astype(jnp.float32),
            m["norm_eps"])
    if "unembed" in sem:
        w = sem["unembed"][:, :vocab].astype(jnp.float32)
    else:
        w = sem["embed"][:vocab].astype(jnp.float32).T
    return mm(h, w, fp8)


def logits(arch, seed: int, cfg: dict, vpad: int, slots: int, rank: int,
           tokens: np.ndarray, idx: np.ndarray, at: list,
           mode: str = "f32") -> np.ndarray:
    """Reference logits (len(at), vocab) at the (step, row) pairs ``at``.

    arch: the configuration's module; cfg: the configuration file; tokens,
    idx: (P, B) what the program was fed at each decode step."""
    fp8 = mode == "fp8"
    model = cfg["model"]
    m_items = weights.frozen(dict(model, norm_eps=cfg["norm_eps"]))
    with jax.default_matmul_precision("highest"):
        sem = weights.build(arch, seed, model, vpad, slots, rank)
        tok = jnp.asarray(tokens.T)                      # (B, P)
        ids = jnp.asarray(idx.T)
        x = jnp.take(sem["embed"], tok, axis=0).astype(jnp.float32)
        rows = arch.row_block(model, tok.shape[0])
        for tensors, g in zip(sem["groups"],
                              arch.groups(model, slots, rank)):
            for layer in range(g["n"]):
                x = _layer(tensors, layer, x, ids, arch, g["name"], m_items,
                           fp8, rows)
        r = jnp.asarray([b for _, b in at])
        c = jnp.asarray([p for p, _ in at])
        out = _logits(sem, x, r, c, m_items, fp8, model["vocab_size"])
        return np.asarray(out)
