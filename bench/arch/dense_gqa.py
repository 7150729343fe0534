"""A dense decoder of grouped-query attention blocks (phi4-mini, InternLM2).

Every layer is one ``global`` block of the program, and the reference's
layer follows the published decoder: RMSNorm, q/k/v projections with a
LoRA delta ``x @ A[id] @ B[id]`` on each of the configuration's
``lora_targets`` (q and v), rotary embedding (rotate-half, full head),
causal grouped-query attention, output projection, residual, RMSNorm,
SwiGLU MLP, residual.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import HI, lora_delta, mm, q8, rms, rope

# a tensor's draw depends on the seed, its layer and this id alone
STREAMS = {"norm1": 10, "wq": 11, "wk": 12, "wv": 13, "wo": 14, "norm2": 15,
           "w_gate": 16, "w_up": 17, "w_down": 18,
           "a_q": 30, "b_q": 31, "a_v": 32, "b_v": 33}


def _widths(m: dict) -> tuple:
    hd = m["head_dim"]
    return m["d_model"], m["n_heads"] * hd, m["n_kv_heads"] * hd


def _lora_outs(m: dict) -> dict:
    """Output width of each LoRA target the configuration names."""
    _, q, kv = _widths(m)
    return {t: {"q": q, "v": kv}[t] for t in m["lora_targets"]}


def groups(m: dict, slots: int, rank: int) -> list:
    d, q, kv = _widths(m)
    ff = m["d_ff"]
    lora = {}
    for t, o in _lora_outs(m).items():
        lora.update({f"a_{t}": (slots, d, rank), f"b_{t}": (slots, rank, o)})
    return [{"name": "layers", "n": m["n_layers"],
             "shapes": {"norm1": (d,), "wq": (d, q), "wk": (d, kv),
                        "wv": (d, kv), "wo": (q, d), "norm2": (d,),
                        "w_gate": (d, ff), "w_up": (d, ff),
                        "w_down": (ff, d)},
             "lora": lora}]


def to_program(sem: dict) -> tuple:
    """A one-segment stack of ``global`` attention blocks."""
    (g,) = sem["groups"]
    lay = g["layers"]
    block = {"norm1": {"scale": lay["norm1"]}, "wq": lay["wq"],
             "wk": lay["wk"], "wv": lay["wv"], "wo": lay["wo"],
             "norm2": {"scale": lay["norm2"]},
             "mlp": {"w_gate": lay["w_gate"], "w_up": lay["w_up"],
                     "w_down": lay["w_down"]}}
    return ({"segments": [{"blocks": (block,)}]},
            {"segments": [{"blocks": (dict(g["lora"]),)}]})


def layer(m: dict, group: str, w: dict, x, ids, fp8: bool):
    """One decoder layer over one row's whole sequence x (P, d)."""
    p_len = x.shape[0]
    hd, nq, nkv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    eps = m["norm_eps"]
    pos = jnp.arange(p_len)
    h = rms(x, w["norm1"], eps)

    def proj(t):    # the projection, with its LoRA delta where t is a target
        y = mm(h, w[f"w{t}"], fp8)
        if f"a_{t}" in w:
            y = y + lora_delta(h, w[f"a_{t}"], w[f"b_{t}"], ids, fp8)
        return y
    q, k, v = proj("q"), proj("k"), proj("v")
    q = rope(q.reshape(p_len, nq, hd), pos, m["rope_theta"])
    k = rope(k.reshape(p_len, nkv, hd), pos, m["rope_theta"])
    v = v.reshape(p_len, nkv, hd)
    q = q.reshape(p_len, nkv, nq // nkv, hd)
    if fp8:
        q, k, v = q8(q, -1), q8(k, -1), q8(v, -1)
    s = jnp.einsum("qkgd,skd->kgqs", q, k, precision=HI) / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if fp8:
        pr = q8(pr, -1)
    o = jnp.einsum("kgqs,skd->qkgd", pr, v, precision=HI)
    x = x + mm(o.reshape(p_len, nq * hd), w["wo"], fp8)
    h = rms(x, w["norm2"], eps)
    f = jax.nn.silu(mm(h, w["w_gate"], fp8)) * mm(h, w["w_up"], fp8)
    return x + mm(f, w["w_down"], fp8)


def row_block(m: dict, batch: int) -> int:
    """Rows per block: as many as keep one block's attention scores and
    MLP activations near 1 GB in float32."""
    rows = batch
    while rows > 1 and rows * m["n_heads"] * 2048 * 2048 * 4 > 1e9:
        rows //= 2
    return max(rows, 1)


def layer_matmul_params(m: dict) -> int:
    d, q, kv = _widths(m)
    return d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]


def lora_calls(m: dict) -> list:
    """(target, layers, output width) of each ``bgmv`` call a step makes."""
    return [(t, m["n_layers"], o) for t, o in _lora_outs(m).items()]


def decode_token_flops(m: dict, rank: int, attended: int) -> int:
    """FLOPs of one token through the decode step: every matmul weight but
    the embedding gather (the unembedding over the real vocabulary), the
    LoRA deltas, and attention over the ``attended`` positions."""
    d, L = m["d_model"], m["n_layers"]
    per_layer = layer_matmul_params(m)
    per_layer += sum(d * rank + rank * o for _, _, o in lora_calls(m))
    attn = 2 * m["n_heads"] * m["head_dim"] * attended   # QK^T and PV
    return 2 * L * per_layer + 2 * L * attn + 2 * d * m["vocab_size"]


def decode_step_flops(m: dict, rank: int, rows: int, pos: int) -> int:
    """A decode step at cache position ``pos`` with ``rows`` requests:
    each attends positions 0..pos."""
    return rows * decode_token_flops(m, rank, pos + 1)
