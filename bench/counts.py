"""Operations and bytes that the algorithm needs, computed from shapes.

These never count work the program does and the algorithm does not need:
not the copy of an undonated KV cache, not cache positions past a row's
own, not rows of the batch that carry no request.  So a change that
removes such work raises a share and cannot push it past 100%.
"""
from __future__ import annotations

import json
from pathlib import Path

BF16 = 2


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def layer_matmul_params(m: dict) -> int:
    d, ff, hd = m["d_model"], m["d_ff"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def lora_outs(m: dict) -> dict:
    hd = m["head_dim"]
    return {"q": m["n_heads"] * hd, "v": m["n_kv_heads"] * hd}


def decode_token_flops(m: dict, rank: int, attended: int) -> int:
    """FLOPs of one token through the decode step: every matmul weight but
    the embedding gather (the unembedding over the real vocabulary), the
    q/v LoRA delta, and attention over the ``attended`` positions."""
    d, L = m["d_model"], m["n_layers"]
    per_layer = layer_matmul_params(m)
    per_layer += sum(d * rank + rank * o for o in lora_outs(m).values())
    attn = 2 * m["n_heads"] * m["head_dim"] * attended   # QK^T and PV
    return 2 * L * per_layer + 2 * L * attn + 2 * d * m["vocab_size"]


def decode_step_flops(m: dict, rank: int, rows: int, pos: int) -> int:
    """A decode step at cache position ``pos`` with ``rows`` requests:
    each attends positions 0..pos."""
    return rows * decode_token_flops(m, rank, pos + 1)


def bgmv_call(m: dict, rank: int, target: str, tokens: int,
              distinct: int) -> tuple:
    """(FLOPs, least bytes) of one ``bgmv`` call: each distinct adapter's
    A (d, r) and B (r, o) read once, x (T, d) read, y (T, o) written, the
    ids read."""
    d, o = m["d_model"], lora_outs(m)[target]
    flops = 2 * tokens * (d * rank + rank * o)
    nbytes = (distinct * (d * rank + rank * o) * BF16
              + tokens * (d + o) * BF16 + tokens * 4)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time on the chip, and which bound sets it."""
    tc, tm = flops / peak["bf16_flop_s"], nbytes / peak["hbm_byte_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
