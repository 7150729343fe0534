"""Operations and bytes that the algorithm needs, computed from shapes.

These never count work the program does and the algorithm does not need:
not the copy of an undonated KV cache, not cache positions past a row's
own, not rows of the batch that carry no request.  So a change that
removes such work raises a share and cannot push it past 100%.  What
depends on the architecture is counted in its module (``bench/arch/``).
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


def bgmv_call(d: int, out: int, rank: int, tokens: int,
              distinct: int) -> tuple:
    """(FLOPs, least bytes) of one ``bgmv`` call from width ``d`` to
    ``out``: each distinct adapter's A (d, r) and B (r, o) read once,
    x (T, d) read, y (T, o) written, the ids read."""
    flops = 2 * tokens * (d * rank + rank * out)
    nbytes = (distinct * (d * rank + rank * out) * BF16
              + tokens * (d + out) * BF16 + tokens * 4)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time on the chip, and which bound sets it."""
    tc, tm = flops / peak["bf16_flop_s"], nbytes / peak["hbm_byte_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
