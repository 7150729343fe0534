"""The counts of operations and bytes, against numbers worked by hand at
both configurations' shapes, reached through their architecture module."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import arch  # noqa: E402
import counts  # noqa: E402


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


PHI4, INTERNLM2 = (config(n)["model"]
                   for n in ("phi4-mini-3.8b", "internlm2-20b-pp4"))
GQA = arch.load(config("phi4-mini-3.8b"))


def bgmv(m, rank, target, tokens, distinct):
    (out,) = [o for t, _, o in GQA.lora_calls(m) if t == target]
    return counts.bgmv_call(m["d_model"], out, rank, tokens, distinct)


def test_layer_params_by_hand():
    # phi4: q 3072x3072, k and v 3072x1024, o 3072x3072, 3 MLP 3072x8192
    assert GQA.layer_matmul_params(PHI4) == (
        9_437_184 + 6_291_456 + 9_437_184 + 75_497_472)
    # internlm2: q 6144x6144, k and v 6144x1024, o 6144x6144, 3 x 6144x16384
    assert GQA.layer_matmul_params(INTERNLM2) == (
        37_748_736 + 12_582_912 + 37_748_736 + 301_989_888)


@pytest.mark.parametrize("m, base, per_position", [
    # 2*32*(100_663_296 + 163_840 LoRA) + 2*3072*200_064 unembedding;
    # attention 2*32*(2*24*128) per attended position
    (PHI4, 7_682_129_920, 393_216),
    # 2*12*(390_070_272 + 311_296) + 2*6144*92_544; 2*12*(2*48*128)
    (INTERNLM2, 10_506_338_304, 294_912),
])
def test_decode_token_flops_by_hand(m, base, per_position):
    for attended in (1, 700, 1280):
        assert GQA.decode_token_flops(m, 16, attended) == \
            base + per_position * attended
    # a step of 13 requests at cache position 99 attends 100 positions
    assert GQA.decode_step_flops(m, 16, 13, 99) == \
        13 * (base + per_position * 100)


def test_bgmv_call_by_hand():
    # one call per target and layer: q to 24*128 (phi4), v to 8*128
    assert GQA.lora_calls(PHI4) == [("q", 32, 3072), ("v", 32, 1024)]
    assert GQA.lora_calls(INTERNLM2) == [("q", 12, 6144), ("v", 12, 1024)]
    # phi4 q: 16 tokens, 5 distinct adapters of rank 16
    flops, nbytes = bgmv(PHI4, 16, "q", 16, 5)
    assert flops == 2 * 16 * (3072 * 16 + 16 * 3072) == 3_145_728
    assert nbytes == 5 * 98_304 * 2 + 16 * (3072 + 3072) * 2 + 16 * 4
    # internlm2 v: o = 8*128
    flops, nbytes = bgmv(INTERNLM2, 16, "v", 16, 1)
    assert flops == 2 * 16 * (6144 * 16 + 16 * 1024)
    assert nbytes == (6144 * 16 + 16 * 1024) * 2 + 16 * (6144 + 1024) * 2 \
        + 64


def test_roofline_and_peaks():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flop_s"] == 197e12 and peak["hbm_byte_s"] == 819e9
    t, bound = counts.roofline_s(3_145_728, 1_179_712, peak)
    assert bound == "memory" and t == pytest.approx(1_179_712 / 819e9)
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
