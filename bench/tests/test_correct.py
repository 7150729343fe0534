"""``correct`` on the CPU at reduced widths: a sound run passes, the
float8 control does not, and each fault a serving cell can have, planted
under the timed path, turns ``correct`` false.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_correct.py

The faults: a decode step that returns its KV cache unchanged (the
position still moves); half of the batch left out (those rows' logits are
never computed and read as zeros); an answer altered where it is produced
(one row's logit of one token raised).  A cell on one chip has no exchange
between chips to leave out.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))

import cell as cell_lib  # noqa: E402
import tiny  # noqa: E402

CELL = "phi4.tenants"


def cpu_limits():
    """(gap, err) limits of the cell's configuration at its CPU size."""
    c = cell_lib.load(CELL)["config"]["cpu"]["check"]
    return c["gap_limit"], c["err_limit"]


def run_once(seed, capsys):
    import run
    rc = run.run(["--workload", CELL, "--seed", str(seed), "--seconds", "3",
                  "--trace", "0"], require_chip=False, shrink=tiny.shrink)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    assert run_once(2 ** 33 + 5, capsys)["correct"] is True


def test_control_fails_the_limit():
    import control
    seeds = [401, 402, 403]
    got = control.readings(CELL, 3.0, seeds, set(seeds),
                           require_chip=False, shrink=tiny.shrink)
    gap, err = cpu_limits()
    assert got["gap_lower"] <= gap
    assert got["err_lower"] <= err < got["err_upper"]


def _plant(fault):
    from repro.models import transformer
    import jax.numpy as jnp
    orig = transformer.Model.decode_step

    def broken(self, params, lora, cache, tokens, adapter_idx=None):
        logits, new = orig(self, params, lora, cache, tokens, adapter_idx)
        if fault == "state_unchanged":
            new = dict(cache, pos=new["pos"])
        elif fault == "half_batch":
            half = logits.shape[0] // 2
            logits = logits.at[half:].set(jnp.zeros_like(logits[half:]))
        elif fault == "answer_altered":
            logits = logits.at[0, 7].add(100.0)
        return logits, new
    return broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_turns_correct_false(fault, monkeypatch, capsys):
    from repro.models import transformer
    monkeypatch.setattr(transformer.Model, "decode_step", _plant(fault))
    result = run_once(2 ** 31 + 99, capsys)
    assert result["correct"] is False
    checks = result["checks"]
    gap, err = cpu_limits()
    assert (checks["logit_gap"]["value"] > gap
            or checks["logit_err"]["value"] > err)
