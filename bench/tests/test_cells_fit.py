"""Every cell's decode step, compiled for one chip of a described TPU v5e,
fits the chip and calls the Pallas LoRA kernel; and every cell's driver
runs end to end on the CPU at reduced widths.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_cells_fit.py

The topology is described inside a fixture, never at import (only one
process at a time may load the TPU library).  Nothing runs on a chip.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import cell as cell_lib  # noqa: E402

sys.path.insert(0, str(BENCH / "tests"))
import tiny  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
HBM_BOUND = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(desc.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _model(cfg, slots, rank):
    import run
    from repro.models import Model, ShardingPlan
    prog = {"ModelConfig": __import__("repro.models.config",
                                      fromlist=["ModelConfig"]).ModelConfig}
    return Model(run.model_config(prog, cfg["model"]),
                 ShardingPlan(mode="decode"))


@pytest.mark.parametrize("name", CELLS)
def test_decode_step_fits_one_v5e(one_chip, name, monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    spec = cell_lib.load(name)
    cfg, mix = spec["config"], spec["traffic"]
    rows, cache_len = cfg["serving"]["batch"], cfg["serving"]["cache_len"]
    model = _model(cfg, mix["slots"], mix["rank"])
    key = jax.random.PRNGKey(0)

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)
    args = on((jax.eval_shape(model.init, key),
               jax.eval_shape(lambda k: model.init_lora(
                   k, mix["slots"], mix["rank"]), key),
               jax.eval_shape(lambda: model.init_cache(rows, cache_len)),
               jax.ShapeDtypeStruct((rows, 1), jnp.int32),
               jax.ShapeDtypeStruct((rows,), jnp.int32)))
    compiled = jax.jit(model.decode_step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    print(f"{name}: args {mem.argument_size_in_bytes} out "
          f"{mem.output_size_in_bytes} temp {mem.temp_size_in_bytes} "
          f"sum {used}")
    assert used < HBM_BOUND, used


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_driver_rehearsal_on_cpu(name, trace, capsys):
    import run
    rc = run.run(["--workload", name, "--seed", str(2 ** 31 + 17),
                  "--seconds", "3", "--trace", str(trace)],
                 require_chip=False, shrink=tiny.shrink)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    result = json.loads(out[-1])
    assert result["correct"] is True
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    if trace:
        # a CPU trace has no device plane: no device metric is printed
        for name_ in ("decode_step_ms", "decode_mfu", "bgmv_roofline",
                      "idle_share"):
            assert name_ not in result["metrics"]
        assert "batch_mean" in result["metrics"]
    else:
        assert "setup_s" in result["metrics"]


def test_no_chip_no_result(capsys):
    import run
    rc = run.run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""
