"""The architecture modules (``bench/arch/``): the weights and the
reference stay what they were before the modules, a configuration is
added as new files only, and the generic parts walk any module's layer
groups in order.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_arch.py
"""
import hashlib
import json
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

import arch as arch_lib  # noqa: E402
import reference  # noqa: E402
import tiny  # noqa: E402
import weights  # noqa: E402

SEED, VPAD, SLOTS, RANK = 2 ** 33 + 5, 640, 3, 4
# what a program fed: (step, row) token and adapter id, -1 for none
TOKENS = np.array([
    [435, 326, 261, 138], [157, 20, 38, 8], [89, 416, 332, 467],
    [257, 310, 497, 373], [323, 278, 286, 478], [142, 417, 343, 1],
    [201, 438, 283, 17], [391, 373, 433, 89], [45, 441, 11, 277],
    [41, 153, 246, 216], [206, 14, 2, 63], [4, 343, 269, 331]])
IDX = np.array([
    [0, 1, 2, 0], [0, 2, 2, 2], [0, 1, 2, 1], [2, 1, 1, 0], [2, -1, 1, 1],
    [2, 1, 0, 0], [0, 0, 1, 2], [-1, 2, 1, 0], [1, 1, 0, 0], [1, 1, 1, 0],
    [2, 0, 0, 2], [0, -1, 1, 1]])
AT = [(0, 0), (5, 1), (11, 2), (11, 3), (7, 0)]

# Recorded before the GQA code moved into bench/arch/dense_gqa.py, at the
# configurations' CPU sizes (both have the same widths there, so only the
# untied head differs): sha256 of each tensor's dtype, shape and bytes.
LAYERS = {"a_q": "08e3c25722862eee", "a_v": "f7d0f318055eef16",
          "b_q": "f5564b6dffb7b299", "b_v": "f2794c2041f7a750",
          "embed": "73849d9434b2b6c1", "final_norm": "24f14638468c72b4",
          "norm1": "cc1e240b209b5ce6", "norm2": "7b09144676c1468d",
          "w_down": "12f297206eefc0a9", "w_gate": "955ce405f9b62361",
          "w_up": "dc7a3a063213bf63", "wk": "f602a8726f45dde0",
          "wo": "29ae845f0c048385", "wq": "a8b99ef6900bebf0",
          "wv": "0bf8f41dbb64831c"}
WEIGHTS = {"phi4-mini-3.8b": LAYERS,
           "internlm2-20b-pp4": dict(LAYERS, unembed="21908dffa2d49c7b")}

# The reference logits at AT, projected on three fixed random directions
# (PROJ), recorded at the same time.  The CPU's threaded matmul sums in an
# order that depends on its threads, which moves these by up to 1.4e-6;
# the float8 control moves them by 1e-3 to 0.2.
PROJ = np.random.default_rng(1).standard_normal((512, 3)) / np.sqrt(512)
LOGITS = {
    ("phi4-mini-3.8b", "f32"): [
        [-0.07337584, -0.04401358, 0.06632069],
        [-0.2105752, -0.3518047, 0.04026418],
        [0.05178446, 0.07797098, -0.1202115],
        [0.001113091, -0.1029247, -0.1519574],
        [-0.006116003, 0.1084963, -0.07986987]],
    ("phi4-mini-3.8b", "fp8"): [
        [-0.07450367, -0.03418565, 0.08557342],
        [-0.1902678, -0.3611221, 0.07444569],
        [0.07197779, 0.1102587, -0.08474111],
        [-0.02700541, -0.09241406, -0.1423273],
        [-0.02312178, 0.104822, -0.1146415]],
    ("internlm2-20b-pp4", "f32"): [
        [1.937492, 1.880828, 0.7638873],
        [0.4013791, -0.5019999, 1.102059],
        [0.1755788, 1.280916, -1.128418],
        [1.700603, 2.233833, 0.7982835],
        [2.743253, -1.232769, -0.1288026]],
    ("internlm2-20b-pp4", "fp8"): [
        [1.940423, 1.838, 0.90495],
        [0.3201958, -0.5518687, 1.083753],
        [0.2772847, 1.263268, -1.085024],
        [1.5202, 2.102513, 0.8330715],
        [2.861165, -1.167304, 0.06759383]],
}


def checksum(x) -> str:
    a = np.asarray(x)
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode()
                          + a.tobytes()).hexdigest()[:16]


def cpu_config(name):
    return tiny.shrink(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_weights_as_recorded(name):
    cfg = cpu_config(name)
    sem = weights.build(arch_lib.load(cfg), SEED, cfg["model"], VPAD, SLOTS,
                        RANK)
    (group,) = sem.pop("groups")
    got = {k: checksum(v) for k, v in
           {**sem, **group["layers"], **group["lora"]}.items()}
    assert got == WEIGHTS[name]


@pytest.mark.parametrize("name, mode", sorted(LOGITS))
def test_reference_logits_as_recorded(name, mode):
    cfg = cpu_config(name)
    got = reference.logits(arch_lib.load(cfg), SEED, cfg, VPAD, SLOTS, RANK,
                           TOKENS, IDX, AT, mode=mode)
    np.testing.assert_allclose(np.asarray(got, np.float64) @ PROJ,
                               LOGITS[name, mode], rtol=0, atol=1e-5)


# -- a configuration or a mix added as new files only -------------------------

def _copy_benchmark(tmp_path):
    """The benchmark's data files and ``BENCHMARK.json``, copied into
    ``tmp_path`` as a checkout holds them; returns the parsed spec."""
    for part in ("configs", "traffic", "knees"):
        shutil.copytree(BENCH / part, tmp_path / BENCH.name / part)
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _add(tmp_path, rel, obj):
    """A new file of the benchmark, at ``rel`` under ``tmp_path``."""
    path = tmp_path / rel
    assert not path.exists()
    path.write_text(json.dumps(obj))


def _add_cell(tmp_path, spec, cell, config=None):
    """``spec`` with the new cell (and configuration) entries appended,
    written as ``tmp_path/BENCHMARK.json``, whose path it returns."""
    spec["workloads"].append(dict(cell, chips=1, why="a new cell"))
    if config is not None:
        spec["configs"].append(config)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def _tracked():
    files = [ROOT / "BENCHMARK.json"] + [
        p for p in BENCH.rglob("*")
        if p.is_file() and "__pycache__" not in p.parts]
    return {p: p.read_bytes() for p in files}


def _new_config():
    """A configuration of the dense GQA family under a new name, with its
    own CPU sizes (3 layers, 6 query heads on 2 KV heads); its limits stand
    well above eight seeds' readings at this size (gap at most 0.0017,
    error 0.0101)."""
    cfg = json.loads((BENCH / "configs" / "phi4-mini-3.8b.json").read_text())
    cfg["name"] = cfg["model"]["name"] = "new-gqa"
    cfg["cpu"] = {"model": {"n_layers": 3, "d_model": 96, "n_heads": 6,
                            "n_kv_heads": 2, "d_ff": 160, "vocab_size": 384,
                            "head_dim": 16},
                  "serving": {"batch": 4, "cache_len": 8192},
                  "check": {"gap_limit": 0.01, "err_limit": 0.03}}
    return cfg


def _with_new_config(tmp_path, cfg):
    """A new configuration and its cell under the tenants mix: the config
    file, the pair's knee and two ``BENCHMARK.json`` entries."""
    spec = _copy_benchmark(tmp_path)
    rel = f"{BENCH.name}/configs/{cfg['name']}.json"
    _add(tmp_path, rel, cfg)
    _add(tmp_path, f"{BENCH.name}/knees/{cfg['name']}.tenants.json",
         {"knee_req_s": 2.0})
    return _add_cell(
        tmp_path, spec, {"name": "new.tenants", "config": cfg["name"],
                         "traffic": "tenants"},
        {"name": cfg["name"], "source": cfg["source"], "file": rel,
         "reduced": [], "why": "a new configuration"})


def _run_to_correct(bench_file, workload, capsys, seed):
    import run
    rc = run.run(["--workload", workload, "--seed", str(seed), "--seconds",
                  "3", "--trace", "0"], require_chip=False,
                 shrink=tiny.shrink, bench_file=bench_file)
    assert rc == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    return err


def test_new_configuration_is_new_files_only(tmp_path, capsys):
    bench_file = _with_new_config(tmp_path, _new_config())
    before = _tracked()
    _run_to_correct(bench_file, "new.tenants", capsys, 2 ** 31 + 3)
    assert _tracked() == before


def test_new_mix_is_new_files_only(tmp_path, capsys):
    """A new mix on a configuration the benchmark has: the mix file, the
    pair's knee and one ``BENCHMARK.json`` entry; the run offers the new
    mix's share of the new knee."""
    spec = _copy_benchmark(tmp_path)
    mix = json.loads((BENCH / "traffic" / "tenants.json").read_text())
    mix.update(name="fewer", adapters=32, rate_share_of_knee=0.5)
    _add(tmp_path, f"{BENCH.name}/traffic/fewer.json", mix)
    _add(tmp_path, f"{BENCH.name}/knees/phi4-mini-3.8b.fewer.json",
         {"knee_req_s": 3.0})
    bench_file = _add_cell(tmp_path, spec, {
        "name": "phi4.fewer", "config": "phi4-mini-3.8b",
        "traffic": "fewer"})
    before = _tracked()
    import cell as cell_lib
    got = cell_lib.load("phi4.fewer", bench_file)
    assert got["knee"] == 3.0 and got["traffic"]["adapters"] == 32
    err = _run_to_correct(bench_file, "phi4.fewer", capsys, 2 ** 31 + 7)
    assert "offered 1.5 req/s" in err
    assert _tracked() == before


def test_unknown_arch_fails_before_any_build(tmp_path, monkeypatch):
    import run

    def built(*a, **k):
        raise AssertionError("something was built")
    monkeypatch.setattr(run, "import_program", built)
    monkeypatch.setattr(weights, "build", built)
    cfg = dict(_new_config(), arch="no_such_arch")
    bench_file = _with_new_config(tmp_path, cfg)
    with pytest.raises(ValueError, match="no bench/arch/no_such_arch.py"):
        run.run(["--workload", "new.tenants", "--seed", "1", "--seconds",
                 "1"], require_chip=False, bench_file=bench_file)


def test_lora_follows_the_configured_targets():
    """The LoRA banks the weights hold, and the ``bgmv`` calls the readers
    count, are those of the configuration's ``lora_targets``."""
    mod = arch_lib.load({"arch": "dense_gqa"})
    m = dict(cpu_config("phi4-mini-3.8b")["model"], lora_targets=["v"])
    (g,) = mod.groups(m, SLOTS, RANK)
    assert sorted(g["lora"]) == ["a_v", "b_v"]
    assert mod.lora_calls(m) == [("v", 2, 32)]
    with pytest.raises(KeyError, match="o"):
        mod.groups(dict(m, lora_targets=["o"]), SLOTS, RANK)


# -- a module of two layer groups of different shapes ------------------------

def _two_groups(n_mix):
    """Two scaling layers (a norm-like vector each), then ``n_mix`` layers
    of a square matrix with a LoRA delta."""
    mod = types.ModuleType("two_groups")
    mod.STREAMS = {"norm_s": 10, "t": 11, "a_x": 30, "b_x": 31}

    def groups(m, slots, rank):
        d = m["d_model"]
        return [{"name": "scale", "n": 2, "shapes": {"norm_s": (d,)},
                 "lora": {}},
                {"name": "mix", "n": n_mix, "shapes": {"t": (d, d)},
                 "lora": {"a_x": (slots, d, rank), "b_x": (slots, rank, d)}}]

    def layer(m, group, w, x, ids, fp8):
        if group == "scale":
            return x * w["norm_s"]
        return reference.mm(x, w["t"], fp8) + reference.lora_delta(
            x, w["a_x"], w["b_x"], ids, fp8)
    mod.groups, mod.layer = groups, layer
    mod.row_block = lambda m, batch: 2
    return mod


STUB_CFG = {"model": {"d_model": 32, "vocab_size": 200, "dtype": "float32",
                      "tie_embeddings": True}, "norm_eps": 1e-6}


def test_two_groups_drawn_in_order():
    m = STUB_CFG["model"]
    sem = weights.build(_two_groups(2), 5, m, 256, SLOTS, RANK)
    scale, mix = sem["groups"]
    assert scale["layers"]["norm_s"].shape == (2, 32) and not scale["lora"]
    assert mix["layers"]["t"].shape == (2, 32, 32)
    assert mix["lora"]["a_x"].shape == (2, SLOTS, 32, RANK)
    assert mix["lora"]["b_x"].shape == (2, SLOTS, RANK, 32)
    # a layer is keyed by its index in the whole stack: the mix group's
    # first layer is layer 2 whatever follows it, and not layer 0's draw
    one = weights.build(_two_groups(1), 5, m, 256, SLOTS, RANK)
    np.testing.assert_array_equal(one["groups"][1]["layers"]["t"][0],
                                  mix["layers"]["t"][0])
    np.testing.assert_array_equal(one["groups"][0]["layers"]["norm_s"],
                                  scale["layers"]["norm_s"])
    assert not np.array_equal(mix["layers"]["t"][0], mix["layers"]["t"][1])


def test_reference_walks_the_groups_in_order():
    mod = _two_groups(2)
    m = STUB_CFG["model"]
    sem = jax.tree.map(np.asarray, weights.build(mod, 5, m, 256, SLOTS,
                                                 RANK))
    got = reference.logits(mod, 5, STUB_CFG, 256, SLOTS, RANK, TOKENS % 200,
                           IDX, AT)
    x = sem["embed"][(TOKENS % 200).T].astype(np.float64)     # (B, P, d)
    ids = IDX.T
    scale, mix = sem["groups"]
    for s in scale["layers"]["norm_s"]:
        x = x * s
    for i, t in enumerate(mix["layers"]["t"]):
        a, b = mix["lora"]["a_x"][i], mix["lora"]["b_x"][i]
        delta = np.zeros_like(x)
        for n in range(SLOTS):
            delta += (ids == n)[..., None] * (x @ a[n] @ b[n])
        x = x @ t + delta
    h = np.stack([x[r, p] for p, r in AT])
    h = h / np.sqrt(np.mean(h * h, axis=-1, keepdims=True) + 1e-6)
    want = (h * sem["final_norm"]) @ sem["embed"][:200].T
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
