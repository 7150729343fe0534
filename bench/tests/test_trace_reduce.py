"""The reduction from trace to device numbers, on a small trace recorded
on one TPU v5e chip (``data/small.xplane.pb``: a few decode steps of
``phi4.tenants`` with the driver's spans), and on made-up intervals."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

SMALL = BENCH / "tests" / "data" / "small.xplane.pb"
PHI4_LAYERS = json.loads(
    (BENCH / "configs" / "phi4-mini-3.8b.json").read_text())["model"][
        "n_layers"]


def test_leaves_drop_enclosing_ops():
    ev = [(0, 10, "%while.1 = loop"), (1, 3, "%a.1 = x"), (3, 9, "%b.2 = y"),
          (12, 13, "%c = z")]
    assert [trace_reduce.short(n) for _, _, n in trace_reduce.leaves(ev)] \
        == ["%a.1", "%b.2", "%c"]


def test_union_and_clip():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]
    assert trace_reduce.clip([(0, 4, "a"), (6, 9, "b"), (10, 12, "c")],
                             2, 8) == [(2, 4), (6, 8)]


@pytest.fixture(scope="module")
def small():
    import jax
    pd = jax.profiler.ProfileData.from_file(str(SMALL))
    return pd, trace_reduce.reduce(SMALL)


def test_small_trace_counts(small):
    pd, got = small
    spans = trace_reduce.host_spans(pd)
    calls = [s for s in spans if s[2] == "bench.decode_call"]
    # one run of the decode-step program for each decode call the host
    # made (the last may still be running when the trace stops)
    assert got["decode_n"] in (len(calls), len(calls) - 1)
    # bgmv runs once for q and once for v in every layer
    assert got["bgmv_n"] == 2 * PHI4_LAYERS * got["decode_n"]
    assert 0 < got["bgmv_s"] < got["decode_s"] <= got["window_s"]
    assert 0 < got["busy_s"] <= got["window_s"]
    # the device runs little but the decode step in the window
    assert got["busy_s"] <= got["decode_s"] * 1.01


def test_small_trace_breakdown(small):
    _, got = small
    ops, gaps = got["breakdown"]["device_ops"], got["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(s for _, s in ops) <= got["busy_s"] * (1 + 1e-9)
    for name, s in gaps:
        assert name == "outside" or name.startswith("bench.")
        assert 0 <= s <= got["window_s"]
    idle = got["window_s"] - got["busy_s"]
    assert sum(s for _, s in gaps) <= idle * (1 + 1e-9)
