"""The readers of the program's tracer and named scopes, on hand-made
inputs and on a tiny cell run on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_program_spans.py
"""
import statistics
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))

import op_scopes  # noqa: E402
import program_spans  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402
from repro import tracing  # noqa: E402
from repro.serving import (AdapterSlotCache, PagedKVCache, Request,  # noqa
                           Scheduler)
from repro.tracing import Tracer  # noqa: E402

NEW = ("queue_wait_p90_ms", "slot_wait_share", "dispatch_ms_per_step",
       "lora_share")


class Clock:
    """A wall clock that reads what the test sets."""
    t = 0.0

    def perf_counter(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(tracing, "time", c)
    return c


def slot_scenario(clock) -> Tracer:
    """2 slots, 3 adapters, all three requests submitted at 0.  The call at
    1 admits requests 0 and 1 and skips 2 for want of a slot; request 0
    finishes, and the call at 5 admits 2.  Waits 1, 1 and 5 s; request 2
    waited 4 s (from the call at 1 to the call at 5) for a slot."""
    tracer = Tracer()
    sched = Scheduler(PagedKVCache(1024, block_size=16), AdapterSlotCache(2),
                      8, tracer=tracer)
    reqs = [Request(uid=i, adapter=i, arrival=0.0, prompt_len=4,
                    output_len=4) for i in range(3)]
    for r in reqs:
        tracer.begin("serve.queued", r.uid)
    sched.add(reqs)
    for t in (1.0, 5.0):
        clock.t = t
        with tracer.span("serve.schedule"):
            sched.schedule(t)
        if t == 1.0:
            sched.finish(reqs[0])
    return tracer


def test_slot_wait_is_charged_to_the_request_that_waited(clock):
    tracer = slot_scenario(clock)
    split = program_spans.wait_split(tracer, 0.5, 10.0)
    assert split == {"slot": 4.0, "rows": 0.0, "kv": 0.0, "other": 0.0,
                     "next_step": 3.0}
    ctx = {"tracer": tracer, "window": (0.5, 10.0)}
    read = run.load_reader
    assert read("slot_wait_share")(ctx) == pytest.approx(100 * 4 / 7)
    assert read("queue_wait_p90_ms")(ctx) == pytest.approx(
        1e3 * statistics.quantiles([1.0, 1.0, 5.0], n=100)[89])
    # only request 2 was admitted in [2, 10): all but its first second
    # of waiting was for a slot; one sample has no p90
    late = {"tracer": tracer, "window": (2.0, 10.0)}
    assert read("slot_wait_share")(late) == pytest.approx(80.0)
    assert read("queue_wait_p90_ms")(late) is None


def test_dispatch_ms_per_step_reads_the_windows_dispatch_spans(clock):
    tracer = Tracer()
    for start, dur in ((0.0, 9.0), (1.0, 0.002), (2.0, 0.004)):
        clock.t = start
        with tracer.span("serve.dispatch"):
            clock.t = start + dur
    ctx = {"tracer": tracer, "window": (0.5, 3.0)}
    assert run.load_reader("dispatch_ms_per_step")(ctx) == \
        pytest.approx(3.0)


def test_lora_share_reads_the_scope_times():
    scopes = {"decode_n": 2, "decode_s": 0.08, "ops_s": 0.079,
              "by_scope": {"lora": 0.02, "mlp": 0.05, "unscoped": 0.009}}
    assert run.load_reader("lora_share")({"scopes": scopes}) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_without_their_input(name):
    """As ``run.py`` builds its context today: no tracer, no scopes."""
    ctx = {"counters": {}, "trace": None, "config": {}, "traffic": {}}
    assert run.load_reader(name)(ctx) is None


def test_innermost_names_each_piece_by_the_deepest_span():
    spans = [(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (6, 8, "d")]
    assert program_spans.innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 6, "a"),
        (6, 8, "d"), (8, 10, "a")]
    segs = program_spans.innermost(spans)
    assert program_spans.intersect([(1, 3.5), (7, 9)], segs) == [
        (1, 2, "a"), (2, 3, "b"), (3, 3.5, "c"), (7, 8, "d"), (8, 9, "a")]


HLO = """\
%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/while/body/attn_proj/lora/mul"}
}

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy.4 = f32[4]{0} copy(%fusion.3)
  ROOT %add.5 = f32[4]{0} add(%copy.4, %x), metadata={op_name="jit(f)/mlp/add"}
}
"""


def test_op_scopes_maps_instructions_and_fusions():
    m = op_scopes.op_scopes(HLO)
    assert m["%fusion.3"] == "lora"       # from its computation's root
    assert m["%add.5"] == "mlp"
    assert m["%copy.4"] is None
    assert op_scopes.check(m, ["%fusion.3", "%copy.4"], ["jit_f(42)"],
                           "7").startswith("names")
    assert op_scopes.check(m, ["%fusion.3"], ["jit_f(42)"],
                           "42") == "fingerprint and names"
    # a binary fingerprint read as the trace's decimal id
    fp = (42).to_bytes(8, "little") + bytes([0xa0] * 8)
    assert op_scopes.check(m, ["%fusion.3"], ["jit_f(42)"],
                           fp) == "fingerprint and names"
    with pytest.raises(ValueError):
        op_scopes.check(m, ["%other.9"], ["jit_f(42)"], "42")


def test_op_scopes_on_the_compiled_tiny_decode_step():
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_reduced
    from repro.models import Model, ShardingPlan
    model = Model(get_reduced("phi4-mini-3.8b"), ShardingPlan(mode="decode"))
    key = jax.random.PRNGKey(0)
    args = (model.init(key), model.init_lora(key, 3, 8),
            model.init_cache(4, 32), jnp.zeros((4, 1), jnp.int32),
            jnp.zeros((4,), jnp.int32))
    compiled = jax.jit(model.decode_step).lower(*args).compile()
    m = op_scopes.op_scopes(compiled.as_text())
    found = set(m.values())
    assert {"lora", "attn_proj", "attention", "mlp", "head"} <= found
    assert op_scopes.check(m, list(m), [], op_scopes.fingerprint(
        compiled)).startswith("names")


def test_tiny_cell_tracer_counts_match_the_drivers(monkeypatch):
    import driver as driver_lib
    import span_report
    kept = []

    class Keep(driver_lib.Driver):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept.append(self)

    monkeypatch.setattr(run, "Driver", Keep)
    rep = span_report.report("phi4.tenants", 2 ** 31 + 17, 3.0,
                             require_chip=False, shrink=tiny.shrink)
    d, c = kept[0], rep["tracer"].counters
    assert c["steps"] == len(d.steps) > 0
    assert c["rows_decoded"] == sum(s.running for s in d.steps)
    assert c["admitted"] == sum(s.admitted for s in d.steps)
    assert c["cold_loads"] == d.loads1
    m = rep["metrics"]
    assert m["dispatch_ms_per_step"] > 0
    assert m["queue_wait_p90_ms"] > 0
    assert 0 <= m["slot_wait_share"] <= 100
    # a CPU trace has no device plane
    assert m["lora_share"] is None and rep["idle"] is None
    assert rep["compiles_in_window"] == 0


SMALL = BENCH / "tests" / "data" / "small.xplane.pb"


def test_scope_times_and_idle_split_on_a_chip_trace():
    """The committed trace of a few phi4 decode steps on one v5e (recorded
    before the program had spans or scopes): every operation unscoped, the
    operations fill the program's time, and all idle time is outside any
    ``serve.*`` span."""
    import jax
    import trace_reduce
    pd = jax.profiler.ProfileData.from_file(str(SMALL))
    reduced = trace_reduce.reduce(SMALL)
    sc = op_scopes.scope_times(pd, {}, 0, 1 << 62)
    assert sc["decode_n"] >= reduced["decode_n"] > 0
    assert set(sc["by_scope"]) == {"unscoped"}
    assert sc["ops_s"] == pytest.approx(sc["decode_s"], rel=0.01)
    idle = program_spans.idle_by_span(pd)
    assert idle["window_s"] == pytest.approx(reduced["window_s"])
    assert idle["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert set(idle["by_span"]) == {"none"}
    assert 0 < sum(idle["in_engine_step"].values()) <= idle["idle_s"]
    gaps = [g for g, _ in idle["longest"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == 5
    for g, parts in idle["longest"]:
        assert set(parts) == {"none"}
        assert sum(parts.values()) == pytest.approx(g)


def test_span_table_and_host_ms_per_step(clock):
    """One engine step by hand: schedule 1 ms, prepare 2, dispatch 3, sync
    4, tokens 5 and 6 ms of the step's own time: 1 + 2 + 5 + 6 = 14 ms on
    the host outside the call and its wait."""
    tracer = Tracer()
    clock.t = 1.0

    def run_for(name, ms):
        with tracer.span(name):
            clock.t += ms / 1e3

    with tracer.span("serve.step"):
        run_for("serve.schedule", 1)
        with tracer.span("serve.execute"):
            for name, ms in (("serve.prepare", 2), ("serve.dispatch", 3),
                             ("serve.sync", 4)):
                run_for(name, ms)
        run_for("serve.tokens", 5)
        clock.t += 6e-3
    assert program_spans.host_ms_per_step(tracer, 0.0, 2.0) == \
        pytest.approx(14.0)
    table = program_spans.span_table(tracer, 0.0, 2.0)
    assert table["serve.step"]["ms"] == pytest.approx(21.0)
    assert table["serve.step"]["self_ms"] == pytest.approx(6.0)
    assert table["serve.execute"]["self_ms"] == pytest.approx(0.0)
    assert program_spans.host_ms_per_step(tracer, 2.0, 3.0) is None
