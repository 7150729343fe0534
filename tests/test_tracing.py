"""The program's tracer (``repro.tracing``): it records what the engine,
scheduler and executor do, and changes nothing they compute."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.registry import get_reduced
from repro.core import WorkloadSpec, generate_requests, make_adapter_pool
from repro.models import Model, ShardingPlan
from repro.serving import (AdapterSlotCache, EngineConfig, HardwareProfile,
                           JaxExecutor, PagedKVCache, Request, Scheduler,
                           ServingEngine, SyntheticExecutor)
from repro.serving import executor as executor_mod
from repro.tracing import NULL_TRACER, Tracer

STEP_CHILDREN = {"serve.schedule", "serve.execute", "serve.tokens"}


def _req(uid, adapter=0, p=4, o=4):
    return Request(uid=uid, adapter=adapter, arrival=0.0, prompt_len=p,
                   output_len=o)


# slots, KV tokens and rows chosen so that every blocking branch of the
# scheduler fires: slot skips (tight), KV stops and preemption (tiny KV),
# a full batch (rows)
ENGINE_CASES = {"slots": (2, 200_000, 256), "kv": (8, 400, 256),
                "rows": (8, 200_000, 4)}


def _synthetic_run(tracer, slots, kv_tokens, max_running):
    pool = make_adapter_pool(12, [8, 16], [0.6])
    spec = WorkloadSpec(adapters=pool, dataset="small", horizon=60.0,
                        seed=5)
    cfg = EngineConfig(kv_capacity_tokens=kv_tokens, adapter_slots=slots,
                       max_running=max_running)
    eng = ServingEngine(cfg, SyntheticExecutor(
        HardwareProfile(), {a.uid: a.rank for a in pool}, slots=slots,
        n_adapters=len(pool), seed=1), tracer=tracer)
    return eng, eng.run(generate_requests(spec), horizon=60.0)


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
def test_live_tracer_leaves_synthetic_run_bitwise(case):
    _, plain = _synthetic_run(None, *ENGINE_CASES[case])
    tracer = Tracer()
    eng, traced = _synthetic_run(tracer, *ENGINE_CASES[case])
    assert eng.tracer is tracer and eng.scheduler.tracer is tracer
    assert repr(dataclasses.astuple(plain)) == \
        repr(dataclasses.astuple(traced))
    c = tracer.counters
    assert c["steps"] == eng.n_exec_steps
    assert c["rows_decoded"] == eng.n_tokens_out
    assert c["cold_loads"] == eng.adapters.load_count
    assert c["preempted"] == traced.n_preemptions
    blocking = {"slots": "slot_skips", "kv": "kv_stops", "rows": "rows_full"}
    assert c[blocking[case]] > 0
    # every admission closes one queue span; a preempted request queues
    # again
    queued = [s for s in tracer.spans if s.name == "serve.queued"]
    assert len(tracer.closed("serve.queued")) == c["admitted"]
    assert len(queued) == len(eng._accepted) + c["preempted"]


def test_null_tracer_reads_no_clock(monkeypatch):
    class NoClock:
        def perf_counter(self):
            raise AssertionError("the null tracer read the clock")
    monkeypatch.setattr(tracing, "time", NoClock())
    eng, m = _synthetic_run(None, *ENGINE_CASES["slots"])
    assert eng.tracer is NULL_TRACER and m.n_finished > 0


class _FakeClock:
    """A wall clock that moves 1 ms each time it is read."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 1e-3
        return self.t


def _jax_run(tracer, monkeypatch):
    monkeypatch.setattr(executor_mod, "time", _FakeClock())
    cfg = get_reduced("phi4-mini-3.8b")
    model = Model(cfg, ShardingPlan(mode="decode"))
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    lora = model.init_lora(key, 3, 8)
    ex = JaxExecutor(model, params, lora, max_batch=4, cache_len=64)
    eng = ServingEngine(EngineConfig(kv_capacity_tokens=4096,
                                     adapter_slots=2, max_running=4),
                        ex, tracer=tracer)
    reqs = [Request(uid=i, adapter=i % 3, arrival=0.01 * i, prompt_len=8,
                    output_len=3 + i % 4) for i in range(7)]
    return eng, eng.run(reqs), ex


def test_live_tracer_leaves_jax_executor_bitwise(monkeypatch):
    _, plain, ex0 = _jax_run(None, monkeypatch)
    tracer = Tracer()
    eng, traced, ex1 = _jax_run(tracer, monkeypatch)
    assert ex1.tracer is tracer and ex0.tracer is NULL_TRACER
    assert repr(dataclasses.astuple(plain)) == \
        repr(dataclasses.astuple(traced))
    for a, b in zip(jax.tree.leaves(ex0.cache), jax.tree.leaves(ex1.cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    steps = tracer.closed("serve.step")
    assert len(steps) >= tracer.counters["steps"] > 0
    for name in ("serve.prepare", "serve.dispatch", "serve.sync"):
        assert len(tracer.closed(name)) == tracer.counters["steps"]


def test_span_parents_nest_and_self_time_is_duration_less_children(
        monkeypatch):
    tracer = Tracer()
    _jax_run(tracer, monkeypatch)
    spans = tracer.spans
    selfs = tracer.self_times()
    children = {i: [] for i in range(len(spans))}
    for i, s in enumerate(spans):
        if s.parent >= 0:
            children[s.parent].append(i)
    assert any(children.values())
    for i, s in enumerate(spans):
        kids = [spans[k] for k in children[i]]
        for k in kids:
            assert s.start <= k.start <= k.end <= s.end
        assert selfs[i] == pytest.approx(
            (s.end - s.start) - sum(k.end - k.start for k in kids),
            abs=1e-12)
        assert selfs[i] >= -1e-12
        names = [k.name for k in kids]
        if s.name == "serve.step":
            assert set(names) <= STEP_CHILDREN and \
                names[0] == "serve.schedule"
        if s.name == "serve.execute":
            assert names == ["serve.prepare", "serve.dispatch",
                             "serve.sync"]
        if s.name == "serve.queued":
            assert s.parent == -1 and s.uid is not None


def test_scheduler_reports_each_blocking_branch():
    tracer = Tracer()
    # 2 slots, 3 adapters: the third request waits for a slot
    s = Scheduler(PagedKVCache(1024, block_size=16), AdapterSlotCache(2),
                  8, tracer=tracer)
    reqs = [_req(i, adapter=i) for i in range(3)]
    for r in reqs:
        tracer.begin("serve.queued", r.uid)
    s.add(reqs)
    plan = s.schedule(0.0)
    assert [r.uid for r in plan.admitted] == [0, 1]
    assert tracer.counters["slot_skips"] == 1
    assert [(n, u) for n, u, _ in tracer.notes] == [("slot_skip", 2)]
    assert [sp.uid for sp in tracer.closed("serve.queued")] == [0, 1]

    # a KV stop: the head request does not fit
    tracer = Tracer()
    s = Scheduler(PagedKVCache(32, block_size=16), AdapterSlotCache(4), 8,
                  tracer=tracer)
    s.add([_req(0, p=40)])
    assert not s.schedule(0.0).admitted
    assert tracer.counters["kv_stops"] == 1
    assert [n for n, _, _ in tracer.notes] == ["kv_stop"]

    # a full batch, at the guard before the scan and inside it
    tracer = Tracer()
    s = Scheduler(PagedKVCache(1024, block_size=16), AdapterSlotCache(4),
                  1, tracer=tracer)
    s.add([_req(0), _req(1)])
    s.schedule(0.0)
    s.schedule(1.0)
    assert tracer.counters["rows_full"] == 2
    assert tracer.counters["slot_skips"] == tracer.counters["kv_stops"] == 0


def test_decode_step_names_its_scopes_in_the_compiled_program():
    cfg = get_reduced("phi4-mini-3.8b")
    model = Model(cfg, ShardingPlan(mode="decode"))
    key = jax.random.PRNGKey(0)
    args = (model.init(key), model.init_lora(key, 3, 8),
            model.init_cache(4, 32), jnp.zeros((4, 1), jnp.int32),
            jnp.zeros((4,), jnp.int32))
    text = jax.jit(model.decode_step).lower(*args).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("embed", "attn_proj", "lora", "attention", "kv_update",
                  "mlp", "head"):
        assert any(scope in p.split("/") for p in paths), scope
    # the LoRA delta sits inside the q/v projections
    assert any("attn_proj/lora/" in p for p in paths)
