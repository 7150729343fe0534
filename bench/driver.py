"""The open-loop driver on the wall clock, and what it records.

It submits each planned request when it is due, stamped with the engine's
current (virtual) clock so that the engine admits it at its next step, and
advances the engine one step at a time through ``run_until``.  Tokens are
timestamped on the wall clock in the engine's ``on_token`` hook.  Around
the calls into each layer it keeps counts and host-clock spans, and with
``annotate`` it also writes those spans into the profiler's trace
(``jax.profiler.TraceAnnotation``), so that device idle gaps can be named
by what the host was doing.

Wrappers sit on the instance's ``executor.step`` and ``executor.decode``
(the jitted decode step) and on ``engine.scheduler.schedule``.  They call
through unchanged, add no device work and no synchronisation, and record:
the tokens and adapter ids the program fed each decode step (which the
reference replays), and the logits of a seeded sample of window steps
(which the check compares).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np


class RunFailed(RuntimeError):
    """The run cannot give numbers (not a wrong output: see check.py)."""


@dataclasses.dataclass
class StepRecord:
    t0: float                 # engine step start (wall)
    pos: int = -1             # the decode step's cache position
    t1: float = 0.0           # engine step end (wall)
    call0: float = 0.0        # decode call start
    ex1: float = 0.0          # executor.step returned
    running: int = 0
    admitted: int = 0
    context: int = 0          # positions held by running requests
    waiting: int = 0


class LogitSampler:
    """Keeps the logits of ``k`` window steps drawn uniformly from the
    seed (reservoir sampling), plus the last step's; the arrays stay on
    the device until the window has closed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(seed)
        self.kept: Dict[int, object] = {}
        self.seen = 0
        self.last = None

    def offer(self, step: int, logits) -> None:
        self.last = (step, logits)
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept[step] = logits
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            drop = sorted(self.kept)[j]
            del self.kept[drop]
            self.kept[step] = logits

    def samples(self) -> Dict[int, object]:
        out = dict(self.kept)
        if self.last is not None:
            out[self.last[0]] = self.last[1]
        return out


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Counts JAX compile and trace events (as ``chip_smoke.py`` does),
    so that set-up can be split and a compile inside the window caught.

    JAX reports a backend compile for every program it makes ready, from
    the persistent cache or not; a cache hit is reported inside it, so a
    backend compile with no hit before it is a miss."""

    def __init__(self, jax):
        self.events: List[tuple] = []
        self.misses: List[str] = []
        self.cache_hits = 0
        self._hit = False

        def on_duration(event, duration, **kw):
            if event.startswith("/jax/core/compile/"):
                name = kw.get("fun_name", "?")
                self.events.append((time.perf_counter(), event, duration,
                                    name))
                if event == BACKEND_COMPILE:
                    if not self._hit:
                        self.misses.append(name)
                    self._hit = False

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
                self._hit = True

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] < t1]

    def compile_s(self) -> float:
        """Seconds of backend compiles, loads from the cache included."""
        return sum(e[2] for e in self.events if e[1] == BACKEND_COMPILE)


class Driver:
    def __init__(self, jax, engine, executor, planned, *, cache_len: int,
                 fill_s: float, window_s: float, sample_steps: int,
                 seed: int, annotate: bool = False):
        self.jax = jax
        self.engine, self.ex = engine, executor
        self.planned = sorted(planned, key=lambda p: p.due)
        self.cache_len = cache_len
        self.fill_s, self.window_s = fill_s, window_s
        self.annotate = annotate
        self.sampler = LogitSampler(sample_steps, seed)
        self.fed_tokens: List[object] = []
        self.fed_idx: List[object] = []
        self.steps: List[StepRecord] = []
        self.first: Dict[int, float] = {}
        self.times: Dict[int, List[float]] = collections.defaultdict(list)
        self.due: Dict[int, float] = {}
        self.lateness: List[float] = []
        self.t_start = self.win0 = self.win1 = 0.0
        self.window_step0: Optional[int] = None
        self.loads0 = self.loads1 = 0
        self._cur: Optional[StepRecord] = None
        self._install()

    # -- spans ------------------------------------------------------------
    def span(self, name: str):
        if self.annotate:
            return self.jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    # -- wrappers ---------------------------------------------------------
    def _install(self) -> None:
        ex, eng = self.ex, self.engine
        decode, step = ex.decode, ex.step
        schedule = eng.scheduler.schedule

        def wrapped_decode(params, lora, cache, tokens, idx):
            n = len(self.fed_idx)
            if n >= self.cache_len:
                raise RunFailed(
                    f"decode step {n} would write past the KV cache's "
                    f"{self.cache_len} positions")
            self.fed_tokens.append(tokens)
            self.fed_idx.append(idx)
            if self._cur is not None:
                self._cur.call0 = time.perf_counter()
                self._cur.pos = n
            with self.span("bench.decode_call"):
                out = decode(params, lora, cache, tokens, idx)
            if self.window_step0 is not None:
                self.sampler.offer(n, out[0])
            return out

        def wrapped_step(plan, n_waiting):
            rec = self._cur
            if rec is not None:
                rec.running = len(plan.running)
                rec.admitted = len(plan.admitted)
                rec.context = sum(r.context_len for r in plan.running)
                rec.waiting = n_waiting
            with self.span("bench.executor_step"):
                out = step(plan, n_waiting)
            if rec is not None:
                rec.ex1 = time.perf_counter()
            return out

        def wrapped_schedule(now):
            with self.span("bench.schedule"):
                return schedule(now)

        ex.decode = wrapped_decode
        ex.step = wrapped_step
        eng.scheduler.schedule = wrapped_schedule
        eng.on_token = self._on_token

    def _on_token(self, req, _t_virtual) -> None:
        t = time.perf_counter()
        uid = req.uid
        if uid not in self.first:
            self.first[uid] = t
        self.times[uid].append(t)

    # -- the loop ---------------------------------------------------------
    def run(self) -> None:
        """Drive the fill, then the window.  With ``annotate``, the window
        is the ``bench.window`` span of the trace."""
        from repro.serving.request import Request
        eng = self.engine
        pending = collections.deque(self.planned)
        self.t_start = t0 = time.perf_counter()
        self.win0 = t0 + self.fill_s
        self.win1 = self.win0 + self.window_s
        window = None
        uid = 0
        while True:
            now = time.perf_counter()
            if window is None and now >= self.win0:
                window = self.span("bench.window")
                window.__enter__()
                self.window_step0 = len(self.fed_idx)
                self.loads0 = eng.adapters.load_count
            if now >= self.win1:
                break
            batch = []
            with self.span("bench.submit"):
                while pending and t0 + pending[0].due <= now:
                    p = pending.popleft()
                    self.due[uid] = t0 + p.due
                    self.lateness.append(now - (t0 + p.due))
                    batch.append(Request(
                        uid=uid, adapter=p.adapter, arrival=eng.clock,
                        prompt_len=p.prompt_len, output_len=p.output_len))
                    uid += 1
                eng.submit(batch)
            if eng.scheduler.has_work or batch:
                rec = StepRecord(t0=time.perf_counter())
                self._cur = rec
                with self.span("bench.engine_step"):
                    eng.run_until(eng.clock + 1e-9, strict=True)
                rec.t1 = time.perf_counter()
                self._cur = None
                if rec.call0:
                    self.steps.append(rec)
                continue
            nxt = t0 + pending[0].due if pending else self.win1
            wake = min(nxt, self.win1 if window else self.win0)
            with self.span("bench.idle"):
                time.sleep(max(0.0, wake - time.perf_counter()))
        self.loads1 = eng.adapters.load_count
        self.jax.block_until_ready(self.ex.cache)
        window.__exit__(None, None, None)

    # -- what the window measured -----------------------------------------
    def window_requests(self) -> List[int]:
        """The requests due in the window (the warm start's are due at the
        traffic's start, before it)."""
        return [u for u, t in self.due.items() if self.win0 <= t < self.win1]

    def ttfts(self, uids) -> List[float]:
        """Seconds from due to first token; a request with no token by the
        window's end counts with its wait so far."""
        return [min(self.first.get(u, self.win1), self.win1) - self.due[u]
                for u in uids]

    def gaps(self) -> List[float]:
        out = []
        for ts in self.times.values():
            for a, b in zip(ts, ts[1:]):
                if self.win0 <= b < self.win1:
                    out.append(b - a)
        return out

    def window_tokens(self) -> int:
        return sum(1 for ts in self.times.values() for t in ts
                   if self.win0 <= t < self.win1)

    def window_steps(self) -> List[StepRecord]:
        return [s for s in self.steps if self.win0 <= s.t0 < self.win1]
