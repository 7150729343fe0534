"""Readings of the program's own tracer (``repro.tracing``), and device idle
time named by the program's ``serve.*`` spans in a profiler trace.

The tracer's times and the driver's window are both ``time.perf_counter``
seconds; the trace's are its own nanoseconds.
"""
from __future__ import annotations

import bisect
import collections
import math
import statistics
from typing import Dict, List, Optional

import trace_reduce

QUEUED, SCHEDULE = "serve.queued", "serve.schedule"


def queued(tracer, lo: float, hi: float) -> list:
    """The ``serve.queued`` spans that ended (the request was admitted) in
    [lo, hi)."""
    return [s for s in tracer.closed(QUEUED) if lo <= s.end < hi]


def in_window(tracer, name: str, lo: float, hi: float) -> list:
    return [s for s in tracer.closed(name) if lo <= s.start < hi]


def wait_split(tracer, lo: float, hi: float) -> Dict[str, float]:
    """The summed queue wait of the requests admitted in [lo, hi), split by
    what held each request between one schedule call and the next:
    ``slot`` (the call skipped it for want of an adapter slot), ``rows``
    (the batch was full), ``kv`` (the KV cache stopped the scan), ``other``
    (none of these at that call), and ``next_step`` (from its submission to
    the first call after it)."""
    calls = sorted(s.start for s in tracer.closed(SCHEDULE))
    slot = set()
    blocked = collections.defaultdict(set)
    for name, uid, t in tracer.notes:
        k = bisect.bisect_right(calls, t) - 1
        if name == "slot_skip":
            slot.add((k, uid))
        else:
            blocked[k].add(name)
    split = dict.fromkeys(("slot", "rows", "kv", "other", "next_step"), 0.0)
    for s in queued(tracer, lo, hi):
        k = bisect.bisect_right(calls, s.start)
        split["next_step"] += min(calls[k] if k < len(calls) else s.end,
                                  s.end) - s.start
        while k < len(calls) and calls[k] < s.end:
            nxt = min(calls[k + 1] if k + 1 < len(calls) else s.end, s.end)
            if (k, s.uid) in slot:
                why = "slot"
            elif "rows_full" in blocked[k]:
                why = "rows"
            elif "kv_stop" in blocked[k]:
                why = "kv"
            else:
                why = "other"
            split[why] += nxt - calls[k]
            k += 1
    return split


def span_table(tracer, lo: float, hi: float) -> Dict[str, dict]:
    """For each step-level span name, over the spans that began in
    [lo, hi): their count and mean duration and self time (ms)."""
    rows = collections.defaultdict(list)
    for s, own in zip(tracer.spans, tracer.self_times()):
        if s.uid is None and lo <= s.start < hi and not math.isnan(s.end):
            rows[s.name].append((s.end - s.start, own))
    return {name: {"n": len(v),
                   "ms": 1e3 * statistics.fmean(d for d, _ in v),
                   "self_ms": 1e3 * statistics.fmean(o for _, o in v)}
            for name, v in rows.items()}


def host_ms_per_step(tracer, lo: float, hi: float) -> Optional[float]:
    """Host milliseconds of each executed engine step that began in
    [lo, hi) outside the decode call and its wait: ``serve.step``'s self
    time plus its ``serve.schedule``, ``serve.tokens`` and
    ``serve.prepare``, averaged over the steps."""
    spans, selfs = tracer.spans, tracer.self_times()
    host = {i: selfs[i] for i, s in enumerate(spans)
            if s.name == "serve.step" and lo <= s.start < hi
            and not math.isnan(s.end)}
    executed = set()
    for s in spans:
        step = s.parent
        if s.name == "serve.prepare" and step >= 0:
            step = spans[step].parent          # serve.execute's parent
        if step not in host:
            continue
        if s.name == "serve.execute":
            executed.add(step)
        elif s.name in ("serve.schedule", "serve.tokens", "serve.prepare"):
            host[step] += s.end - s.start
    steps = [host[i] for i in executed]
    return 1e3 * statistics.fmean(steps) if steps else None


# -- device idle time by span ------------------------------------------------
def innermost(spans) -> List[tuple]:
    """Properly nested (start, end, name) spans -> disjoint (start, end,
    name) segments, each named by the innermost span over it."""
    segs, stack = [], []
    t = None

    def close_until(x):
        nonlocal t
        while stack and stack[-1][1] <= x:
            _, b, name = stack.pop()
            if b > t:
                segs.append((t, b, name))
            t = b

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(a)
        if stack and a > t:
            segs.append((t, a, stack[-1][2]))
        stack.append((a, b, name))
        t = a
    close_until(float("inf"))
    return segs


def intersect(xs, ys) -> List[tuple]:
    """Pieces of the disjoint sorted intervals ``xs`` that lie in the
    disjoint sorted (start, end, name) segments ``ys``, each with its
    segment's name."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b, ys[j][2]))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(pd) -> Optional[dict]:
    """Device idle seconds in the ``bench.window`` span (as
    ``trace_reduce.reduce`` finds the window), named by the
    innermost ``serve.*`` host span over each piece ("none" where there is
    none), over the whole window and within ``bench.engine_step`` spans,
    and the five longest idle gaps with their pieces; None where no chip
    ran anything."""
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += trace_reduce._events(line)
    bench = [e for e in host if e[2].startswith("bench.")]
    windows = [e for e in bench if e[2] == "bench.window"]
    if windows:
        lo, hi = windows[0][0], windows[0][1]
    elif bench:
        # as trace_reduce.reduce: from the first to the last bench span
        lo, hi = min(bench)[0], max(b for _, b, _ in bench)
    else:
        return None
    busy = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = trace_reduce.clip(trace_reduce._events(line),
                                            lo, hi)
                    if ops:
                        busy = trace_reduce.union(ops)
            if busy:
                break
    if not busy:
        return None
    idle = _gaps(busy, lo, hi)
    serve = [e for e in host if e[2].startswith("serve.")]
    segs = innermost(serve)
    cover = [(a, b, "none") for a, b in
             _gaps([(a, b) for a, b, _ in segs], lo, hi)]
    named = sorted(segs + cover)
    steps = trace_reduce.union([(a, b) for a, b, n in host
                                if n == "bench.engine_step"])
    in_steps = [(a, b) for a, b, _ in
                intersect(idle, [(a, b, "") for a, b in steps])]

    def total(pieces):
        out = collections.Counter()
        for a, b, name in pieces:
            out[name] += (b - a) / 1e9
        return dict(out.most_common())

    pieces = intersect(idle, named)
    starts = [a for a, _ in idle]
    longest = collections.defaultdict(collections.Counter)
    for a, b, name in pieces:
        longest[bisect.bisect_right(starts, a) - 1][name] += (b - a) / 1e9
    top = sorted(range(len(idle)), key=lambda i: idle[i][0] - idle[i][1])
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": sum(b - a for a, b in idle) / 1e9,
            "by_span": total(pieces),
            "in_engine_step": total(intersect(in_steps, named)),
            "longest": [((idle[i][1] - idle[i][0]) / 1e9,
                         dict(longest[i].most_common()))
                        for i in top[:5]]}


def _gaps(intervals, lo, hi) -> List[tuple]:
    """[lo, hi) less the disjoint sorted ``intervals``."""
    out, t = [], lo
    for a, b in intervals:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]
