"""The reduction from a profiler trace (``.xplane.pb``) to device numbers.

    python3 bench/trace_reduce.py <trace dir or .xplane.pb>   # prints them

From the TPU planes (``/device:TPU:<n>``), averaged over the chips that ran
anything:

- ``busy_s``: the union of the intervals in which an operation ran
  (line ``XLA Ops``), within the traced window;
- ``window_s``: the measured window, the driver's ``bench.window`` span
  on the host plane (in a trace without one, from the first to the last
  ``bench.*`` span);
- ``decode_n``/``decode_s``: runs and summed time of the decode-step
  program (line ``XLA Modules``, a name containing ``decode_step``);
- ``bgmv_n``/``bgmv_s``: calls and summed time of the ``bgmv`` kernel
  (ops whose HLO name contains ``bgmv``; not the ops that read its
  output);
- ``breakdown``: the ten operations that took most time, by their HLO
  name (leaf operations only: a ``while`` that holds a layer loop is not
  counted beside the operations inside it), and the ten longest idle
  gaps, each named by the innermost ``bench.*`` host span around its
  middle ("outside" where none is).
"""
from __future__ import annotations

import bisect
import collections
import sys
from pathlib import Path

DECODE = "decode_step"
BGMV = "bgmv"


def find(path) -> Path:
    p = Path(path)
    if p.is_file():
        return p
    found = sorted(p.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {p}")
    return found[-1]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b, *_ in intervals
            if b > lo and a < hi]


def leaves(events):
    """The events that hold no other event (on one line, ops nest)."""
    out = []
    ev = sorted(events, key=lambda e: (e[0], -e[1]))
    for i, (a, b, name) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is None or nxt[0] >= b:
            out.append((a, b, name))
    return out


def short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def host_spans(pd):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [e for e in _events(line) if e[2].startswith("bench.")]
    return sorted(spans)


def name_gap(spans, starts, t) -> str:
    """Innermost (latest-starting) bench span that covers time t."""
    i = bisect.bisect_right(starts, t)
    best = None
    for a, b, name in reversed(spans[max(0, i - 64):i]):
        if a <= t < b and (best is None or a > best[0]):
            best = (a, name)
    return best[1] if best else "outside"


def reduce(path) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(find(path)))
    spans = host_spans(pd)
    windows = [sp for sp in spans if sp[2] == "bench.window"]
    if windows:
        lo, hi = windows[0][0], windows[0][1]
        spans = [sp for sp in spans if sp[2] != "bench.window"]
    elif spans:
        lo, hi = spans[0][0], max(b for _, b, _ in spans)
    else:
        raise ValueError("the trace holds no bench.* host span")
    starts = [a for a, _, _ in spans]
    chips = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        ops = clip(lines.get("XLA Ops", []), lo, hi)
        if not ops:
            continue
        mods = [e for e in lines.get("XLA Modules", [])
                if DECODE in e[2] and lo <= e[0] < hi]
        kern = [e for e in lines.get("XLA Ops", [])
                if BGMV in short(e[2]) and lo <= e[0] < hi]
        busy = union(ops)
        op_time = collections.Counter()
        for a, b, name in leaves(lines["XLA Ops"]):
            if b > lo and a < hi:
                op_time[short(name)] += (min(b, hi) - max(a, lo)) / 1e9
        gaps = [(a2 - b1, b1, a2)
                for (_, b1), (a2, _) in zip(busy, busy[1:])]
        gaps += [(busy[0][0] - lo, lo, busy[0][0]),
                 (hi - busy[-1][1], busy[-1][1], hi)]
        chips.append({
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "decode_n": len(mods),
            "decode_s": sum(b - a for a, b, _ in mods) / 1e9,
            "bgmv_n": len(kern),
            "bgmv_s": sum(b - a for a, b, _ in kern) / 1e9,
            "ops": op_time, "gaps": gaps})
    window_s = (hi - lo) / 1e9
    if not chips:
        return {"busy_s": None, "window_s": window_s, "decode_n": 0,
                "decode_s": 0.0, "bgmv_n": 0, "bgmv_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    n = len(chips)
    out = {k: sum(c[k] for c in chips) / n
           for k in ("busy_s", "decode_s", "bgmv_s")}
    out.update(decode_n=chips[0]["decode_n"], bgmv_n=chips[0]["bgmv_n"],
               window_s=window_s)
    top = chips[0]["ops"].most_common(10)
    gaps = sorted(chips[0]["gaps"], reverse=True)[:10]
    out["breakdown"] = {
        "device_ops": [[name, s] for name, s in top],
        "idle_gaps": [[name_gap(spans, starts, (a + b) / 2), g / 1e9]
                      for g, a, b in gaps]}
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(reduce(sys.argv[1]), indent=1, default=str))
