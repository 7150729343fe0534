"""Which model scope each operation of the compiled decode step belongs to,
and the decode step's device time by scope.

The model marks its parts with ``jax.named_scope`` (``SCOPES``); the
compiler keeps the scope path in each instruction's
``metadata={op_name="..."}``.  ``op_scopes`` reads the compiled program's
text (``jit(...).lower(...).compile().as_text()``) and maps each
instruction name, as the profiler's trace shows it (``%fusion.12``), to
the innermost of those scopes in its path, or to None.  A fusion whose own
instruction carries no ``op_name`` takes the scope of the computation it
calls: its root's, else the commonest among its instructions.

``check`` makes sure the map is the traced program's: every traced
operation name is in it, and, where the compiled module exposes a
fingerprint that the trace's module name (``jit_decode_step(<id>)``)
carries, the two match.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, Optional

import trace_reduce

SCOPES = ("embed", "attn_proj", "lora", "attention", "kv_update", "mlp",
          "head")

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_MODULE_ID = re.compile(r"\((\d+)\)$")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def op_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> scope (or None), for every instruction of every
    computation in the compiled program's text."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    members = collections.defaultdict(list)     # computation -> scopes
    roots: Dict[str, Optional[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else None
        own[name] = scope
        members[comp].append(scope)
        if m.group(1):
            roots[comp] = scope
        target = _CALLS.search(line)
        if target is not None:
            calls[name] = target.group(1)
    out = {}
    for name, scope in own.items():
        if scope is None and name in calls:
            callee = calls[name]
            scope = roots.get(callee)
            if scope is None:
                found = collections.Counter(
                    s for s in members[callee] if s is not None)
                scope = found.most_common(1)[0][0] if found else None
        out[name] = scope
    return out


def fingerprint(compiled):
    """The compiled module's fingerprint (text or bytes), or None where it
    exposes none."""
    return getattr(compiled.runtime_executable(), "fingerprint", None)


def _as_ids(fp) -> set:
    """The decimal forms a trace's module id could give ``fp``: its text,
    or the integers its first eight and all its bytes read as."""
    if isinstance(fp, str):
        return {fp}
    out = set()
    try:
        out.add(fp.decode("ascii"))
    except UnicodeDecodeError:
        pass
    for raw in (fp[:8], fp):
        for order in ("little", "big"):
            for signed in (False, True):
                out.add(str(int.from_bytes(raw, order, signed=signed)))
    return out


def check(op_map: dict, traced_ops: Iterable[str],
          module_names: Iterable[str], fp) -> str:
    """How the map was matched to the trace: "names" (every traced
    operation is in it), and "fingerprint" too where the compiled module's
    fingerprint is the trace's module id.  Raises ValueError where a
    traced operation is missing."""
    ids = {m.group(1) for n in module_names
           if (m := _MODULE_ID.search(n)) is not None}
    missing = sorted(set(traced_ops) - set(op_map))
    shown = fp.hex() if isinstance(fp, bytes) else fp
    if missing:
        raise ValueError(
            f"{len(missing)} traced operations are not in the compiled "
            f"decode step (e.g. {missing[:3]}); module ids {sorted(ids)}, "
            f"compiled fingerprint {shown}")
    if fp is not None and _as_ids(fp) & ids:
        return "fingerprint and names"
    return f"names (module ids {sorted(ids)}, fingerprint {shown})"


def scope_times(pd, op_map: dict, lo: int, hi: int) -> Optional[dict]:
    """Device seconds of the decode-step program's leaf operations by scope
    ("unscoped" for None, with its eight longest operations), over its runs
    that start in [lo, hi) on the first chip that ran it; None where no
    chip did."""
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        lines = {line.name: trace_reduce._events(line)
                 for line in plane.lines}
        mods = sorted(e for e in lines.get("XLA Modules", [])
                      if trace_reduce.DECODE in e[2] and lo <= e[0] < hi)
        if not mods:
            continue
        starts = [a for a, _, _ in mods]
        by_scope = collections.Counter()
        unscoped = collections.Counter()
        names = set()
        ops_s = 0.0
        for a, b, name in trace_reduce.leaves(lines.get("XLA Ops", [])):
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= mods[i][1]:
                continue
            op = trace_reduce.short(name)
            names.add(op)
            scope = op_map.get(op)
            by_scope[scope or "unscoped"] += (b - a) / 1e9
            if scope is None:
                unscoped[op] += (b - a) / 1e9
            ops_s += (b - a) / 1e9
        return {"decode_n": len(mods),
                "decode_s": sum(b - a for a, b, _ in mods) / 1e9,
                "ops_s": ops_s, "by_scope": dict(by_scope),
                "unscoped_top": unscoped.most_common(8),
                "op_names": names,
                "modules": sorted({n for _, _, n in mods})}
    return None
