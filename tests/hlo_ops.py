"""Reads a compiled program's text for the arrays its operations write.

``written(text, dims)`` lists the operations outside fusion bodies (each
of which writes its output to memory) whose output holds the given
dimensions in any order, and any of size 1: a transposed copy of a cache,
or one layer of it kept with its layer axis, counts too.  An
operation inside a fusion body writes nothing of its own, so a slice that
the compiler fuses into the dot that reads it is not listed.
"""
import re

_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INST = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]\S*\s+"
                   r"([\w\-]+)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")

# operations that write a cache-sized output without moving the cache: the
# loop's own plumbing, and the in-place update of one row
IN_PLACE = {"parameter", "get-tuple-element", "bitcast",
            "dynamic-update-slice"}


def _computations(text):
    comps, name = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.strip() == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def written(text, dims):
    """(instruction, opcode) of each operation outside fusion bodies whose
    output has `dims`, in any order and with any axes of size 1, other than
    those in ``IN_PLACE`` and fusions of them."""
    comps = _computations(text)
    fused = {c for lines in comps.values() for line in lines
             if " fusion(" in line for c in _CALLS.findall(line)}

    def key(shape):
        return sorted(d for d in shape if d != 1)

    def sized(line):
        m = _INST.match(line)
        if m and key(int(d) for d in m.group(2).split(",") if d) == want:
            return m.group(1), m.group(3)
        return None

    want = key(dims)
    out = []
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            inst = sized(line)
            if inst is None or inst[1] in IN_PLACE:
                continue
            if inst[1] == "fusion":
                called = _CALLS.findall(line)
                inner = [sized(x) for c in called for x in comps.get(c, ())]
                if all(i is None or i[1] in IN_PLACE for i in inner):
                    continue
            out.append(inst)
    return out
