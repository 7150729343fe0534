"""In-memory spans and counters for the serving path.

A ``Tracer`` records, on the host's wall clock:

- step-level spans (``span``): the engine step and its parts, nested on
  the one thread that runs the engine.  Each has a name, start, end and
  parent; with ``annotate=True`` each is also a
  ``jax.profiler.TraceAnnotation``, so that a profiler trace shows it on
  its host plane, on the device trace's clock;
- per-request spans (``begin``/``end``, keyed by request uid): a
  request's time in the waiting queue.  They overlap each other, so they
  stay in memory only;
- notes (``note``): instants, such as a schedule call that skipped a
  request for want of an adapter slot;
- counters (``count``): a name and an integer.

``NULL_TRACER``, the default everywhere, records nothing and never reads
a clock.

The serving layers read the wall clock through this module only.
Nothing a tracer records feeds back into the virtual clock or any
scheduling decision: the engine, scheduler and executor only write to it.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(slots=True)
class Span:
    name: str
    start: float
    end: float = math.nan
    parent: int = -1              # index in ``Tracer.spans``; -1 for none
    uid: Optional[int] = None     # the request's, for per-request spans


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Records nothing: ``span`` returns one shared no-op context."""

    def span(self, name: str) -> _NullSpan:
        return _NULL_SPAN

    def count(self, name: str, n: int = 1) -> None:
        pass

    def begin(self, name: str, uid: int) -> None:
        pass

    def end(self, name: str, uid: int) -> None:
        pass

    def note(self, name: str, uid: Optional[int] = None) -> None:
        pass


NULL_TRACER = NullTracer()


class _Open:
    """The context of one step-level span."""

    __slots__ = ("tracer", "index", "annotation")

    def __init__(self, tracer: "Tracer", index: int, annotation):
        self.tracer, self.index, self.annotation = tracer, index, annotation

    def __enter__(self) -> Span:
        return self.tracer.spans[self.index]

    def __exit__(self, *exc):
        tr = self.tracer
        tr.spans[self.index].end = time.perf_counter()
        tr._stack.pop()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False


class Tracer:
    """Records spans, notes and counters in memory (see the module's
    docstring).  ``spans`` holds every span in the order it began."""

    def __init__(self, annotate: bool = False):
        self.spans: List[Span] = []
        self.notes: List[Tuple[str, Optional[int], float]] = []
        self.counters: Dict[str, int] = collections.Counter()
        self._stack: List[int] = []
        self._open: Dict[Tuple[str, int], int] = {}
        self._annotation = None
        if annotate:
            import jax
            self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str) -> _Open:
        """A step-level span, entered with ``with``; its parent is the
        innermost step-level span open when it begins."""
        ann = None
        if self._annotation is not None:
            ann = self._annotation(name)
            ann.__enter__()
        parent = self._stack[-1] if self._stack else -1
        index = len(self.spans)
        self.spans.append(Span(name, time.perf_counter(), parent=parent))
        self._stack.append(index)
        return _Open(self, index, ann)

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] += n

    def begin(self, name: str, uid: int) -> None:
        """Opens request ``uid``'s span ``name`` (replacing one left
        open)."""
        self._open[(name, uid)] = len(self.spans)
        self.spans.append(Span(name, time.perf_counter(), uid=uid))

    def end(self, name: str, uid: int) -> None:
        """Closes request ``uid``'s open span ``name``, if it has one."""
        index = self._open.pop((name, uid), None)
        if index is not None:
            self.spans[index].end = time.perf_counter()

    def note(self, name: str, uid: Optional[int] = None) -> None:
        self.notes.append((name, uid, time.perf_counter()))

    # -- reading -------------------------------------------------------
    def closed(self, name: str) -> List[Span]:
        return [s for s in self.spans
                if s.name == name and not math.isnan(s.end)]

    def self_times(self) -> List[float]:
        """Each span's duration less its children's, by index in
        ``spans`` (children of one span run one after another, so their
        durations are the part of its interval they cover)."""
        out = [s.end - s.start for s in self.spans]
        for s in self.spans:
            if s.parent >= 0:
                out[s.parent] -= s.end - s.start
        return out
