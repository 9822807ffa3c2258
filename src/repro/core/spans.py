"""One span tree per verdict: the verifier's phases on the host clock and
on the profiler's.

``verdict()`` opens the root span of one verdict (a ``Session.verify`` call,
or a bare ``verify_graphs``); inside it ``span(name)`` opens a child of the
innermost open span.  Spans record their name, parent and
``time.perf_counter`` start and end, and stay in memory for the verdict.
Outside a verdict, and on any thread but the verdict's own, ``span`` does
nothing.

Each span is also entered as ``jax.profiler.TraceAnnotation(name,
verdict=<id>)``, so under any profiler session the verifier's phases lie on
the host plane of the same trace as the device ops.  While the root is
open, a ``gc.callbacks`` hook records each garbage collection as a ``gc``
span under the innermost open span (annotated likewise): span durations
stay wall time, and a pause is "of which" time of the span it hit.

``Recorder.timings(mark)`` aggregates the spans opened since ``mark`` by
name -- calls, inclusive seconds, and self seconds (inclusive minus the
part covered by child spans) -- into a :class:`PhaseTimings`.
"""
from __future__ import annotations

import gc
import itertools
import os
import threading
import time
from contextlib import contextmanager
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Mark", "Recorder", "span", "verdict"]

_ids = itertools.count(1)  # verdict ids, counting up per process
_tls = threading.local()  # the verdict open on this thread, if any


class Span:
    __slots__ = ("name", "parent", "t0", "t1", "generation")

    def __init__(self, name: str, parent: Optional["Span"], generation: int = -1):
        self.name = name
        self.parent = parent
        self.generation = generation  # a gc span's generation, else -1
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None

    @property
    def seconds(self) -> float:
        return (self.t1 if self.t1 is not None else time.perf_counter()) - self.t0


class Mark(NamedTuple):
    """A point in a verdict: the spans opened after it form a window."""

    index: int
    t: float


class Recorder:
    """The span tree of one verdict."""

    def __init__(self) -> None:
        self.id = next(_ids)
        self.spans: list[Span] = []  # in the order they opened
        self.stack: list[Span] = []  # open spans, innermost last
        self.root: Optional[Span] = None
        self._gc: Optional[tuple[Span, TraceAnnotation]] = None

    def open(self, name: str) -> tuple[Span, TraceAnnotation]:
        ann = TraceAnnotation(name, verdict=self.id)
        ann.__enter__()
        s = Span(name, self.stack[-1] if self.stack else None)
        self.spans.append(s)
        self.stack.append(s)
        return s, ann

    def close(self, s: Span, ann: TraceAnnotation) -> None:
        s.t1 = time.perf_counter()
        self.stack.pop()
        ann.__exit__(None, None, None)

    def on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: each collection becomes a ``gc`` span."""
        if phase == "start":
            gen = info["generation"]
            ann = TraceAnnotation("gc", verdict=self.id, generation=gen)
            ann.__enter__()
            s = Span("gc", self.stack[-1] if self.stack else None, gen)
            self.spans.append(s)
            self._gc = (s, ann)
        elif self._gc is not None:
            s, ann = self._gc
            self._gc = None
            s.t1 = time.perf_counter()
            ann.__exit__(None, None, None)

    def mark(self) -> Mark:
        return Mark(len(self.spans), time.perf_counter())

    def elapsed(self, mark: Mark) -> float:
        return time.perf_counter() - mark.t

    def timings(self, mark: Mark):
        """The spans closed since ``mark``, aggregated by name, as a
        :class:`~repro.core.report.PhaseTimings`; the four phases are read
        from the spans of their names."""
        from .report import PHASES, PhaseTimings  # report imports partition

        window = self.spans[mark.index:]
        covered: dict[int, float] = {}
        for s in window:
            if s.parent is not None:
                covered[id(s.parent)] = covered.get(id(s.parent), 0.0) + s.seconds
        agg: dict[str, dict] = {}
        gens = [0, 0, 0]
        for s in window:
            a = agg.setdefault(s.name, {"calls": 0, "s": 0.0, "self_s": 0.0})
            a["calls"] += 1
            a["s"] += s.seconds
            a["self_s"] += s.seconds - covered.get(id(s), 0.0)
            if s.generation >= 0:
                gens[s.generation] += 1
        phases = {f"{p}_s": agg[p]["s"] for p in PHASES if p in agg}
        return PhaseTimings(**phases, spans=agg,
                            gc_s=agg["gc"]["s"] if "gc" in agg else 0.0,
                            gc_collections=gens)


@contextmanager
def verdict():
    """Open the root span ``verdict``, or join the verdict already open on
    this thread.  Yields the :class:`Recorder`."""
    rec = getattr(_tls, "rec", None)
    if rec is not None:
        yield rec
        return
    rec = Recorder()
    _tls.rec = rec
    gc.callbacks.append(rec.on_gc)
    try:
        rec.root, ann = rec.open("verdict")
        try:
            yield rec
        finally:
            rec.close(rec.root, ann)
    finally:
        gc.callbacks.remove(rec.on_gc)
        _tls.rec = None


def _forget_in_child() -> None:
    """A process forked inside a verdict (the rules engine's worker pool)
    records nothing: its copy of the verdict would only grow."""
    rec = getattr(_tls, "rec", None)
    if rec is not None:
        gc.callbacks.remove(rec.on_gc)
        _tls.rec = None


os.register_at_fork(after_in_child=_forget_in_child)


@contextmanager
def span(name: str):
    """A child of the innermost open span of this thread's verdict."""
    rec = getattr(_tls, "rec", None)
    if rec is None:
        yield
        return
    s, ann = rec.open(name)
    try:
        yield
    finally:
        rec.close(s, ann)
