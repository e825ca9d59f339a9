"""Process-wide wall-clock registry: the program's spans, and compile vs
execute per entry point.

``span(name)`` is the one timing primitive. It times a block on
``time.monotonic()``, keeps a :class:`Span` record (name, start, end, and
the innermost span still open on the same thread as its parent, so a
span's self time is its duration less its children's), appends the
duration to the per-name list that :func:`entries` returns, and, while a
profiler records, opens ``jax.profiler.TraceAnnotation(name)`` so the span
also lands in the trace on the device trace's clock. The module imports
nothing of JAX itself: no profiler can run before JAX is loaded, so a span
opened earlier has nothing to annotate.

jax entry points pay tracing+lowering+compilation on their first call and
run from cache afterwards, so the registry models every named call site as
``cold`` (first call: compile + execute) vs ``warm`` (subsequent calls:
execute only) and reports ``compile_s ~= cold - mean(warm)`` — an
approximation that is exact up to run-to-run execute variance, which is
all a text dashboard needs. ``benchmarks.common.timed`` feeds this
registry automatically; ``repro.obs.export`` snapshots it into the trace
artifact's ``wallclock`` section.
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import NamedTuple, Optional

# span records kept in memory; a long-running server drops the oldest
SPAN_CAP = 1 << 18

_CALLS: dict = {}      # name -> [seconds, ...] in call order
# (name, start, end, parent, id) tuples, made into Span records on read
_SPANS: collections.deque = collections.deque(maxlen=SPAN_CAP)
_OPEN = threading.local()    # .ids: the ids of this thread's open spans
_IDS = itertools.count()
_ANNOTATION = None     # jax.profiler.TraceAnnotation, once JAX is loaded


class Span(NamedTuple):
    """One finished span: ``start``/``end`` on ``time.monotonic()``,
    ``parent`` the ``id`` of the enclosing span (None at the top)."""
    name: str
    start: float
    end: float
    parent: Optional[int]
    id: int

    @property
    def seconds(self) -> float:
        return self.end - self.start


class span:
    """``with span(name):`` times the block as a span (module docstring).
    Parents follow the thread, not the asyncio task: a span held across an
    ``await`` is the parent of spans other tasks of its loop open
    meanwhile, so such a span should have no children."""
    __slots__ = ("name", "id", "parent", "start", "end", "_open", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        global _ANNOTATION
        self.id = next(_IDS)
        try:
            self._open = _OPEN.ids
        except AttributeError:
            self._open = _OPEN.ids = []
        self.parent = self._open[-1] if self._open else None
        self._open.append(self.id)
        if _ANNOTATION is None and "jax.profiler" in sys.modules:
            _ANNOTATION = sys.modules["jax.profiler"].TraceAnnotation
        # annotate only while a profiler records: a span otherwise makes
        # no profiler call at all
        self._ann = None
        if _ANNOTATION is not None and _ANNOTATION.is_enabled():
            self._ann = _ANNOTATION(self.name)
            self._ann.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.end = end = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._open.remove(self.id)
        _SPANS.append((self.name, self.start, end, self.parent, self.id))
        _CALLS.setdefault(self.name, []).append(end - self.start)
        return False


def record(name: str, seconds: float):
    _CALLS.setdefault(name, []).append(float(seconds))


def timeit(name: str, fn, *args, **kw):
    """Run ``fn`` inside ``span(name)``. Returns ``(result, seconds)``."""
    with span(name) as s:
        out = fn(*args, **kw)
    return out, s.end - s.start


def clear():
    _CALLS.clear()
    _SPANS.clear()


def entries() -> dict:
    """Raw per-name call durations (copy)."""
    return {k: list(v) for k, v in _CALLS.items()}


def spans(name: str = None) -> list:
    """The kept span records in the order they ended, all or one name's."""
    return [Span(*s) for s in list(_SPANS) if name is None or s[0] == name]


def self_time(sp: Span, records) -> float:
    """``sp``'s duration less its children's among ``records`` (children
    of one span run one after another on its thread, so they never
    overlap)."""
    return sp.seconds - sum(r.seconds for r in records if r.parent == sp.id)


def summary() -> list:
    """One dict per name: calls, total_s, cold_s (first call), warm_s
    (mean of later calls, None if single-call) and the compile-time
    estimate ``compile_s = cold_s - warm_s`` (None if single-call)."""
    out = []
    for name, xs in _CALLS.items():
        warm = sum(xs[1:]) / (len(xs) - 1) if len(xs) > 1 else None
        out.append(dict(
            name=name, calls=len(xs), total_s=sum(xs), cold_s=xs[0],
            warm_s=warm,
            compile_s=max(xs[0] - warm, 0.0) if warm is not None else None,
        ))
    return out
