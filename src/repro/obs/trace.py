"""Structured trace spans for synthesis and serving (DESIGN.md §12).

A :class:`Tracer` records nested, timed spans:

  synthesis    Stage-A planning, each fixed-point iteration (autotune +
               Stage-C mode probes), the validation gate and its
               demotions, Stage-D AOT compiles (``synthesis.*``);
  serving      batcher enqueue→flush waits, replica bucket dispatch,
               steal and shed events (``serve.*``).

Spans nest per thread: a span opened inside another (on the same thread)
records the outer span as its parent, and closing is LIFO — the span
taxonomy is a forest whose invariants ("every span closes", "parents
outlive children") are pinned by tests/test_obs.py.  Completed spans are
appended to one shared list under a lock; the per-thread *open* stack is
thread-local, so replicas tracing concurrently never corrupt each
other's nesting.

Tracing is opt-in: every instrumented call site takes ``tracer=None``
and skips span bookkeeping entirely when no tracer is supplied, so the
serving hot path pays nothing until someone asks for a trace.  The
export format is JSONL — one span per line, ``parent_id`` linking the
forest — written by ``serve_cnn --trace-out``.

``Tracer(annotate=...)`` mirrors every span opened with
:meth:`Tracer.span` into a second recorder: ``annotate(name)`` returns a
context manager entered just before the span opens and exited just after
it closes.  Passing ``jax.profiler.TraceAnnotation`` puts the spans in the
profiler's own trace, on their thread's line and on the device trace's
clock (``serve_cnn --profile-out``).  This module imports no jax; the
caller supplies the factory.
"""
from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, ContextManager, Dict, List, Optional

#: Attribute values are kept JSON-scalar so export never fails mid-run.
_SCALARS = (str, int, float, bool, type(None))


def _jsonable(value: object) -> object:
    return value if isinstance(value, _SCALARS) else repr(value)


@dataclass
class Span:
    """One timed, named region.  ``t_end`` is None while still open."""
    name: str
    span_id: int
    parent_id: Optional[int]
    t_start: float
    t_end: Optional[float] = None
    thread: str = ""
    attrs: Dict[str, object] = field(default_factory=dict)

    @property
    def closed(self) -> bool:
        return self.t_end is not None

    @property
    def duration_s(self) -> float:
        if self.t_end is None:
            raise ValueError(f"span {self.name!r} (#{self.span_id}) "
                             "is still open")
        return self.t_end - self.t_start

    def as_dict(self) -> Dict[str, object]:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "t_start": self.t_start,
                "t_end": self.t_end, "thread": self.thread,
                "attrs": {k: _jsonable(v) for k, v in self.attrs.items()}}


class Tracer:
    """Collects spans; one instance per serving tier / synthesis run.

    ``enabled=False`` turns every entry point into a no-op (the spans
    list stays empty) — the other half of the obs_overhead A/B.

    ``annotate`` (default None: off) maps a span name to a context
    manager that encloses each :meth:`span` (see the module docstring).
    Retroactive spans — :meth:`record_span` and :meth:`event` — are not
    mirrored: their start has passed by the time they are recorded.
    """

    def __init__(self, *, clock: Callable[[], float] = time.perf_counter,
                 enabled: bool = True,
                 annotate: Optional[Callable[[str], ContextManager]] = None):
        self.clock = clock
        self.enabled = enabled
        self.annotate = annotate
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._next_id = 0
        self._tls = threading.local()

    # -- internals -----------------------------------------------------------
    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _new_span(self, name: str, t_start: float,
                  attrs: Dict[str, object],
                  parent: Optional[int] = None) -> Span:
        if parent is None:
            stack = self._stack()
            parent = stack[-1].span_id if stack else None
        with self._lock:
            self._next_id += 1
            sid = self._next_id
        return Span(name=name, span_id=sid, parent_id=parent,
                    t_start=t_start, thread=threading.current_thread().name,
                    attrs=dict(attrs))

    def _finish(self, span: Span, t_end: float) -> None:
        span.t_end = t_end
        with self._lock:
            self._spans.append(span)

    # -- recording -----------------------------------------------------------
    def span(self, name: str, **attrs) -> "_SpanScope":
        """Open a nested span around the with-block.

        Yields the :class:`Span` so the block can attach late attributes
        (``span.attrs["batch"] = n``).  Closes — and records — the span
        even when the block raises, tagging it ``error=True``.
        """
        return _SpanScope(self, name, attrs)

    def event(self, name: str, **attrs) -> Optional[Span]:
        """A zero-duration span at "now" (shed/steal/demotion markers)."""
        if not self.enabled:
            return None
        t = self.clock()
        s = self._new_span(name, t, attrs)
        self._finish(s, t)
        return s

    def record_span(self, name: str, t_start: float, t_end: float,
                    parent: Optional[int] = None,
                    **attrs) -> Optional[Span]:
        """Record a span from caller-supplied timestamps (same clock base
        as ``tracer.clock``).  Used for retroactive regions whose start
        predates the recording call — e.g. the batcher's enqueue→flush
        wait, whose start is the oldest request's enqueue time.

        ``parent`` is the ``span_id`` of the span's parent; by default,
        the span open on the calling thread, as for :meth:`span`.  A
        region that ran on another thread, such as a phase of a
        pipelined dispatch, names its parent here."""
        if not self.enabled:
            return None
        s = self._new_span(name, t_start, attrs, parent)
        self._finish(s, t_end)
        return s

    # -- reads / export ------------------------------------------------------
    def finished(self) -> List[Span]:
        """Completed spans, in completion order (a copy)."""
        with self._lock:
            return list(self._spans)

    def open_spans(self) -> List[Span]:
        """Spans open on the *calling* thread (other threads' stacks are
        private by construction)."""
        return list(self._stack())

    def by_name(self, name: str) -> List[Span]:
        return [s for s in self.finished() if s.name == name]

    def export_jsonl(self, path: str) -> int:
        """Write one JSON object per completed span; returns span count."""
        spans = self.finished()
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps(s.as_dict(), sort_keys=True) + "\n")
        return len(spans)


class _SpanScope:
    """The context manager :meth:`Tracer.span` returns (a class, not a
    generator: it runs on the serving hot path when tracing is on)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_span", "_mirror")

    def __init__(self, tracer: Tracer, name: str, attrs: Dict[str, object]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._span: Optional[Span] = None
        self._mirror: Optional[ContextManager] = None

    def __enter__(self) -> Optional[Span]:
        tr = self._tracer
        if not tr.enabled:
            return None
        if tr.annotate is not None:
            self._mirror = tr.annotate(self._name)
            self._mirror.__enter__()
        s = self._span = tr._new_span(self._name, 0.0, self._attrs)
        tr._stack().append(s)
        s.t_start = tr.clock()
        return s

    def __exit__(self, exc_type, exc, tb) -> bool:
        s = self._span
        if s is None:
            return False
        tr = self._tracer
        tr._stack().pop()
        if exc_type is not None:
            s.attrs["error"] = True
        # The clock is read as late as possible on entry and as early as
        # possible on exit, so the span holds almost none of its own
        # bookkeeping, and the mirror encloses it closely.
        t_end = tr.clock()
        if self._mirror is not None:
            self._mirror.__exit__(exc_type, exc, tb)
        tr._finish(s, t_end)
        return False
