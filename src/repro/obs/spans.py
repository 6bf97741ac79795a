"""Nested timed spans — the telemetry layer's one span API.

A :class:`SpanCollector` is installed for a dynamic extent (a ``compile``
call, a benchmark section); inside it, ``with span(name, **attrs):``
records a nested timed span and ``set_attr(**attrs)`` annotates the
innermost open one (B&B states expanded, calibration batches, cache
hits).

While the JAX profiler records, every span also enters a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so the
inference path's ``vmcu.*`` spans (DESIGN.md §12) land on the profiler's
host line, on the same clock as the device's ops.  The profiler being on
is the only switch.

With NO collector installed and no profiler recording, :func:`span` is a
no-op context manager and :func:`set_attr` returns immediately —
instrumented code pays one contextvar lookup and one flag read, nothing
else, so spans are safe to leave in hot paths like the scheduler's
search loop and the per-op dispatch loop.

The collector is a :mod:`contextvars` variable, so concurrent compiles
(threads, async) each see their own span tree.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time
from typing import Any, ContextManager, Iterator

from jax.profiler import TraceAnnotation

_ACTIVE: contextvars.ContextVar["SpanCollector | None"] = \
    contextvars.ContextVar("vmcu_span_collector", default=None)


@dataclasses.dataclass
class Span:
    """One timed region: wall seconds, free-form attributes, children."""

    name: str
    seconds: float = 0.0
    start_s: float = 0.0       # offset from the collector's epoch
    attrs: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return {"name": self.name, "seconds": self.seconds,
                "start_s": self.start_s, "attrs": dict(self.attrs),
                "children": [c.to_dict() for c in self.children]}

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        return cls(name=d["name"], seconds=d["seconds"],
                   start_s=d.get("start_s", 0.0),
                   attrs=dict(d.get("attrs", {})),
                   children=[cls.from_dict(c)
                             for c in d.get("children", [])])


class SpanCollector:
    """Accumulates a forest of spans for one instrumented extent."""

    def __init__(self) -> None:
        self.spans: list[Span] = []
        self._stack: list[Span] = []
        self._epoch = time.perf_counter()

    def to_dicts(self) -> list[dict]:
        return [s.to_dict() for s in self.spans]


@contextlib.contextmanager
def collect(collector: SpanCollector | None = None
            ) -> Iterator[SpanCollector]:
    """Install a collector for the enclosed extent (a fresh one when not
    given; pass your own to accumulate several extents into one tree)."""
    col = collector if collector is not None else SpanCollector()
    token = _ACTIVE.set(col)
    try:
        yield col
    finally:
        _ACTIVE.reset(token)


_NOOP = contextlib.nullcontext()


class _Annotation(TraceAnnotation):
    """The profiler's annotation; like a span without a collector, it
    yields ``None``."""

    def __enter__(self) -> None:
        super().__enter__()


def span(name: str, **attrs: Any) -> ContextManager[Span | None]:
    """Record a timed span when a collector is active, and annotate the
    profiler's trace while it records; a no-op otherwise.  Yields the
    recorded :class:`Span`, or ``None`` without a collector."""
    col = _ACTIVE.get()
    if col is not None:
        return _span(col, name, attrs)
    if TraceAnnotation.is_enabled():
        return _Annotation(name, **attrs)
    return _NOOP


@contextlib.contextmanager
def _span(col: SpanCollector, name: str, attrs: dict) -> Iterator[Span]:
    with (_Annotation(name, **attrs) if TraceAnnotation.is_enabled()
          else _NOOP):
        s = Span(name=name, attrs=dict(attrs))
        s.start_s = time.perf_counter() - col._epoch
        parent = col._stack[-1] if col._stack else None
        (parent.children if parent is not None else col.spans).append(s)
        col._stack.append(s)
        t0 = time.perf_counter()
        try:
            yield s
        finally:
            s.seconds = time.perf_counter() - t0
            col._stack.pop()


def set_attr(**attrs: Any) -> None:
    """Annotate the innermost open span (no-op without a collector)."""
    col = _ACTIVE.get()
    if col is not None and col._stack:
        col._stack[-1].attrs.update(attrs)


def active() -> bool:
    """True iff a collector is installed (for cheap guard checks)."""
    return _ACTIVE.get() is not None
