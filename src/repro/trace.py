"""Named host spans on the profiler's clock, and per-request stage times.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: while a profiler
trace runs (``jax.profiler.start_trace``), the span lands in the trace's
host plane beside the device's operations, on the same clock.  While the
thread works for a request — inside ``recording(record)`` — the span's
duration is also added to that request's :class:`Record`, which the serve
engine turns into the response's ``timing`` fields.  With the profiler
off a span costs the annotation object and two ``perf_counter`` reads.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from time import perf_counter

from jax.profiler import TraceAnnotation

__all__ = ["Record", "recording", "span"]

_local = threading.local()


class Record:
    """One request's span times in seconds, summed per span name:
    ``total`` is the spans' duration, ``own`` the part of it that no
    nested span covers."""

    __slots__ = ("total", "own")

    def __init__(self):
        self.total: dict = {}
        self.own: dict = {}


@contextmanager
def recording(record: Record):
    """Add the spans this thread opens to ``record`` until exit."""
    prev = getattr(_local, "record", None), getattr(_local, "stack", None)
    _local.record, _local.stack = record, []
    try:
        yield record
    finally:
        _local.record, _local.stack = prev


class span:
    """``with span("d4m.stage"):`` — see the module docstring."""

    __slots__ = ("name", "_ann", "_rec", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._rec = getattr(_local, "record", None)
        if self._rec is not None:
            _local.stack.append(0.0)       # time of the spans nested here
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = perf_counter() - self._t0
        self._ann.__exit__(*exc)
        rec = self._rec
        if rec is not None:
            stack = _local.stack
            nested = stack.pop()
            if stack:
                stack[-1] += dur
            rec.total[self.name] = rec.total.get(self.name, 0.0) + dur
            rec.own[self.name] = rec.own.get(self.name, 0.0) + dur - nested
        return False
