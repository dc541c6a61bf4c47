"""Spans and counters at the layer boundaries of the program.

The recorder is off by default.  Off, :func:`span` returns one shared
no-op context manager after a single flag check — no allocation, no
clock read, no profiler annotation — and :func:`count` returns at once.
:func:`enable` turns it on for the whole process:

* a span enters ``jax.profiler.TraceAnnotation(name)``, so it lands in
  a profiler trace on the same clock as the device planes, and appends
  a :class:`SpanRecord` (``time.perf_counter`` seconds) to an in-memory
  list when it closes;
* the stack of open spans is per thread (the sweep service evaluates in
  its dispatcher thread), so each record names the span that encloses
  it in its own thread;
* the outermost ``sweep`` span allocates a ``sweep_id`` that every span
  opened inside it inherits, in the record and as a stat of the trace
  annotation (the trace keeps the span under its plain name);
* a counter sums into a dict.

:func:`snapshot` returns what was recorded and clears it.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict
from typing import NamedTuple

#: The span whose outermost instance allocates a ``sweep_id``.
ROOT = "sweep"

_on = False
_annotation = None                  # jax.profiler.TraceAnnotation, once on
_spans: list = []
_counters: dict = defaultdict(int)
_lock = threading.Lock()
_local = threading.local()
_ids = itertools.count(1)


class SpanRecord(NamedTuple):
    name: str
    parent: str | None
    sweep_id: int | None
    t0: float
    t1: float


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "parent", "sweep_id", "t0", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _stack()
        outer = stack[-1] if stack else None
        self.parent = None if outer is None else outer.name
        self.sweep_id = None if outer is None else outer.sweep_id
        if self.sweep_id is None and self.name == ROOT:
            self.sweep_id = next(_ids)
        self._ann = (_annotation(self.name) if self.sweep_id is None
                     else _annotation(self.name, sweep_id=self.sweep_id))
        self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _stack().pop()
        self._ann.__exit__(*exc)
        rec = SpanRecord(self.name, self.parent, self.sweep_id, self.t0, t1)
        with _lock:
            _spans.append(rec)
        return False


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str):
    """Context manager timing one pass through a layer boundary."""
    if not _on:
        return _OFF
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` (nothing when off)."""
    if not _on:
        return
    with _lock:
        _counters[name] += n


def enabled() -> bool:
    return _on


def enable() -> None:
    global _on, _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    global _on
    _on = False


def snapshot() -> dict:
    """``{"spans": [SpanRecord, ...], "counters": {name: total}}`` recorded
    since the last snapshot, which are cleared."""
    global _spans, _counters
    with _lock:
        spans, counters = _spans, _counters
        _spans, _counters = [], defaultdict(int)
    return {"spans": spans, "counters": dict(counters)}
