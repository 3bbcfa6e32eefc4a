"""Spans at the program's layer boundaries, recorded only while a profiler
session runs.

``span(name, **attrs)`` is a context manager.  With no profiler session
(``jax.profiler.TraceAnnotation.is_enabled()`` false) it returns one
shared null context and records nothing, so an untraced run pays one
check per span.  While a session runs (``jax.profiler.start_trace``, the
benchmark's ``--trace 1``, an operator's capture) it enters a
``TraceAnnotation``, which puts the span on the device trace's clock with
``attrs`` as event stats, and appends one ``Span`` to a bounded
process-wide log that ``spans()`` returns: the profiler session it
mirrors is process-wide too.

Times are ``time.perf_counter_ns()``; ``parent`` is the ``id`` of the
enclosing span on the same thread, or None.  Spans of one request or job
share an id attr (``rid``, ``job``).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# 77,000 spans cover a 51 s KMeans window; older spans fall off first
MAX_SPANS = 200_000

_NULL = contextlib.nullcontext()
_LOG: Deque["Span"] = deque(maxlen=MAX_SPANS)
_IDS = itertools.count()
_STACK = threading.local()


class Span(NamedTuple):
    id: int
    name: str
    start_ns: int
    end_ns: int
    thread: int
    parent: Optional[int]
    attrs: Dict[str, Any]


class _Recorded:
    __slots__ = ("name", "attrs", "id", "parent", "start", "annotation")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = _STACK.__dict__.setdefault("ids", [])
        self.id = next(_IDS)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self.annotation = TraceAnnotation(self.name, **self.attrs)
        self.annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _STACK.ids.pop()
        _LOG.append(Span(self.id, self.name, self.start, end,
                         threading.get_ident(), self.parent, self.attrs))
        return False


def span(name: str, **attrs):
    """A span named `name` with `attrs` (numbers or strings) while a
    profiler session runs; the shared null context otherwise."""
    if not TraceAnnotation.is_enabled():
        return _NULL
    return _Recorded(name, attrs)


def spans() -> Deque[Span]:
    """The log itself, oldest first, at most ``MAX_SPANS`` long."""
    return _LOG
