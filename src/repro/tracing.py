"""Program spans: what the executor and the serving loop were doing, when.

A span is one timed stretch of host work at a layer boundary, such as
``ProgramExecutor.submit`` and its marshal, transfer and dispatch, or one
``DecodeServer.step`` and its argmax sync::

    from repro import tracing
    tracing.enable(annotate=False)      # record spans in memory
    ...                                 # serve
    spans = tracing.take()              # [Span(name, key, parent, ...)]
    tracing.disable()

Each :class:`Span` holds its wall times (``time.perf_counter``), the CPU
time its thread used (``time.thread_time``), its ``key`` (the executor's
step index or the server's wave number; a span without one takes its
enclosing span's) and the name of its enclosing span.  Wall time less CPU
time is time the thread was blocked: on the device, a transfer or a lock.

While ``annotate`` is on, each span is also written into the profiler's
trace as a ``jax.profiler.TraceAnnotation`` named ``ember.<name>``, on the
same clock as the device's operations, so an idle gap on the device is
named by the innermost span around it.

Off is the default.  Off, a site costs one flag check: no record is made
and no annotation built.  Counters stay in the components' ``stats`` dicts.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import NamedTuple, Optional

#: prefix of the profiler annotations the spans write
PREFIX = "ember."

_on = False
_annotate = False
_records: list = []
_local = threading.local()
_OFF = contextlib.nullcontext()


class Span(NamedTuple):
    name: str
    key: Optional[int]
    parent: Optional[str]
    t0: float                   # perf_counter at entry
    t1: float                   # perf_counter at exit
    cpu: float                  # thread CPU seconds between them


def enable(annotate: bool = False) -> None:
    """Start recording spans; with ``annotate`` also into the profiler."""
    global _on, _annotate
    _on, _annotate = True, bool(annotate)


def set_annotate(annotate: bool) -> None:
    """Switch the profiler annotations on or off; recording goes on."""
    global _annotate
    _annotate = bool(annotate)


def disable() -> None:
    """Stop recording; the records so far stay until :func:`take`."""
    global _on, _annotate
    _on = _annotate = False


def take() -> list:
    """The spans recorded so far, in the order they closed; clears them."""
    global _records
    out, _records = _records, []
    return out


def span(name: str, key: Optional[int] = None):
    """Context manager timing one span (a no-op while recording is off)."""
    if not _on:
        return _OFF
    return _Open(name, key)


class _Open:
    __slots__ = ("name", "key", "parent", "ann", "t0", "c0")

    def __init__(self, name: str, key: Optional[int]):
        self.name, self.key, self.ann = name, key, None

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        up = stack[-1] if stack else None
        self.parent = None if up is None else up.name
        if self.key is None and up is not None:
            self.key = up.key
        stack.append(self)
        if _annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        cpu = time.thread_time() - self.c0
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        _local.stack.pop()
        _records.append(Span(self.name, self.key, self.parent, self.t0, t1,
                             cpu))
        return False
