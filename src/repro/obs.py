"""Spans and counts inside the program, on the profiler's clock.

``span(name)`` brackets a piece of host work and ``count(name, n)`` adds to
a count on the innermost span open on the calling thread.  Both record only
while a profiler session collects (``jax.profiler.start_trace`` ...
``stop_trace``): with the profiler off a span costs one check of
``TraceAnnotation.is_enabled()`` and nothing is stored, so a trace window
scopes what is recorded without any other switch.

A span is kept only if the profiler collects both when it opens and when it
closes, so the spans of one session are those that lie wholly inside it,
and each carries all its children.  A count made outside every kept span is
lost with it: a ratio of counts to spans sees only whole spans at the
window's edges.

While recording, a span also writes a ``jax.profiler.TraceAnnotation`` of
its name, so it lands in the trace's host plane beside the device
operations.  Its in-memory record carries ``time.time_ns()`` stamps taken
inside that annotation: records and trace events sit at a constant offset
from each other, so the records can be laid over the device trace.

``collected()`` returns ``{"spans": [...], "dropped": n}``; each span is a
dict with ``seq`` (entry order), ``name``, ``start_ns``, ``end_ns``,
``parent`` (the ``seq`` of the innermost span open on the same thread when
it began, or None) and ``counts`` (its own counts and those of the spans
inside it).  ``reset()`` clears them.  At most ``CAPACITY`` spans are kept;
``dropped`` counts the rest.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time

import jax

CAPACITY = 1 << 16

recording = jax.profiler.TraceAnnotation.is_enabled

_OFF = contextlib.nullcontext()
_lock = threading.Lock()
_spans: list = []
_dropped = 0
_seq = itertools.count()


class _Local(threading.local):
    def __init__(self):
        self.stack = []     # the spans open and recording, innermost last


_local = _Local()


class _Span:
    __slots__ = ("name", "seq", "parent", "start", "counts", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.counts: dict = {}

    def __enter__(self):
        stack = _local.stack
        self.parent = stack[-1] if stack else None
        self.seq = next(_seq)
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.time_ns()
        return self

    def __exit__(self, *exc):
        global _dropped
        end = time.time_ns()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        parent = self.parent
        if parent is not None:
            for k, n in self.counts.items():
                parent.counts[k] = parent.counts.get(k, 0) + n
        if recording():
            rec = {"seq": self.seq, "name": self.name,
                   "start_ns": self.start, "end_ns": end,
                   "parent": None if parent is None else parent.seq,
                   "counts": self.counts}
            with _lock:
                if len(_spans) < CAPACITY:
                    _spans.append(rec)
                else:
                    _dropped += 1
        return False


def span(name: str):
    """Context manager: a host span named ``name`` (recorded only while a
    profiler session collects)."""
    if not recording():
        return _OFF
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to count ``name`` of the innermost span recording on this
    thread; without one the count is not kept."""
    stack = _local.stack
    if stack:
        counts = stack[-1].counts
        counts[name] = counts.get(name, 0) + n


def wait(name: str, arr) -> None:
    """Block on ``arr`` under span ``name`` if it is not ready yet, so that
    a read's wait for the device is timed apart from the read itself.  Only
    while recording: the read that follows would block there anyway."""
    if recording() and not arr.is_ready():
        with span(name):
            arr.block_until_ready()


def collected() -> dict:
    with _lock:
        return {"spans": list(_spans), "dropped": _dropped}


def reset() -> None:
    global _dropped
    with _lock:
        _spans.clear()
        _dropped = 0
