"""The traced window: the profiler around part of a run, the benchmark's
host spans around calls into the program, and the reduction."""

from __future__ import annotations

import contextlib
import functools
import shutil
import tempfile

from bench.harness import trace as tr

WINDOW_SPAN = "trace-window"


def annotate(owner, attr: str, span: str, undo: list) -> None:
    """Wrap ``owner.attr`` so every call runs under a host span named
    ``span``; ``undo`` collects what restores it."""
    import jax

    orig = getattr(owner, attr)

    @functools.wraps(orig)
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(span):
            return orig(*a, **k)

    setattr(owner, attr, wrapped)
    undo.append((owner, attr, orig))


def restore(undo: list) -> None:
    for owner, attr, orig in reversed(undo):
        setattr(owner, attr, orig)
    undo.clear()


class TracedWindow:
    """``with TracedWindow(spans) as tw:`` profiles the body; afterwards
    ``tw.result`` is ``trace.reduce`` over the body's span."""

    def __init__(self, span_names):
        self.span_names = set(span_names) | {WINDOW_SPAN}
        self.result = None
        self._stack = contextlib.ExitStack()

    def __enter__(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only: no per-call cost
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._stack.enter_context(jax.profiler.TraceAnnotation(WINDOW_SPAN))
        return self

    def __exit__(self, *exc):
        import jax

        self._stack.close()
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                ops, spans = tr.load_xplane(self._dir, self.span_names)
                win = [s for s in spans if s[0] == WINDOW_SPAN]
                if not win:
                    raise RuntimeError("the trace holds no window span")
                t0, t1 = win[0][1], win[0][2]
                spans = [s for s in spans if s[0] != WINDOW_SPAN]
                self.result = tr.reduce(ops, spans, t0, t1)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
