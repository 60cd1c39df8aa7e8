"""Trace spans — the one span primitive, and the one span log.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation`` on every
backend, so in any profiler capture (``capture_trace(dir)``, or whatever
else started one) the span sits on the device trace's own timeline; its
attrs are the annotation's keyword arguments (the event's stats in the
xplane). The same span is recorded, always, into a process-wide bounded
log as ``(name, start, end, parent, attrs)``: ``start`` and ``end`` are
the two ``time.perf_counter()`` reads the span makes, ``parent`` is the
name of the span that was open on this thread when it started, ``attrs``
are small ints and strings (a request's spans carry its ``rid``). The log
is what ``span_events()`` returns; the flight recorders take their
intervals from the span object's ``start``/``end`` and make no clock read
of their own for an interval a span times.

No flag and no exporter: the log is always on, like the serving engine's
registry, and the xplane capture is the "on". A span costs about 2 us
off-capture (README, Observability).

Distinct from paddle_tpu.profiler: that module is the reference-parity
``paddle.profiler`` surface (scheduler states, summary tables, chrome
trace). ``obs.span`` is the internal instrumentation primitive the
runtime itself uses.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

_tls = threading.local()

#: the log's size: a set-up of ~5k spans plus a 51 s serving window of
#: ~520 ticks x ~10 spans fits nine times over, so a benchmark run reads
#: a whole log; a server that runs for days keeps its newest 65,536
SPAN_LOG_CAP = 65536
# thread-safe: GIL-atomic bounded-deque appends; readers snapshot
_span_log: deque = deque(maxlen=SPAN_LOG_CAP)

# thread-safe: one float, written by clear_spans() alone (last write
# wins), read by span_log_start()
_cleared_at = 0.0


class SpanRecord(NamedTuple):
    """One finished span as the log holds it. Times are
    ``time.perf_counter()`` seconds."""

    name: str
    start: float
    end: float
    parent: str | None
    attrs: dict


def _stack() -> list:
    s = getattr(_tls, "span_stack", None)
    if s is None:
        s = _tls.span_stack = []
    return s


class span:
    """Named scope: ``with obs.span("serving.decode.run", active=3) as sp:``.

    After the block ``sp.start`` and ``sp.end`` hold its two clock reads.
    ``sp.attrs`` may be added to inside the block (what is only known at
    the end, such as how many requests an admission pass admitted): the
    log holds the attrs as they are at the end, the annotation those the
    span was opened with."""

    __slots__ = ("name", "attrs", "start", "end", "parent", "_ann",
                 "_open")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._ann = TraceAnnotation(name, **attrs)

    def __enter__(self):
        stack = self._open = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._open.pop()
        _span_log.append((self.name, self.start, self.end, self.parent,
                          self.attrs))
        return False


def span_events(clear: bool = False) -> list[SpanRecord]:
    """Snapshot of the span log, in the order the spans ended (a parent
    after its children)."""
    out = [SpanRecord._make(r) for r in list(_span_log)]
    if clear:
        clear_spans()
    return out


def span_log_start() -> float:
    """The ``perf_counter`` time from which the log is whole: every span
    that started at or after it is still in the log. 0.0 until the ring
    first wraps or is cleared; then the end of the oldest record it still
    holds (records enter as spans end, so whatever the ring dropped ended
    before that). A reader of an interval that starts before this time
    reads a cut log."""
    if len(_span_log) < SPAN_LOG_CAP:
        return _cleared_at
    return max(_cleared_at, _span_log[0][2])


def clear_spans():
    global _cleared_at
    _cleared_at = time.perf_counter()
    _span_log.clear()


@contextlib.contextmanager
def capture_trace(log_dir: str):
    """On-demand device profile capture around a suspect window:

        with obs.capture_trace("/tmp/xplane"):
            engine.step()

    Wraps ``jax.profiler.start_trace/stop_trace`` (works on CPU too — the
    xplane then holds host events only, the program's spans among them).
    Refuses to nest with an already running capture (paddle_tpu.profiler's
    device tracing included): jax allows one active trace per process."""
    import os

    import jax.profiler

    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()
