"""Program spans and their attributes, on the profiler's timeline.

A span is a named host interval with a few small attributes (integers, or a
constant string naming a program or an op).  The tuning loop opens spans at
the points where it does its work, so a trace of a job shows what the host
was doing while the device waited::

    with tracing.span("flight.retire", job_id=7, lane=1):
        scheduler.complete(...)

    ev = tracing.begin("flight.event")        # ends at another call site
    ...
    if ev is not None:
        tracing.record("flight.event", ev, time.time(), step=s)

``spans(name, t0, t1)`` returns the recorded spans that start in
``[t0, t1)``.  Spans of one trial share its ``job_id`` attribute.

The recorder records only while a JAX profiler session is collecting
(between ``jax.profiler.start_trace`` and ``stop_trace``, or inside
``jax.profiler.trace``) or after ``enable()``.  While recording, each span is
also a ``jax.profiler.TraceAnnotation`` carrying its attributes, so it lands
on the host lines of the same trace as the device's ops.  The stamps kept in
memory are ``time.time()``; the trace's own clock starts with the session,
so the two agree in durations and relative positions.

When it is off, a site costs one flag test and one ``TraceMe.is_enabled()``
call: no span object, no string and no clock read.  Nothing here touches a
device value.  Importing this module does not import JAX.
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

MAX_SPANS = 1 << 17   # the oldest spans are dropped past this many


class Span(NamedTuple):
    id: int
    name: str
    start: float                 # time.time() seconds
    end: float
    thread: str
    parent: Optional[int]        # id of the span open on the thread at start
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Begun:
    """An interval opened by ``begin``: its start stamp, the span open on
    the opening thread at that moment, and its profiler event."""

    __slots__ = ("start", "parent", "annotation")

    def __init__(self, start: float, parent: Optional[int], annotation):
        self.start = start
        self.parent = parent
        self.annotation = annotation


class _Open:
    """A ``span`` being recorded (the context manager of one with-block)."""

    __slots__ = ("rec", "name", "attrs", "id", "parent", "start", "annotation")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        stack = self.rec._stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(self.rec._ids)
        self.annotation = self.rec._annotate(self.name, self.attrs)
        self.start = time.time()
        stack.append(self)
        return self

    def __exit__(self, *exc):
        end = time.time()
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)
        stack = self.rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self.rec._add(Span(self.id, self.name, self.start, end,
                           threading.current_thread().name, self.parent,
                           self.attrs))
        return False


class _Off:
    """The with-block of a span while the recorder is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class Recorder:
    """A bounded, thread-safe buffer of spans.  The module's functions act
    on one recorder per process; tests may build their own."""

    def __init__(self, max_spans: int = MAX_SPANS):
        self._buf: collections.deque = collections.deque(maxlen=max_spans)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._enabled = False
        self._profiling = None     # TraceMe.is_enabled, once JAX is loaded
        self._annotation = None    # jax.profiler.TraceAnnotation

    # -- switches ---------------------------------------------------------------
    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def _profiler_on(self) -> bool:
        probe = self._profiling
        if probe is None:
            if "jax" not in sys.modules:
                return False   # no JAX, so no profiler session either
            from jax._src.lib import _profiler
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
            probe = self._profiling = _profiler.TraceMe.is_enabled
        return probe()

    def on(self) -> bool:
        """True while spans are recorded."""
        return self._enabled or self._profiler_on()

    # -- recording --------------------------------------------------------------
    def span(self, name: str, **attrs):
        """Context manager recording one span around its block."""
        if not (self._enabled or self._profiler_on()):
            return _OFF
        return _Open(self, name, attrs)

    def begin(self, name: str) -> Optional[Begun]:
        """Open an interval whose end sits at another call site or in another
        thread; ``record`` closes it.  None while the recorder is off."""
        if not (self._enabled or self._profiler_on()):
            return None
        stack = self._stack()
        return Begun(time.time(), stack[-1].id if stack else None,
                     self._annotate(name, None))

    def record(self, name: str, start, end: float, **attrs) -> None:
        """Record the interval ``[start, end)``.  ``start`` is a
        ``time.time()`` stamp, or the ``Begun`` of ``begin(name)``, whose
        profiler event ends now (pass ``end=time.time()`` with it)."""
        parent = None
        if isinstance(start, Begun):
            parent = start.parent
            if start.annotation is not None:
                start.annotation.set_metadata(**attrs)
                start.annotation.__exit__(None, None, None)
            start = start.start
        else:
            stack = self._stack()
            parent = stack[-1].id if stack else None
        self._add(Span(next(self._ids), name, float(start), float(end),
                       threading.current_thread().name, parent, attrs))

    def spans(self, name: Optional[str] = None, t0: float = float("-inf"),
              t1: float = float("inf")) -> List[Span]:
        """Recorded spans named ``name`` (any name if None) that start in
        ``[t0, t1)``, in the order they ended."""
        with self._lock:
            out = list(self._buf)
        return [s for s in out if (name is None or s.name == name)
                and t0 <= s.start < t1]

    # -- internals --------------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _annotate(self, name: str, attrs):
        """A started profiler event for the span, while a session collects."""
        if self._profiling is None or not self._profiling():
            return None
        ann = self._annotation(name, **(attrs or {}))
        ann.__enter__()
        return ann

    def _add(self, span: Span) -> None:
        with self._lock:
            self._buf.append(span)


_RECORDER = Recorder()
enable = _RECORDER.enable
disable = _RECORDER.disable
clear = _RECORDER.clear
on = _RECORDER.on
span = _RECORDER.span
begin = _RECORDER.begin
record = _RECORDER.record
spans = _RECORDER.spans
