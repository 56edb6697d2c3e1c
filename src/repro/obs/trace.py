"""Span tracer: timed sections -> ring buffer -> Chrome trace_event.

``with span("engine.compile", bucket=key):`` times a section, records it
as a completed-event dict in a bounded in-memory ring buffer, optionally
appends it as JSONL to ``$CIM_TUNER_TRACE``, and (when the span was given
a histogram) feeds the duration into the metrics registry -- one
instrumentation point serves both the trace timeline and the latency
distributions.

Events are stored directly in Chrome ``trace_event`` shape (``ph: "X"``
complete events, ``ts``/``dur`` in microseconds), so export is a thin
wrapper: ``repro-service trace --export chrome`` writes a
``{"traceEvents": [...]}`` file Perfetto / ``chrome://tracing`` loads
as-is.

Spans nest: each records the span it opened inside (``args["parent"]``,
the enclosing span on the same thread), and a span that names no ``batch``
or ``job`` of its own takes the enclosing span's, so every span of one
queue dispatch (``batch``) or one job (``job``, its canonical key) can be
joined from submit to resolve.  While JAX is imported, a span also enters a
``jax.profiler.TraceAnnotation`` of its name, so a JAX profile taken while
the process runs shows the span on its own host plane, on the device
trace's clock; JAX is found through ``sys.modules`` only, so importing or
using this module never imports JAX nor starts a JAX backend.

Environment:

``CIM_TUNER_TRACE``
    Path; every finished span is appended there as one JSON line.
``CIM_TUNER_TRACE_BUFFER``
    Ring-buffer capacity (default 8192 spans); 0 disables buffering.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import json
import os
import sys
import threading
import time
import typing

__all__ = ["Span", "Tracer", "tracer", "span", "record", "current_span",
           "chrome_trace"]

_DEF_CAPACITY = 8192

#: process-unique span-id sequence (itertools.count increments atomically
#: under the GIL, so ids are race-free without a lock)
_SPAN_SEQ = itertools.count(1)


def _next_span_id() -> str:
    return f"{os.getpid():x}-{next(_SPAN_SEQ):x}"


#: args a span takes from the enclosing span when it names none itself:
#: the queue's dispatch sequence number and the job's canonical key
INHERITED = ("batch", "job")

#: the innermost open span of this thread (threads start with none)
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_span", default=None)


def current_span() -> "Span | None":
    """The innermost span open on this thread, or ``None``."""
    return _CURRENT.get()


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` when JAX is already
    imported, else ``None``; never imports JAX itself."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class Span:
    """One timed section, used as a context manager; attributes land in
    the event's ``args``.  ``span_id`` is the process-unique id the event
    carries in ``/v1/trace`` -- histogram exemplars reference it (see
    ``obs/metrics.py``)."""

    __slots__ = ("name", "cat", "args", "t0", "duration_s", "span_id",
                 "_tracer", "_histogram", "_token", "_ann")

    def __init__(self, name: str, cat: str, args: dict,
                 tracer: "Tracer | None" = None, histogram=None):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = time.perf_counter()
        self.duration_s: float | None = None
        self.span_id = _next_span_id()
        self._tracer = tracer
        self._histogram = histogram
        self._token = self._ann = None
        parent = _CURRENT.get()
        if parent is not None:
            args["parent"] = parent.span_id
            for k in INHERITED:
                if k not in args and k in parent.args:
                    args[k] = parent.args[k]

    def set(self, **kw) -> None:
        """Attach extra args discovered mid-span (e.g. result counts)."""
        self.args.update(kw)

    def __enter__(self) -> "Span":
        self._token = _CURRENT.set(self)
        self._ann = _annotation(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.duration_s = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _CURRENT.reset(self._token)
        self._tracer._finish(self, self._histogram)


class Tracer:
    """Bounded ring buffer of finished spans with optional JSONL sink."""

    def __init__(self, capacity: int | None = None,
                 jsonl_path: str | None = None):
        if capacity is None:
            capacity = int(os.environ.get("CIM_TUNER_TRACE_BUFFER",
                                          _DEF_CAPACITY))
        if jsonl_path is None:
            jsonl_path = os.environ.get("CIM_TUNER_TRACE") or None
        self.capacity = max(0, capacity)
        self.jsonl_path = jsonl_path
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(
            maxlen=self.capacity or 1)
        self._pid = os.getpid()
        # epoch anchor so perf_counter offsets become absolute-ish ts
        self._epoch_us = time.time() * 1e6 - time.perf_counter() * 1e6

    def span(self, name: str, *, cat: str = "repro",
             histogram=None, **args) -> Span:
        """Time a ``with`` block as one complete trace event.

        ``histogram`` is an optional :class:`repro.obs.metrics.Histogram`
        child or family (no labels) whose ``observe`` receives the span
        duration in seconds on exit, tagged with this span's id as an
        exemplar (so a latency outlier in ``/v1/metrics`` links back to
        its span in ``/v1/trace``).  Extra keyword args become the
        event's ``args`` payload.
        """
        return Span(name, cat, args, self, histogram)

    def record(self, name: str, t0: float, duration_s: float, *,
               cat: str = "repro", histogram=None, **args) -> Span:
        """Record a section that has already ended (``t0`` on the
        ``time.perf_counter`` clock) as a child of the current span: for
        sections known to be worth a span only once they are over, such
        as an executable call that turned out to re-trace."""
        sp = Span(name, cat, args)
        sp.t0, sp.duration_s = t0, duration_s
        self._finish(sp, histogram)
        return sp

    def _finish(self, sp: Span, histogram) -> None:
        self._record(sp)
        if histogram is not None:
            try:
                histogram.observe(sp.duration_s,
                                  exemplar={"span_id": sp.span_id})
            except TypeError:      # foreign histogram, no exemplars
                histogram.observe(sp.duration_s)

    def _record(self, sp: Span) -> None:
        ev = {
            "name": sp.name,
            "cat": sp.cat,
            "id": sp.span_id,
            "ph": "X",
            "ts": round(self._epoch_us + sp.t0 * 1e6, 3),
            "dur": round(sp.duration_s * 1e6, 3),
            "pid": self._pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": sp.args,
        }
        if self.capacity:
            with self._lock:
                self._events.append(ev)
        if self.jsonl_path:
            line = json.dumps(ev, default=str)
            with self._lock:
                try:
                    with open(self.jsonl_path, "a") as f:
                        f.write(line + "\n")
                except OSError:
                    # tracing must never take the workload down
                    self.jsonl_path = None

    def events(self) -> list[dict]:
        """Snapshot of the buffered events, oldest first."""
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        """Drop all buffered events (tests)."""
        with self._lock:
            self._events.clear()


def chrome_trace(events: typing.Iterable[dict]) -> dict:
    """Wrap raw span events as a Chrome/Perfetto trace document."""
    return {"traceEvents": list(events), "displayTimeUnit": "ms"}


# --------------------------------------------------------------------- #
# the process-wide tracer
# --------------------------------------------------------------------- #
_TRACER: Tracer | None = None
_TRACER_LOCK = threading.Lock()


def tracer() -> Tracer:
    """The process-wide :class:`Tracer` (lazily built so env vars set by
    tests before first use are honoured)."""
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = Tracer()
    return _TRACER


def span(name: str, *, cat: str = "repro", histogram=None, **args) -> Span:
    """``tracer().span(...)`` shorthand -- the one-liner subsystems use."""
    return tracer().span(name, cat=cat, histogram=histogram, **args)


def record(name: str, t0: float, duration_s: float, *, cat: str = "repro",
           histogram=None, **args) -> Span:
    """``tracer().record(...)`` shorthand."""
    return tracer().record(name, t0, duration_s, cat=cat,
                           histogram=histogram, **args)
