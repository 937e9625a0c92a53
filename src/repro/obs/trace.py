"""Trace spans, in one of two records.

``TraceRecorder.span("harvest", kind="insert")`` wraps any region in a
span; ``instant`` drops a point marker.

* Chrome mode (the default): each span is a complete-event (``ph: "X"``)
  with microsecond timestamps on ``clock``; ``save(path)`` writes the
  standard ``{"traceEvents": [...]}`` envelope — open it at
  https://ui.perfetto.dev or ``chrome://tracing``.
* Profiler mode (``jax_profiler=True``): each span is one
  ``jax.profiler.TraceAnnotation(name, **args)`` and nothing else, so the
  JAX profiler's trace is the only record, on the device trace's clock:
  the name stays plain and the arguments become the event's stats.  The
  recorder keeps no event list.  Each garbage-collector pause of the
  process is recorded as a ``gc_pause`` span too (``generation`` stat),
  so an idle gap during a collection is booked to the collection, not to
  the span it interrupted.

A recorder is cheap but not free, so the serving stack only creates spans
when a recorder is passed in — ``tracer=None`` keeps the hot path
untouched.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator, List, Optional

from jax.profiler import TraceAnnotation as _JaxAnnotation


class TraceRecorder:
    """One recorder per run/scenario: Chrome events, or the profiler's."""

    def __init__(self, *, process_name: str = "repro",
                 jax_profiler: bool = False,
                 clock=time.perf_counter) -> None:
        self._jax = bool(jax_profiler)
        self._events: Optional[List[dict]] = None
        if self._jax:
            _record_gc_pauses()
            return
        self._events = []
        self._clock = clock
        self._t0 = clock()
        self._pid = os.getpid()
        self._events.append({
            "name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
            "args": {"name": process_name}})

    def _us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def span(self, name: str, **args: Any):
        if self._jax:
            return _JaxAnnotation(name, **args)
        return self._chrome_span(name, args)

    @contextmanager
    def _chrome_span(self, name: str, args: dict) -> Iterator[None]:
        tid = threading.get_ident() % (1 << 31)
        t0 = self._us()
        yield
        self._events.append({
            "name": name, "ph": "X", "ts": t0, "dur": self._us() - t0,
            "pid": self._pid, "tid": tid,
            "args": {k: _jsonable(v) for k, v in args.items()}})

    def instant(self, name: str, **args: Any) -> None:
        if self._jax:
            with _JaxAnnotation(name, **args):
                pass
            return
        self._events.append({
            "name": name, "ph": "i", "s": "t", "ts": self._us(),
            "pid": self._pid, "tid": threading.get_ident() % (1 << 31),
            "args": {k: _jsonable(v) for k, v in args.items()}})

    @property
    def events(self) -> List[dict]:
        return list(self._events or ())

    def save(self, path: str) -> None:
        if self._jax:
            raise ValueError("a profiler-mode recorder keeps no events: "
                             "the profiler's trace is its record")
        with open(path, "w") as f:
            json.dump({"traceEvents": self._events,
                       "displayTimeUnit": "ms"}, f)


_gc_open: List[_JaxAnnotation] = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        span = _JaxAnnotation("gc_pause", generation=info["generation"])
        span.__enter__()
        _gc_open.append(span)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def _record_gc_pauses() -> None:
    """Hook ``_gc_span`` into the collector, once per process."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return float(v)  # numpy / jax scalars
    except Exception:
        return str(v)


__all__ = ["TraceRecorder"]
