"""Per-solve trace spans → Chrome trace-event JSON (Perfetto-loadable).

A :func:`trace` context installs a process-wide :class:`TraceCollector`;
instrumented code opens nested :func:`span`s (solve → rung attempt → mbcg)
and drops :func:`instant` markers.  The collector writes the Trace Event
Format's "X" (complete) and "i" (instant) events with microsecond
timestamps, so the file loads directly in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``:

    with obs.trace("solve.trace.json"):
        solve(op, b, settings)

Nesting is positional, exactly as Chrome expects: spans on the same
thread whose [ts, ts+dur] intervals contain one another render as a
flame-graph stack.  Thread id = Python ``threading.get_ident()`` so the
serving session's worker threads get their own rows.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name,
so inside a ``jax.profiler`` capture the program's host spans sit on the
device trace's clock, next to the device ops they dispatch and wait for.
Spans record run time only: a span opened while JAX traces a function
(under ``jit``, ``grad``, ``vmap``) would time the tracing, once per
compile, so it records nothing.  What a jitted step does on the device is
read from the device trace by its named scopes (``bbmm.*``, ``optim.adam``)
and kernel names, not from this collector.

With no collector installed, :func:`span` only enters the annotation (a
no-op outside a capture) and :func:`instant` is a ``None``-check.  No jax
imports at module scope: jax is imported on the first span.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional


class TraceCollector:
    """Accumulates Chrome trace events (thread-safe appends)."""

    def __init__(self, *, clock=time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self.events: list = []

    def now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def add_complete(self, name: str, ts_us: float, dur_us: float, args=None):
        ev = {
            "name": name,
            "ph": "X",
            "ts": ts_us,
            "dur": max(dur_us, 0.0),
            "pid": self._pid,
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def add_instant(self, name: str, args=None):
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",  # thread-scoped instant
            "ts": self.now_us(),
            "pid": self._pid,
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def spans(self, name: Optional[str] = None) -> list:
        """All complete ("X") events, optionally filtered by name."""
        with self._lock:
            evs = list(self.events)
        return [e for e in evs if e["ph"] == "X" and (name is None or e["name"] == name)]

    def instants(self, name: Optional[str] = None) -> list:
        with self._lock:
            evs = list(self.events)
        return [e for e in evs if e["ph"] == "i" and (name is None or e["name"] == name)]

    def to_dict(self) -> dict:
        with self._lock:
            return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())


_active: Optional[TraceCollector] = None
_install_lock = threading.Lock()


def active_trace() -> Optional[TraceCollector]:
    """The installed collector, or None (the null-sink fast path)."""
    return _active


@contextmanager
def trace(path: Optional[str] = None, *, collector: Optional[TraceCollector] = None):
    """Install a trace collector for the dynamic extent of the block.

    Yields the collector; if ``path`` is given the Chrome trace JSON is
    written there on exit (even on error — a failed solve's trace is the
    one you want to look at)."""
    global _active
    col = collector if collector is not None else TraceCollector()
    with _install_lock:
        prev = _active
        _active = col
    try:
        yield col
    finally:
        with _install_lock:
            _active = prev
        if path is not None:
            col.save(path)


_jax_hooks = None  # (TraceAnnotation, is_top_level) once jax is imported


def _hooks():
    global _jax_hooks
    if _jax_hooks is None:
        import jax
        from jax.profiler import TraceAnnotation

        _jax_hooks = (TraceAnnotation, jax.core.trace_ctx.is_top_level)
    return _jax_hooks


@contextmanager
def span(name: str, **args):
    """A named host span covering the block: a ``jax.profiler`` annotation,
    plus a complete event in the active trace() collector if any.  Records
    nothing while JAX traces a function (see the module docstring)."""
    annotation, at_top_level = _hooks()
    if not at_top_level():
        yield None
        return
    with annotation(name):
        col = _active
        if col is None:
            yield None
            return
        t0 = col.now_us()
        try:
            yield col
        finally:
            col.add_complete(name, t0, col.now_us() - t0, args or None)


def instant(name: str, **args) -> None:
    """A zero-duration trace marker; no-op when no trace() active."""
    col = _active
    if col is not None:
        col.add_instant(name, args or None)
