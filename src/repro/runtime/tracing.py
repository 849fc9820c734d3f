"""Spans and counters at the layer boundaries, on the profiler's clock.

A span is on only while a JAX profiler session records: inside
``jax.profiler.trace(dir)``, between ``start_trace`` and ``stop_trace``, or
during a profiler-server capture. It then opens a profiler annotation
named ``repro.<name>``, which lands on a host plane of the session's
``.xplane.pb`` on the same clock as the device ops, and it adds its count,
total time and self time (total less the time of its child spans on the
same thread) to an in-process table. Off, :func:`span` returns one shared
no-op context: no formatting, no lock, no allocation.

A span opened with ``request=True`` starts a request (one
``Planner.__call__``, one sweep point): the spans nested in it on its
thread carry its ``id``.

While a session records, two counters count JAX's compile events:
``jax.traces`` (a function traced to a jaxpr) and ``jax.compiles`` (a
program compiled, or loaded from the persistent compile cache), and
:func:`count` adds to the program's own counters, ``repro.<name>``.

:func:`snapshot` gives ``{span name: (count, total_s, self_s)}`` and
``{counter name: count}``; :func:`reset` clears both. They cover every
session recorded since the last reset, and nothing outside a session.
"""

from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import time
from typing import Dict, Tuple, Union

# ``jax.profiler.TraceAnnotation`` is this class with nothing added; taking
# it from jaxlib keeps JAX itself out of the import of the program's core.
from jaxlib._profiler import TraceMe

PREFIX = "repro."

#: JAX's compile events, and the counter each one adds to.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.traces",
    "/jax/core/compile/backend_compile_duration": "jax.compiles",
}

_OFF = contextlib.nullcontext()
#: Whether a profiler session records, so that spans and counters are on.
recording = TraceMe.is_enabled
_lock = threading.Lock()
_spans: Dict[str, list] = {}
_counters: Dict[str, int] = {}
_local = threading.local()
_ids = itertools.count(1)
_listening = False


class _Span:
    __slots__ = ("t0", "name", "request", "attrs", "stack", "rid", "child_s",
                 "annotation")

    # The interval runs from the first read of the clock in span() to the
    # last in __exit__, so a span's own cost is charged to the span and not
    # to its parent's self time.
    def __init__(self, t0: float, name: str, request: bool, attrs: dict):
        self.t0 = t0
        self.name = name
        self.request = request
        self.attrs = attrs

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self.stack = stack
        self.rid = (stack[-1].rid if stack and not self.request
                    else next(_ids))
        self.child_s = 0.0
        stack.append(self)
        self.annotation = TraceMe(self.name, id=self.rid, **self.attrs)
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        stack = self.stack
        stack.pop()
        with _lock:
            row = _spans.get(self.name)
            if row is None:
                row = _spans[self.name] = [0, 0.0, 0.0]
            dur = time.perf_counter() - self.t0
            row[0] += 1
            row[1] += dur
            row[2] += dur - self.child_s
        if stack:
            stack[-1].child_s += dur
        return False


def span(name: str, request: bool = False, **attrs):
    """A context for ``repro.<name>``: recorded while a profiler session
    records, the shared no-op otherwise."""
    if not recording():
        return _OFF
    t0 = time.perf_counter()
    if not _listening:
        _listen()
    return _Span(t0, PREFIX + name, request, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``repro.<name>`` while a profiler session
    records; nothing otherwise."""
    if recording():
        name = PREFIX + name
        with _lock:
            _counters[name] = _counters.get(name, 0) + n


def _count_compile(event: str, duration: float, **kwargs) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None and recording():
        with _lock:
            _counters[name] = _counters.get(name, 0) + 1


def _listen() -> None:
    """Register the compile-event listener, once, when JAX is loaded.

    A session records only after JAX is imported, so registering at the
    first span of a session misses no compile after it.
    """
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_count_compile)


def snapshot() -> Dict[str, Union[Tuple[int, float, float], int]]:
    """``{span: (count, total_s, self_s)}`` plus ``{counter: count}``;
    empty when nothing was recorded since the last :func:`reset`."""
    with _lock:
        out: Dict[str, Union[Tuple[int, float, float], int]] = {
            k: tuple(v) for k, v in _spans.items()}
        out.update(_counters)
    return out


def reset() -> None:
    """Clear every span aggregate and counter."""
    with _lock:
        _spans.clear()
        _counters.clear()


if "jax" in sys.modules:
    _listen()
