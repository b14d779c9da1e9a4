"""Program spans and host stalls, on the profiler's clock.

One recorder for the solver path.  A span is at once a
``jax.profiler.TraceAnnotation`` — so it lands on the host plane of the
profiler trace, on the clock of the device's ``XLA Ops`` — and an entry in a
bounded in-memory buffer that :func:`recorded` reads back, timed on
``time.perf_counter``::

    with spans.span("repro.service.flush", requests=4) as sp:
        ...
        sp.set(groups=2)            # attributes known only inside

The recorder is on only while a profiler session is active
(``jax.profiler.start_trace``) or inside :func:`recording`.  Off,
:func:`span` is one check and returns a shared no-op context that is falsy,
so a caller guards work done only for the record with ``if sp:``.

Two stalls that no call boundary shows are recorded as children of the span
open when they happen: an XLA compilation (``repro.compile``, from JAX's
backend-compile duration event, with end = now and start = end − duration)
and a Python garbage collection (``repro.gc``, from ``gc.callbacks``).  Off,
each costs one check.

Names are namespaced ``repro.*``.  The benchmark sums device time over its
own span names (``bench/run.py:TRACED_SPANS`` and ``window``); a program span
of the same name would count that time twice.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import time
from collections import deque

import jax
from jax._src.lib import _profiler

__all__ = ["Span", "span", "active", "recording", "recorded", "dropped", "clear",
           "CAPACITY"]

CAPACITY = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_profiling = _profiler.TraceMe.is_enabled
_forced = 0  # depth of open recording() blocks
_buffer: deque = deque(maxlen=CAPACITY)
_dropped = 0
_dropped_end = float("-inf")  # latest end of a span the full buffer let go
_ids = itertools.count(1)
_open: contextvars.ContextVar = contextvars.ContextVar("repro_span", default=None)


class Span:
    """One span: ``id``, ``parent`` (the enclosing span's id, or None),
    ``name``, ``request`` (a ticket, inherited from the parent when not
    given), ``t0``/``t1`` (``perf_counter`` seconds) and ``attrs``."""

    __slots__ = ("id", "parent", "name", "request", "t0", "t1", "attrs", "_trace", "_token")

    def __init__(self, name: str, request, attrs: dict):
        self.id = next(_ids)
        self.name, self.request, self.attrs = name, request, attrs
        self.parent = None
        self.t0 = self.t1 = float("nan")

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def set(self, **attrs) -> None:
        """Add attributes, to the record and to the open trace event."""
        self.attrs.update(attrs)
        self._trace.set_metadata(**attrs)

    def __enter__(self) -> Span:
        outer = _open.get()
        if outer is not None:
            self.parent = outer.id
            if self.request is None:
                self.request = outer.request
        self._token = _open.set(self)
        meta = self.attrs if self.request is None else {"request": self.request, **self.attrs}
        self._trace = _profiler.TraceMe(self.name, **meta)
        self._trace.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._trace.__exit__(*exc)
        _open.reset(self._token)
        _keep(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"request={self.request}, ms={1e3 * self.duration:.3f}, attrs={self.attrs})")


class _Off:
    """The shared no-op context :func:`span` returns while the recorder is
    off: falsy, and ``set`` does nothing."""

    __slots__ = ()

    def __enter__(self) -> _Off:
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


def active() -> bool:
    """True while spans are recorded: under a profiler or :func:`recording`."""
    return _forced > 0 or _profiling()


def span(name: str, *, request=None, **attrs):
    """A span named ``name`` with ``attrs``; ``request`` is the ticket it
    serves.  Returns the shared no-op context while the recorder is off."""
    if not (_forced or _profiling()):
        return _OFF
    return Span(name, request, attrs)


@contextlib.contextmanager
def recording():
    """Record spans inside the block without a profiler session."""
    global _forced
    _forced += 1
    try:
        yield
    finally:
        _forced -= 1


def recorded(t0: float = float("-inf"), t1: float = float("inf")) -> list[Span] | None:
    """The spans that started and ended inside ``[t0, t1]`` (``perf_counter``
    seconds), in the order they ended.  ``None`` when the full buffer let go
    of a span that ended at or after ``t0``: the list would be short."""
    if _dropped and _dropped_end >= t0:
        return None
    return [s for s in _buffer if s.t0 >= t0 and s.t1 <= t1]


def dropped() -> int:
    """How many spans the full buffer let go since the last :func:`clear`."""
    return _dropped


def clear() -> None:
    """Empty the buffer and forget what it dropped."""
    global _dropped, _dropped_end
    _buffer.clear()
    _dropped, _dropped_end = 0, float("-inf")


def _keep(s: Span) -> None:
    global _dropped, _dropped_end
    if len(_buffer) == _buffer.maxlen:
        _dropped += 1
        _dropped_end = max(_dropped_end, _buffer[0].t1)
    _buffer.append(s)


def _stall(name: str, t0: float, t1: float, outer: Span | None, **attrs) -> None:
    s = Span(name, None if outer is None else outer.request, attrs)
    s.parent = None if outer is None else outer.id
    s.t0, s.t1 = t0, t1
    _keep(s)


def _on_event(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT and (_forced or _profiling()):
        t1 = time.perf_counter()
        _stall("repro.compile", t1 - duration, t1, _open.get(), duration_s=duration)


_gc_open = None  # (t0, enclosing span, trace event) of the collection under way


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        if _forced or _profiling():
            trace = _profiler.TraceMe("repro.gc", generation=info["generation"])
            trace.__enter__()
            _gc_open = (time.perf_counter(), _open.get(), trace)
    elif _gc_open is not None:
        t0, outer, trace = _gc_open
        _gc_open = None
        trace.__exit__(None, None, None)
        _stall("repro.gc", t0, time.perf_counter(), outer,
               generation=info["generation"], collected=info["collected"])


jax.monitoring.register_event_duration_secs_listener(_on_event)
gc.callbacks.append(_on_gc)
