"""The program's span recorder: where the time of a publish, a restore, a
train step or a decode call goes.

``with span(name, **attrs):`` records ``name``, its start and end on
``time.perf_counter()``, an id, the id of the innermost span open on the
same thread (its parent), the thread and ``attrs``. The same ``with`` enters
a ``jax.profiler.TraceAnnotation`` while a profiler session is on, so every
span also lands in the trace, on the device trace's clock; outside a
session it only asks whether one is on. ``annotate(**counts)`` adds counters
to the innermost open span of the calling thread; ``last()`` is the span
that thread closed last, for a caller that times a call by its span.

Records go into one bounded ring, always on. ``records(t0, t1)`` returns
the spans that lie wholly inside a window; ``truncated(t0)`` says whether
the ring has already dropped one that started at or after ``t0``.

JAX's compile events (``/jax/core/compile/*_duration``, trace, lowering and
backend compile, a persistent-cache load included in the last) become
``jax.compile`` records with the event's ``phase`` and ``fun``: written
after the fact, so they are in the ring but not in the profiler's trace.
Nested traces give nested records; a sum over time takes their union.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque

import jax.monitoring
from jax.profiler import TraceAnnotation

RING = 1 << 16
COMPILE = "jax.compile"
_COMPILE_EVENT = "/jax/core/compile/"

_ring: deque = deque(maxlen=RING)
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_dropped_start = float("-inf")  # latest start among the records the ring dropped


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _keep(rec: "span") -> None:
    global _dropped_start
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped_start = max(_dropped_start, _ring[0].start)
        _ring.append(rec)


class span:
    """``with span("dhp.publish", job=job_id) as s:`` ... ``s.duration``.

    The object is its own record: ``name``, ``start`` and ``end``
    (``perf_counter`` seconds), ``id``, ``parent`` (an id or None),
    ``thread`` and ``attrs``.
    """

    __slots__ = ("name", "attrs", "start", "end", "id", "parent", "thread", "_ann", "_stack")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self.start = self.end = 0.0

    @property
    def duration(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "span":
        self._stack = stack = _stack()
        self.id, self.thread = next(_ids), threading.get_ident()
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self._ann = None
        if TraceAnnotation.is_enabled():  # a profiler session is on
            self._ann = TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stack.pop()
        _local.last = self
        _keep(self)


def annotate(**counts) -> None:
    """Add ``counts`` to the innermost open span of this thread (none: no-op)."""
    stack = _stack()
    if stack:
        stack[-1].attrs.update(counts)


def last() -> span:
    """The span the calling thread closed last."""
    return _local.last


def records(t0: float = float("-inf"), t1: float = float("inf")) -> list[span]:
    """The recorded spans that lie wholly inside ``[t0, t1]``, in end order."""
    with _lock:
        snap = list(_ring)
    return [r for r in snap if r.start >= t0 and r.end <= t1]


def truncated(t0: float) -> bool:
    """Whether the ring has dropped a span that started at or after ``t0``."""
    return _dropped_start >= t0


def _on_duration(event: str, duration: float, **kw) -> None:
    if not event.startswith(_COMPILE_EVENT):
        return
    rec = span(COMPILE, phase=event[len(_COMPILE_EVENT):].removesuffix("_duration"),
               fun=str(kw.get("fun_name", "")))
    stack = _stack()
    rec.end = time.perf_counter()
    rec.start = rec.end - duration
    rec.id, rec.thread = next(_ids), threading.get_ident()
    rec.parent = stack[-1].id if stack else None
    _keep(rec)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
