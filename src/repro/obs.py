"""Spans and counters of the program's own phases, always on and bounded.

A span names a host phase — a search call, its query preparation, the
dispatch, the wait for the device, the host re-rank, a build stage — and
is recorded twice:

* as a ``jax.profiler.TraceAnnotation``, so that inside a profiler session
  it lands in the trace on the same clock as the device operations (an
  idle gap on the device then lies under the host phase that caused it);
  with no session the annotation costs next to nothing;
* as a :class:`Span` in a bounded in-memory ring, timed with
  ``time.perf_counter_ns`` (the clock of ``time.perf_counter``), so a
  caller can read its own phases back without a profiler.

A span's parent is the innermost span open on the same thread when it
started (0 at the top); the ids of one process increase. :func:`count`
adds to a named counter and records the addition, timestamped and tagged
with the innermost open span, in a second ring of the same bound.
Nothing grows: each ring keeps its last :data:`CAPACITY` records.

Every program the process obtains from the backend, compiled or loaded
from the persistent compilation cache, is recorded as a ``dumpy.compile``
span (attribute ``fun``, the program's name) parented to the span open
at the time. It is read from the ``jax.monitoring`` time-span event that
JAX's compile funnel (``compiler.compile_or_get_cached``) reports around
both outcomes; :func:`compiles` is the running total.

Readout: :func:`spans`, :func:`counters`, :func:`clip` (to a window),
:func:`self_time`.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax

#: records each ring keeps (spans, counter additions)
CAPACITY = 1 << 14

#: the span name of a program obtained from the backend
COMPILE = "dumpy.compile"

# ``jax._src.dispatch.BACKEND_COMPILE_EVENT``: reported with ``fun_name``
# around every call of the compile funnel, cache hits included
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    sid: int
    parent: int            # enclosing span's sid, 0 at the top
    t0: int                # time.perf_counter_ns at entry
    t1: int                # ... at exit
    attrs: dict | None


class Count(NamedTuple):
    name: str
    n: int
    t: int                 # time.perf_counter_ns of the addition
    span: int              # innermost open span's sid, 0 at the top


_spans: collections.deque = collections.deque(maxlen=CAPACITY)
_counts: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_compiles = itertools.count(1)
_n_compiles = 0
_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the body as span ``name`` (yields its sid); ``attrs`` go to
    the profiler annotation and the ring record."""
    stack = _stack()
    sid = next(_ids)
    parent = stack[-1] if stack else 0
    stack.append(sid)
    t0 = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield sid
    finally:
        t1 = time.perf_counter_ns()
        stack.pop()
        _spans.append(Span(name, sid, parent, t0, t1, attrs or None))


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name``."""
    stack = _stack()
    _counts.append(Count(name, int(n), time.perf_counter_ns(),
                         stack[-1] if stack else 0))


def spans() -> list[Span]:
    """The ring's spans, in the order they ended."""
    return list(_spans)


def counters() -> list[Count]:
    """The ring's counter additions, oldest first."""
    return list(_counts)


def compiles() -> int:
    """Programs obtained from the backend since the process started."""
    return _n_compiles


def mark() -> int:
    """An id below that of every span opened from now on."""
    return next(_ids)


def clip(records: list, t0: int, t1: int) -> list:
    """The spans that lie wholly in ``[t0, t1]``, or the counter additions
    made in it."""
    return [r for r in records
            if (t0 <= r.t <= t1 if isinstance(r, Count)
                else t0 <= r.t0 and r.t1 <= t1)]


def self_time(spans: list[Span], name: str) -> list[int]:
    """Self time in ns of every span named ``name`` in ``spans``: its
    duration less that of its children among ``spans``."""
    child_ns: dict[int, int] = collections.defaultdict(int)
    for s in spans:
        child_ns[s.parent] += s.t1 - s.t0
    return [s.t1 - s.t0 - child_ns.get(s.sid, 0)
            for s in spans if s.name == name]


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    global _n_compiles
    if event != _COMPILE_EVENT:
        return
    t1 = time.perf_counter_ns()
    stack = _stack()
    _spans.append(Span(COMPILE, next(_ids), stack[-1] if stack else 0,
                       t1 - int((end - start) * 1e9), t1,
                       {"fun": str(kw.get("fun_name", "?"))}))
    _n_compiles = next(_compiles)


jax.monitoring.register_event_time_span_listener(_on_time_span)
