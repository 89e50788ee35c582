"""Spans and counters of the program's own host work.

A :class:`span` marks one piece of host work (a training step, the
serving engine's batch assembly, a fetch of logits) twice:

- as a ``jax.profiler.TraceAnnotation``, so that while a profile is
  being taken the work sits on the profiler's clock beside the device's
  operations (outside a profile this is one cheap check);
- as a record ``(id, parent, name, t0_ns, t1_ns, attrs)`` on
  ``time.perf_counter_ns``, appended to a bounded in-memory ring when
  the span closes, also when it is left by an exception.

Spans nest per thread; ``parent`` is the id of the enclosing span of
the same thread, 0 at the top. :func:`event` records a point in time
(a zero-length span), :func:`count` a named counter, :func:`gauge` a
named reading that each new one replaces.

Every jaxpr trace and every executable build (a compile, or a load
from the persistent compilation cache) of a jitted function is counted
through ``jax.monitoring``: under ``jit.traces.<fun>`` and
``jit.compiles.<fun>``, and once more in the ``compiles`` attribute of
the innermost span open in the thread that did it, so a record says
which step traced or compiled.

Tracing is always on; a span costs a few microseconds of host time.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple

import jax
from jax.profiler import TraceAnnotation

RING = 1 << 16              # records kept; the oldest are dropped first

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_KIND = {TRACE_EVENT: "jit.traces", COMPILE_EVENT: "jit.compiles"}


class Span(NamedTuple):
    id: int
    parent: int
    name: str
    t0_ns: int
    t1_ns: int
    attrs: Dict[str, Any]


_ring: "collections.deque[tuple]" = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_counters: "collections.Counter[str]" = collections.Counter()
_lock = threading.Lock()
_local = threading.local()


def _stack() -> List["span"]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span("serve.decode", step=3) as s: ...``; after the block
    ``s.seconds`` is the span's length on ``perf_counter_ns``."""

    __slots__ = ("id", "parent", "name", "attrs", "t0_ns", "t1_ns",
                 "_annotation", "_stack")

    def __init__(self, name: str, **attrs: Any):
        self.id = next(_ids)
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        stack = self._stack = _stack()
        self.parent = stack[-1].id if stack else 0
        stack.append(self)
        self._annotation = TraceAnnotation(self.name, **self.attrs)
        self._annotation.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._stack.pop()
        # a plain tuple here; :func:`spans` makes the named records
        _ring.append((self.id, self.parent, self.name, self.t0_ns,
                      self.t1_ns, self.attrs))
        return False

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


def new_id() -> int:
    """A fresh identifier from the records' own sequence, for tying
    several records together (one request's events)."""
    return next(_ids)


def event(name: str, **attrs: Any) -> int:
    """Record a point in time; returns the record's id."""
    with TraceAnnotation(name, **attrs):
        pass
    stack = _stack()
    i, t = next(_ids), time.perf_counter_ns()
    _ring.append((i, stack[-1].id if stack else 0, name, t, t, attrs))
    return i


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] += n


def gauge(name: str, value: int) -> None:
    """Set counter ``name`` to ``value``: a reading of the program as it
    now is (the bytes a built step exchanges), not a tally."""
    with _lock:
        _counters[name] = value


def spans() -> List[Span]:
    """The records in the ring, in the order their spans closed."""
    return [Span._make(r) for r in list(_ring)]


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Drop every record and counter (identifiers keep rising)."""
    _ring.clear()
    with _lock:
        _counters.clear()


def _on_compile_event(event_name: str, duration: float,
                      **kwargs: Any) -> None:
    kind = _KIND.get(event_name)
    if kind is None:
        return
    fun = str(kwargs.get("fun_name", "?"))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]                   # compiles say jit(f), traces f
    count(f"{kind}.{fun}")
    stack = getattr(_local, "stack", None)
    if stack:
        attrs = stack[-1].attrs
        attrs["compiles"] = attrs.get("compiles", 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
