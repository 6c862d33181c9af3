"""Program spans and request records for the scheduler and engine layers.

Off by default: ``span`` then returns one shared no-op context manager
and nothing is recorded.  Only a caller turns it on, with ``start()``;
``stop()`` turns it off again and hands over what was recorded::

    from repro.serving import trace
    trace.start()
    ...                      # serve
    records = trace.stop()   # Records(spans=[Span...], requests=[RequestRecord...])

While on, each span records its id, its parent's id, its name, its start
and end on ``time.monotonic_ns()`` (the clock ``WallClock`` reads), the
dispatch it belongs to and its attributes, and enters
``jax.profiler.TraceAnnotation(name)``: while the profiler runs, the same
span lands in the trace's host plane, on the clock of the device ops.
Garbage collections are recorded as ``host.gc`` spans, with their
generation.  Records stay in memory; there is no exporter.

A span's dispatch is its ``dispatch`` attribute, or else its parent's.
Spans are recorded from the serving thread; the stack of open spans is
not per thread.
"""
from __future__ import annotations

import gc
import itertools
import time
from typing import Any, Dict, List, NamedTuple, Optional


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    start_ns: int
    end_ns: int
    dispatch: Optional[int]
    attrs: Dict[str, Any]


class RequestRecord(NamedTuple):
    """One request as the scheduler saw it, on the scheduler's clock (s)."""
    rid: int
    arrival: float
    dispatched: float
    finish: float
    dispatch: Optional[int]


class Records(NamedTuple):
    spans: List[Span]
    requests: List[RequestRecord]


class _Off:
    """The span handed out while tracing is off: enters nothing."""
    dispatch = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


OFF = _Off()


class _Tracer:
    def __init__(self):
        import jax
        self.annotation = jax.profiler.TraceAnnotation
        self.spans: List[Span] = []
        self.requests: List[RequestRecord] = []
        self.stack: List["_Open"] = []
        self.ids = itertools.count()
        self.dispatches = itertools.count()
        self._gc = None

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = self.annotation("host.gc")
            ann.__enter__()
            self._gc = (time.monotonic_ns(), ann)
        elif self._gc is not None:
            start, ann = self._gc
            self._gc = None
            ann.__exit__(None, None, None)
            top = self.stack[-1] if self.stack else None
            self.spans.append(Span(
                next(self.ids), top and top.id, "host.gc", start,
                time.monotonic_ns(), top and top.dispatch,
                {"generation": info.get("generation")}))


class _Open:
    """A span being recorded."""
    __slots__ = ("tracer", "name", "attrs", "id", "parent", "dispatch",
                 "start", "ann")

    def __init__(self, tracer: _Tracer, name: str, attrs: dict):
        self.tracer, self.name, self.attrs = tracer, name, attrs

    def __enter__(self):
        t = self.tracer
        top = t.stack[-1] if t.stack else None
        self.id = next(t.ids)
        self.parent = top and top.id
        self.dispatch = self.attrs.get("dispatch", top and top.dispatch)
        self.ann = t.annotation(self.name)
        self.ann.__enter__()
        t.stack.append(self)
        self.start = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        end = time.monotonic_ns()
        t = self.tracer
        t.stack.pop()
        self.ann.__exit__(*exc)
        t.spans.append(Span(self.id, self.parent, self.name, self.start, end,
                            self.dispatch, self.attrs))
        return False


_tracer: Optional[_Tracer] = None


def active() -> bool:
    return _tracer is not None


def start() -> None:
    """Turn recording on, discarding anything recorded before."""
    global _tracer
    stop()
    _tracer = _Tracer()
    gc.callbacks.append(_tracer.on_gc)


def stop() -> Records:
    """Turn recording off; returns what was recorded (empty when off)."""
    global _tracer
    t, _tracer = _tracer, None
    if t is None:
        return Records([], [])
    gc.callbacks.remove(t.on_gc)
    return Records(t.spans, t.requests)


def span(name: str, **attrs):
    """A context manager recording one span; the shared no-op when off."""
    if _tracer is None:
        return OFF
    return _Open(_tracer, name, attrs)


def next_dispatch() -> Optional[int]:
    """A fresh dispatch id while on, else None."""
    return None if _tracer is None else next(_tracer.dispatches)


def request(rid: int, arrival: float, dispatched: float, finish: float,
            dispatch: Optional[int]) -> None:
    """Record one request's times (no-op when off)."""
    if _tracer is not None:
        _tracer.requests.append(
            RequestRecord(rid, arrival, dispatched, finish, dispatch))
