"""The program's tracer: named host spans, off unless switched on.

    with obs.span("ckpt.save", step=step) as sid:
        ...
        with obs.span("ckpt.snapshot"):     # child of ckpt.save
            ...

Off (the default), `span` returns one shared no-op context after a single
flag check: nothing is recorded and the profiler is not touched.  On, each
span is kept in memory as a `Record` and is also entered as a
`jax.profiler.TraceAnnotation` of the same name, so under a profiler
session it lands on the trace's host plane, on the device ops' clock.

A record's `parent` is the enclosing span on the same thread.  Work handed
to another thread names the span that started it with `cause=<id>`: the id
is what `with span(...) as sid` binds (None when tracing is off).  Spans of
one unit of work carry its identifier in their attributes (`step` for a
training step, `batch` for a served batch).  Names start with the layer:
`train.`, `ckpt.`, `data.`, `fs.`, `serve.`.

`enable()`, `disable()` and `drain()` are the whole control surface.  At
most `MAX_RECORDS` records are held between drains; later ones are counted
as dropped.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

MAX_RECORDS = 200_000


class Record(NamedTuple):
    id: int
    parent: Optional[int]
    cause: Optional[int]
    name: str
    thread: str
    start_ns: int          # time.perf_counter_ns()
    end_ns: int
    attrs: Dict[str, Any]


_on = False
_NOOP = contextlib.nullcontext()
_annotation = None          # jax.profiler.TraceAnnotation, bound by enable()
_ids = itertools.count(1)
_local = threading.local()
_lock = threading.Lock()
_records: List[Record] = []
_dropped = 0


class _Span:
    __slots__ = ("name", "cause", "attrs", "id", "parent", "ann", "t0")

    def __init__(self, name: str, cause: Optional[int],
                 attrs: Dict[str, Any]) -> None:
        self.name, self.cause, self.attrs = name, cause, attrs

    def __enter__(self) -> int:
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self.id

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _local.stack.pop()
        rec = Record(self.id, self.parent, self.cause, self.name,
                     threading.current_thread().name, self.t0, t1, self.attrs)
        global _dropped
        with _lock:
            if len(_records) < MAX_RECORDS:
                _records.append(rec)
            else:
                _dropped += 1


def span(name: str, cause: Optional[int] = None, **attrs: Any):
    """A context manager timing the block as span `name`; binds its id."""
    if not _on:
        return _NOOP
    return _Span(name, cause, attrs)


def enable() -> None:
    global _on, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> Tuple[List[Record], int]:
    """The records held and the count dropped since the last drain; both
    are cleared."""
    global _records, _dropped
    with _lock:
        out, dropped = _records, _dropped
        _records, _dropped = [], 0
    return out, dropped
