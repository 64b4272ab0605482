"""Per-request spans and counters of the index's query path, kept in memory.

A *request* is one outermost public call into the query path
(``SegmentedIndex.query_many``, ``SegmentedIndex.count``,
``SegmentedIndex.execute_compressed_many``,
``JaxBackend.execute_compressed_many``).  It opens a :class:`Record` with
an id of its own and a root span named ``query``; a public call made
inside an open request joins that record instead of opening another.  The
open record lives in a context variable, so each thread (and each asyncio
task) records into its own.

Inside a request, :class:`span` records ``(name, start_ns, end_ns,
parent)`` -- ``parent`` is the index of the enclosing span in
``Record.spans``, ``None`` for the root -- and :func:`add` adds to a named
counter.  A span also enters ``jax.profiler.TraceAnnotation(name)`` when
JAX is already imported, so under a profiler session it sits on the
trace's ``/host:CPU`` plane, on the clock of the device ops; it never
imports JAX itself, so the numpy backend's path stays JAX-free.  Outside a
request a span still times itself (``span.seconds``) and annotates the
profiler, but records nothing.

A request that completes is appended to a bounded ring of the newest
:data:`RING` records; one that raises is dropped.  :func:`recent` returns
copies of the newest records, oldest first.  Recording is always on and
costs a few microseconds per span; nothing switches it.
"""

from __future__ import annotations

import contextvars
import itertools
import sys
import time
from collections import deque

from ..analysis.runtime import make_lock

__all__ = ["RING", "Record", "add", "recent", "request", "span"]

#: Completed requests the ring keeps.
RING = 1024

# (record, index of the innermost open span) of this context's request
_OPEN: contextvars.ContextVar = contextvars.ContextVar("repro_trace_open",
                                                       default=None)
_IDS = itertools.count(1)
_ANNOTATION = None  # jax.profiler.TraceAnnotation, once JAX is imported


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class Record:
    """One request: ``id``, ``kind`` (the public call that opened it),
    ``spans`` as ``(name, start_ns, end_ns, parent)`` in the order they
    opened (``spans[0]`` is the ``query`` root) and ``counters``."""

    __slots__ = ("id", "kind", "spans", "counters")

    def __init__(self, id_: int, kind: str, spans=None, counters=None):
        self.id = id_
        self.kind = kind
        self.spans = spans if spans is not None else []
        self.counters = counters if counters is not None else {}

    def copy(self) -> "Record":
        return Record(self.id, self.kind, list(self.spans),
                      dict(self.counters))

    @property
    def duration_ns(self) -> int:
        _, start, end, _ = self.spans[0]
        return end - start

    def self_ns(self) -> list:
        """Each span's duration minus its children's, in span order; the
        list sums to :attr:`duration_ns`."""
        own = [end - start for _, start, end, _ in self.spans]
        for _, start, end, parent in self.spans:
            if parent is not None:
                own[parent] -= end - start
        return own

    def self_ns_of(self, names) -> int:
        """Summed self time of the spans named in ``names``."""
        return sum(t for (name, *_), t in zip(self.spans, self.self_ns())
                   if name in names)

    def __repr__(self):
        return (f"Record(id={self.id}, kind={self.kind!r}, "
                f"spans={len(self.spans)}, counters={self.counters})")


class _Ring:
    """The newest completed records, shared by every thread."""

    def __init__(self, size: int):
        self._mutex = make_lock("trace_ring", reentrant=False)
        self._records: deque = deque(maxlen=size)  # guarded-by: _mutex

    def append(self, record: Record) -> None:
        with self._mutex:
            self._records.append(record)

    def recent(self, n: int | None) -> list:
        with self._mutex:
            records = list(self._records)
        if n is not None:
            records = records[max(len(records) - n, 0):] if n > 0 else []
        return [r.copy() for r in records]


_RING = _Ring(RING)


def recent(n: int | None = None) -> list:
    """Copies of the newest ``n`` completed request records (all the ring
    holds when ``n`` is None), oldest first."""
    return _RING.recent(n)


class span:
    """Context manager timing one stage: recorded into the open request,
    annotated on the profiler's host plane.  After it exits,
    ``seconds`` is its duration."""

    __slots__ = ("name", "start_ns", "end_ns", "_open", "_index", "_token",
                 "_ann")

    def __init__(self, name: str):
        self.name = name
        self.start_ns = self.end_ns = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __enter__(self):
        ann = _annotation()
        self._ann = ann(self.name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self._open = cur = _OPEN.get()
        if cur is not None:
            rec, parent = cur
            self._index = len(rec.spans)
            rec.spans.append((self.name, 0, 0, parent))  # filled on exit
            self._token = _OPEN.set((rec, self._index))
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        if self._open is not None:
            _OPEN.reset(self._token)
            rec, parent = self._open
            rec.spans[self._index] = (self.name, self.start_ns, self.end_ns,
                                      parent)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class request:
    """Context manager around a public call: opens a record and its
    ``query`` span, or joins the record already open in this context.
    The record is kept only if the call returns."""

    __slots__ = ("kind", "_token", "_record", "_root")

    def __init__(self, kind: str):
        self.kind = kind

    def __enter__(self):
        if _OPEN.get() is not None:
            self._token = None
            return self
        self._record = Record(next(_IDS), self.kind)
        self._token = _OPEN.set((self._record, None))
        self._root = span("query")
        self._root.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._token is None:
            return False
        self._root.__exit__(exc_type, exc, tb)
        _OPEN.reset(self._token)
        if exc_type is None:
            _RING.append(self._record)
        return False


def add(counter: str, n) -> None:
    """Add ``n`` to ``counter`` of the open request (nothing outside
    one)."""
    cur = _OPEN.get()
    if cur is not None:
        counters = cur[0].counters
        counters[counter] = counters.get(counter, 0) + int(n)
