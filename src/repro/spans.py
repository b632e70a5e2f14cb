"""Spans and counters of the program's own work, for a caller that asks.

    from repro import spans
    with spans.record() as rec:
        ...                          # the work to be measured
    rec.spans, rec.counters

``span(name, **ids)`` marks one piece of work and ``count(name, n)`` adds
to a counter.  Both do nothing unless a caller holds ``record()`` open:
then one test of a module variable, and ``span`` hands back a shared
no-op context.  While recording, each span also enters a
``jax.profiler.TraceAnnotation`` of its name and ids, so that it lands in
a profiler trace, on the device trace's clock, whenever the profiler runs;
and it is kept in memory as ``(name, start_ns, end_ns, parent, ids)`` on
``time.perf_counter_ns``, ``parent`` being the index of the span open
around it (-1 for none); a span still open when the record ends stays
``None``.  ``note(**ids)`` on the context adds ids found out inside the
span.  Spans past ``CAP`` are counted in ``spans.dropped`` and not kept.
JAX is imported only when recording starts, so modules that mark spans
need not depend on it.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

#: (name, start_ns, end_ns, parent index or -1, ids)
Span = Tuple[str, int, int, int, Dict[str, Any]]

DROPPED = "spans.dropped"
#: spans a record keeps; those past it only count in ``DROPPED``
CAP = 1_000_000


class Record:
    """What one ``record()`` collected."""

    def __init__(self, annotate: Callable[..., Any]):
        self.spans: List[Optional[Span]] = []
        self.counters: Dict[str, int] = {}
        self._annotate = annotate
        self._open: List[int] = []      # indices of the spans open, inner last


class _Noop:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def note(self, **ids: Any) -> None:
        return None


_NOOP = _Noop()
#: the record being collected, or None
_active: Optional[Record] = None


class _Span:
    __slots__ = ("rec", "name", "ids", "index", "start", "ann")

    def __init__(self, rec: Record, name: str, ids: Dict[str, Any]):
        self.rec, self.name, self.ids = rec, name, ids

    def __enter__(self):
        rec = self.rec
        self.ann = rec._annotate(self.name, **self.ids)
        self.ann.__enter__()
        if len(rec.spans) >= CAP:
            rec.counters[DROPPED] = rec.counters.get(DROPPED, 0) + 1
            self.index = -1
        else:
            self.index = len(rec.spans)
            rec.spans.append(None)      # filled in when the span ends
            rec._open.append(self.index)
        self.start = time.perf_counter_ns()
        return self

    def note(self, **ids: Any) -> None:
        """Add ids known only once the work has begun."""
        self.ids.update(ids)
        self.ann.set_metadata(**ids)

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        rec = self.rec
        if self.index >= 0:
            rec._open.pop()
            parent = rec._open[-1] if rec._open else -1
            rec.spans[self.index] = (self.name, self.start, end, parent,
                                     self.ids)
        self.ann.__exit__(*exc)


def recording() -> bool:
    """Whether a caller holds ``record()`` open (to skip work that only
    feeds a span's ids)."""
    return _active is not None


def span(name: str, **ids: Any):
    """A context that marks one piece of work while recording."""
    if _active is None:
        return _NOOP
    return _Span(_active, name, ids)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while recording."""
    rec = _active
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + n


@contextlib.contextmanager
def record() -> Iterator[Record]:
    """Record spans and counters until the block ends; one at a time."""
    global _active
    if _active is not None:
        raise RuntimeError("spans are already being recorded")
    from jax.profiler import TraceAnnotation
    rec = _active = Record(TraceAnnotation)
    try:
        yield rec
    finally:
        _active = None
