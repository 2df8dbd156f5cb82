"""The program's own spans, on one clock with the device trace.

:meth:`repro.obs.Telemetry.annotate` is the one emit path.  When the
telemetry is armed, each span it returns does three things: it reads the
monotonic clock in nanoseconds (:func:`repro.obs.clock.now_ns`, out of
band of the engine's injected clock, so a flight recording never sees
it), enters a ``jax.profiler.TraceAnnotation`` of the same name (when
dispatch annotations are on), and on exit stores one
:class:`SpanRecord` in a bounded ring, the :class:`SpanStore`.

A span's index is taken when it opens, so records sort by start and a
child names its enclosing span (``parent``) by that index.  The ring
keeps the newest ``capacity`` indices; older ones are dropped and
counted.  While a store watches the garbage collector, every collection
is a ``python/gc`` span carrying its ``generation`` and ``collected``
count, nested under whatever span was open.

Readers line the store up with a profiler capture through the
annotations of the same name: the offset between the two clocks is
constant over a run (``bench/spans.py``; ``ProfilerSession.start``
marks it with a ``repro/clock`` annotation for operators).
"""
from __future__ import annotations

import gc
import weakref
from typing import Iterator, List, NamedTuple, Optional, Tuple

from repro.obs.clock import now_ns
from repro.obs.profiler import annotation

GC_SPAN = "python/gc"
DEFAULT_CAPACITY = 65536            # ~51 s at ~10 spans per 40 ms step


class SpanRecord(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: int                     # index of the enclosing span, -1: none
    request_id: Optional[int]
    args: dict


class Span:
    """One armed span: a context manager made by :meth:`SpanStore.span`."""

    __slots__ = ("store", "name", "request_id", "args", "index", "parent",
                 "start", "note")

    def __init__(self, store: "SpanStore", name: str,
                 request_id: Optional[int], args: dict):
        self.store = store
        self.name = name
        self.request_id = request_id
        self.args = args
        self.note = None

    def __enter__(self) -> "Span":
        st = self.store
        self.index, self.parent = st._open()
        # the annotation's event starts when it is made, so the clock is
        # read just before: the two stamps differ by a few microseconds
        self.start = now_ns()
        if st.annotate:
            self.note = annotation(self.name, **self.args)
            self.note.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self.note is not None:
            self.note.__exit__(*exc)
            self.note = None
        end = now_ns()
        self.store._close(self.index, SpanRecord(
            self.name, self.start, end, self.parent, self.request_id,
            self.args))
        return False


class SpanStore:
    """A bounded ring of finished spans with their nesting.

    ``annotate`` also enters a profiler annotation per span; a store
    without it (a Chrome trace exporter alone) only records."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 annotate: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.annotate = annotate
        self.opened = 0             # spans opened so far: the next index
        self._ring: List[Optional[SpanRecord]] = [None] * capacity
        self._stack: List[int] = []     # open spans, innermost last
        self._gc_start: Optional[int] = None
        self._gc_hook = None

    # -- recording ---------------------------------------------------------
    def span(self, name: str, request_id: Optional[int] = None,
             args: Optional[dict] = None) -> Span:
        return Span(self, name, request_id, {} if args is None else args)

    def _open(self) -> Tuple[int, int]:
        i = self.opened
        self.opened += 1
        self._ring[i % self.capacity] = None
        parent = self._stack[-1] if self._stack else -1
        self._stack.append(i)
        return i, parent

    def _close(self, i: int, rec: SpanRecord) -> None:
        self._stack.pop()               # `with` blocks close innermost first
        if i >= self.opened - self.capacity:
            self._ring[i % self.capacity] = rec

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = now_ns()
            return
        if self._gc_start is None:
            return
        t0, self._gc_start = self._gc_start, None
        i = self.opened
        self.opened += 1
        self._ring[i % self.capacity] = SpanRecord(
            GC_SPAN, t0, now_ns(), self._stack[-1] if self._stack else -1,
            None, {"generation": info.get("generation"),
                   "collected": info.get("collected")})

    # -- the garbage collector -------------------------------------------
    def watch_gc(self) -> None:
        """Record every collection as a ``python/gc`` span until
        :meth:`unwatch_gc`.  The hook holds the store weakly and leaves
        ``gc.callbacks`` when the store is collected."""
        if self._gc_hook is not None:
            return
        ref = weakref.ref(self)

        def hook(phase, info):
            st = ref()
            if st is not None:
                st._on_gc(phase, info)

        self._gc_hook = hook
        gc.callbacks.append(hook)
        weakref.finalize(self, _drop_hook, hook)

    def unwatch_gc(self) -> None:
        hook, self._gc_hook = self._gc_hook, None
        if hook is not None:
            _drop_hook(hook)

    # -- reading -------------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Spans opened so long ago that the ring no longer holds them."""
        return max(0, self.opened - self.capacity)

    def records(self) -> Iterator[Tuple[int, SpanRecord]]:
        """(index, record) of every finished span the ring holds, by
        index (that is, by start; a ``python/gc`` span by its end)."""
        for i in range(self.dropped, self.opened):
            rec = self._ring[i % self.capacity]
            if rec is not None:
                yield i, rec

    def __len__(self) -> int:
        return sum(1 for _ in self.records())


def _drop_hook(hook) -> None:
    try:
        gc.callbacks.remove(hook)
    except ValueError:
        pass
