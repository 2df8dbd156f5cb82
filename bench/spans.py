"""Put every idle nanosecond of a traced window down to one cause, from
the program's own spans (``repro.obs.spans``, the span store on the
engine's telemetry).

The store stamps its spans on the monotonic clock, the trace on the
profiler's.  The step spans ``repro/decode`` and ``repro/prefill_chunk``
are both a stored span and a host annotation in the trace, entered in
one ``with``: paired in order, the median of note start less record
start is the offset between the clocks.  The store's clock is read just
before the annotation starts, so a pair whose host stood still between
the two (a collection, a preempted thread) reads late by that stall
alone, and leaves the median as it is.  The pairing stands where the
``LINE_UP`` share of its pairs nearest the median lie within
``MAX_SPREAD_NS`` of it; else the two do not line up, and nothing is
read.

Each idle gap of device 0 is then split at span boundaries.  An idle
nanosecond goes to ``python/gc`` wherever a collection covers it, else
to the innermost span that covers it, else to the client (outside every
``repro/step``: the harness between steps).  The innermost span's name
decides its group (:func:`group`), so the four groups sum to the idle
time of the window.  New readers only: this file reads what
``bench/trace.py`` loads and changes nothing there.
"""
from __future__ import annotations

import bisect
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace as TR

ALIGN = ("repro/decode", "repro/prefill_chunk")
MAX_SPREAD_NS = 100_000.0
LINE_UP = 0.9           # share of the pairs that MAX_SPREAD_NS has to hold
GC = "python/gc"
GROUPS = ("engine_host", "dispatch", "gc", "client")
# a step span's own time (outside its launch and readback) is dispatch
# too: the argument conversions and the call's return
DISPATCH = frozenset({"repro/decode", "repro/prefill_chunk",
                      "repro/prefill_chunk/first_token"})


def group(name: Optional[str]) -> str:
    """The group of the innermost span over an idle nanosecond."""
    if name is None:
        return "client"
    if name == GC:
        return "gc"
    if name in DISPATCH or name.endswith(("/launch", "/readback")):
        return "dispatch"
    # repro/step's own time, admit, */prepare, */emit, repro/prefix_*
    return "engine_host"


def store_records(run) -> Optional[list]:
    """(index, record) of the engine's span store, or None where the
    program has no store (a program from before the store)."""
    eng = getattr(run.client, "eng", None)
    spans = getattr(getattr(eng, "obs", None), "spans", None)
    if spans is None or not hasattr(spans, "records"):
        return None
    return list(spans.records())


def offset(records: Sequence, notes: Sequence[TR.Span]
           ) -> Tuple[Optional[float], float]:
    """(offset, spread) in ns: the median of note start less record
    start over the paired step spans, and the ``LINE_UP`` quantile of
    each pair's distance from it; offset None where no pairing lines up
    within ``MAX_SPREAD_NS``.  A stalled pair or two do not sink it."""
    recs = [r for _, r in records if r.name in ALIGN]
    ns = sorted((n for n in notes if n.name in ALIGN), key=lambda n: n.start)
    if not ns or len(recs) < len(ns):
        return None, float("inf")
    code = {name: i for i, name in enumerate(ALIGN)}
    rc = np.array([code[r.name] for r in recs])
    rs = np.array([r.start_ns for r in recs], np.float64)
    nc = np.array([code[n.name] for n in ns])
    nst = np.array([n.start for n in ns], np.float64)
    k_n = len(ns)
    best, best_spread = None, float("inf")
    for k in np.flatnonzero(rc[:len(recs) - k_n + 1] == nc[0]):
        if not np.array_equal(rc[k:k + k_n], nc):
            continue
        offs = nst - rs[k:k + k_n]
        mid = float(statistics.median(offs.tolist()))
        spread = float(np.quantile(np.abs(offs - mid), LINE_UP))
        if spread < best_spread:
            best, best_spread = mid, spread
    if best is None or best_spread > MAX_SPREAD_NS:
        return None, best_spread
    return best, best_spread


def _innermost(spans: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, name) pieces, each named by the innermost
    span over it (the latest started of those open), where any is."""
    ev = []
    for i, (a, b, _) in enumerate(spans):
        if b > a:
            ev.append((a, 1, -b, i))
            ev.append((b, 0, -a, i))
    ev.sort()
    out, stack, last = [], [], None
    for t, kind, _, i in ev:
        if stack and last is not None and t > last:
            out.append((last, t, spans[stack[-1]][2]))
        if kind == 1:
            stack.append(i)
        else:                   # nested spans: the innermost ends first
            stack.pop()
        last = t
    return out


def _covered(iv: List[Tuple[float, float]], starts: List[float],
             a: float, b: float) -> float:
    """Length of [a, b] covered by the sorted disjoint intervals iv."""
    j = max(0, bisect.bisect_right(starts, a) - 1)
    total = 0.0
    while j < len(iv) and iv[j][0] < b:
        x, y = max(iv[j][0], a), min(iv[j][1], b)
        if y > x:
            total += y - x
        j += 1
    return total


def attribute(tr: TR.Trace, records: Sequence, off: float, lo: float,
              hi: float) -> Tuple[Dict[str, float], List[list]]:
    """Idle ns of device 0 in [lo, hi] by group, and every gap as
    [length ns, {innermost name or "client": ns}] (GC under its name)."""
    moved = [(r.start_ns + off, r.end_ns + off, r.name) for _, r in records
             if r.end_ns + off > lo and r.start_ns + off < hi]
    gc_iv = TR.union((TR.Span(GC, a, b) for a, b, n in moved if n == GC),
                     lo, hi)
    gc_starts = [a for a, _ in gc_iv]
    pieces = _innermost([s for s in moved if s[2] != GC])
    p_starts = [a for a, _, _ in pieces]
    by = {g: 0.0 for g in GROUPS}
    gaps = []
    for a, b in TR.gaps(tr, lo, hi):
        parts: Dict[str, float] = {}

        def put(x, y, name):
            g_ns = _covered(gc_iv, gc_starts, x, y)
            rest = (y - x) - g_ns
            by["gc"] += g_ns
            by[group(name)] += rest
            if g_ns > 0:
                parts[GC] = parts.get(GC, 0.0) + g_ns
            if rest > 0:
                k = name or "client"
                parts[k] = parts.get(k, 0.0) + rest

        t = a
        j = max(0, bisect.bisect_right(p_starts, a) - 1)
        while j < len(pieces) and pieces[j][0] < b:
            x, y, name = pieces[j]
            x, y = max(x, a), min(y, b)
            if y > x:
                if x > t:
                    put(t, x, None)
                put(x, y, name)
                t = y
            j += 1
        if b > t:
            put(t, b, None)
        gaps.append([b - a, parts])
    return by, gaps


def idle_by_group(run) -> Optional[Dict[str, float]]:
    """Each group's idle share of the traced window, in %, or None where
    there is no trace, no span store, or no alignment.  Worked out once
    per run and kept on it."""
    if hasattr(run, "_idle_by_group"):
        return run._idle_by_group
    out = None
    tr = run.trace
    records = store_records(run) if tr is not None and tr.devices else None
    if records:
        lo, hi = run.trace_lo, run.trace_hi
        off, spread = offset(records, tr.notes)
        if off is None:
            print(f"spans: no alignment (pairs spread {spread:.0f} ns)",
                  file=sys.stderr)
        elif hi > lo:
            by, gaps = attribute(tr, records, off, lo, hi)
            out = {g: 100.0 * v / (hi - lo) for g, v in by.items()}
            longest = sorted(gaps, key=lambda g: -g[0])[:5]
            print(f"spans: offset {off:.0f} ns, pairs spread {spread:.0f} "
                  f"ns; idle % by group {out}; longest gaps (ms): "
                  + "; ".join(
                      f"{n * 1e-6:.3f} " + ", ".join(
                          f"{k} {v * 1e-6:.3f}" for k, v in
                          sorted(p.items(), key=lambda kv: -kv[1]))
                      for n, p in longest), file=sys.stderr)
    run._idle_by_group = out
    return out


def share(run, g: str) -> Optional[float]:
    by = idle_by_group(run)
    return None if by is None else by[g]
