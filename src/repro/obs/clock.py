"""One monotonic clock for the whole serving stack.

Every serving timestamp — engine stats, controller ticks, spans, events,
JSONL snapshots, benchmark replay — routes through :func:`now`, so the
different telemetry streams are mutually orderable.  Historically the
engine used ``time.monotonic()`` while the serve CLI timed runs with
``time.time()``; a span at monotonic ``t`` and a log line at epoch ``t'``
could not be correlated.  :func:`to_wall` maps a monotonic timestamp to
approximate epoch seconds for human-facing output only — never compare
``to_wall`` results across processes or use them for durations.

Injectable clocks: the serving engine reads time through an injected
clock object (``Engine(..., clock=...)``), defaulting to the shared
:data:`SYSTEM_CLOCK` singleton.  That indirection is what makes the
flight recorder (``repro.obs.flight``) possible — a recording run wraps
the clock to log every observation, and a replay run substitutes a
:class:`ReplayClock` that feeds the recorded timestamps back verbatim,
so every controller input (inter-token gaps, deadline sweeps, EWMA
updates) is bit-identical to the recorded incident."""
from __future__ import annotations

import time
from typing import Optional, Sequence

# captured once at import: the (approximate, NTP-drift-affected) offset
# between the monotonic clock and the wall clock
_WALL_OFFSET = time.time() - time.monotonic()


def now() -> float:
    """Monotonic seconds — THE serving timestamp source."""
    return time.monotonic()


def now_ns() -> int:
    """:func:`now` in integer nanoseconds (the same monotonic clock): the
    span store's stamp, read out of band of the engine's injected
    clock so that a flight recording's clock stream never sees it."""
    return time.monotonic_ns()


def to_wall(t_mono: float) -> float:
    """Approximate wall-clock epoch seconds for a :func:`now` timestamp
    (human-facing logs only; durations must subtract monotonic stamps)."""
    return t_mono + _WALL_OFFSET


class SystemClock:
    """The live clock: every ``now(site)`` is a fresh monotonic read.
    ``site`` is a call-site tag (e.g. ``"decode.t1"``) that the flight
    recorder logs next to each observation so a replay divergence names
    the exact consuming site; the live clock ignores it."""

    __slots__ = ()

    def now(self, site: str = "") -> float:
        return time.monotonic()


# the shared default — engines constructed without an explicit clock use
# this exact object, so the clock-off path is `is`-identity testable
# (same standard as NULL_TELEMETRY / NULL_CONTEXT)
SYSTEM_CLOCK = SystemClock()


class ReplayDivergence(RuntimeError):
    """Replay consumed the recording differently than the live run:
    the engine asked for a clock read where the recording holds a
    different record kind (or no record at all), or the consuming call
    site changed.  ``detail`` is the structured first-divergence report
    (record index, expected vs got) the replay CLI prints."""

    def __init__(self, message: str, detail: Optional[dict] = None):
        super().__init__(message)
        self.detail = detail or {}


class ReplayClock:
    """Feeds recorded timestamps back to the engine, positionally.

    Holds the recording's ordered *input* records (clock reads and
    request submissions, as loaded by ``repro.obs.flight``) and a shared
    cursor: the replay driver advances the cursor over ``submit``
    records (re-issuing each submission), and every engine clock read
    consumes the ``clock`` record at the cursor.  Because the engine is
    deterministic given its submissions and clock observations, feeding
    both back in recorded order reproduces every decision bit-exactly.

    Any mismatch — the engine reads the clock where the recording has a
    submission, reads past the end, or reads from a different call site
    than the recorded one — raises :class:`ReplayDivergence` with a
    structured detail dict instead of silently desynchronizing."""

    def __init__(self, inputs: Sequence[dict]):
        self.inputs = list(inputs)
        self.cursor = 0

    @property
    def exhausted(self) -> bool:
        return self.cursor >= len(self.inputs)

    def peek(self) -> Optional[dict]:
        if self.exhausted:
            return None
        return self.inputs[self.cursor]

    def now(self, site: str = "") -> float:
        rec = self.peek()
        if rec is None:
            raise ReplayDivergence(
                f"replay clock exhausted: the engine read the clock at "
                f"site {site!r} but all {len(self.inputs)} recorded "
                f"inputs are already consumed",
                detail={"record": self.cursor, "expected": None,
                        "got": {"k": "clock", "s": site}})
        if rec.get("k") != "clock":
            raise ReplayDivergence(
                f"replay desynchronized at record {self.cursor}: the "
                f"engine read the clock at site {site!r} but the "
                f"recording holds a {rec.get('k')!r} record there",
                detail={"record": self.cursor, "expected": rec,
                        "got": {"k": "clock", "s": site}})
        want = rec.get("s", "")
        if want and site and want != site:
            raise ReplayDivergence(
                f"replay desynchronized at record {self.cursor}: clock "
                f"read from site {site!r} but the recording's read came "
                f"from {want!r}",
                detail={"record": self.cursor, "expected": rec,
                        "got": {"k": "clock", "s": site}})
        self.cursor += 1
        return float(rec["t"])
