"""``repro.obs`` — unified, zero-overhead-when-disabled engine telemetry.

The surfaces behind one :class:`Telemetry` facade threaded through the
serving stack (``Engine(..., telemetry=...)``):

* **metrics** (:mod:`repro.obs.metrics`) — Counter/Gauge/Histogram with
  fixed log-spaced buckets, Prometheus text exposition adapted from the
  engine's live :class:`~repro.serving.metrics.EngineStats`, an optional
  stdlib ``/metrics`` endpoint, and the shared exposition validator;
* **tracing** (:mod:`repro.obs.trace`) — per-request span timelines in
  Chrome trace-event JSON, loadable in Perfetto;
* **events** (:mod:`repro.obs.events`) — structured ring-buffered event
  log (rung switches with reasons, gamma changes, prefix evictions, KV
  rollbacks, compile/retrace records) with an optional JSONL sink;
* **profiler** (:mod:`repro.obs.profiler`) — JAX dispatch annotations
  and an opt-in ``jax.profiler`` capture window;
* **spans** (:mod:`repro.obs.spans`) — the program's own spans at every
  boundary of an engine step (and ``python/gc`` collections) in a
  bounded store on the monotonic clock, each also a dispatch
  annotation, so a profiler capture's idle gaps can be put down to
  what the host was doing;
* **quality** (:mod:`repro.obs.quality`) — live sparsity-quality probes:
  shadow dense probes, online Eq. 6 reconstruction error vs calibration
  baselines, saliency-drift detection.

The default engine configuration uses :data:`NULL_TELEMETRY`: every
surface is ``None``, every hot-path emit site is an ``is not None``
check, and :meth:`Telemetry.annotate` returns a shared reusable null
context — the disabled path reads no clock, stores nothing and hooks
nothing into the garbage collector.

Clock discipline: all serving timestamps come from :func:`now`
(monotonic; :mod:`repro.obs.clock`), so spans, events, stats, and
snapshots are mutually orderable; :func:`to_wall` converts for
human-facing output only.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.obs.clock import (SYSTEM_CLOCK, ReplayClock, ReplayDivergence,
                             SystemClock, now, now_ns, to_wall)
from repro.obs.events import EventLog
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               engine_exposition, engine_registry,
                               log_buckets, parse_exposition, serve_metrics,
                               validate_exposition)
from repro.obs.profiler import NULL_CONTEXT, ProfilerSession, annotation
from repro.obs.quality import QualityConfig, QualityMonitor
from repro.obs.spans import SpanStore
from repro.obs.trace import SpanTracer, validate_chrome_trace


@dataclasses.dataclass
class Telemetry:
    """Per-engine telemetry bundle.  Any surface may be ``None`` (off);
    the all-``None`` default is :data:`NULL_TELEMETRY` and costs nothing
    on the hot path.

    ``annotate_dispatch`` arms the span store (:attr:`spans`) with a
    ``jax.profiler.TraceAnnotation`` per span; a ``tracer`` alone arms
    the store without annotations, for the tracer's engine track.
    ``profiler`` is an opt-in capture-window session the caller
    starts/stops around the region it wants profiled."""

    tracer: Optional[SpanTracer] = None
    events: Optional[EventLog] = None
    annotate_dispatch: bool = False
    profiler: Optional[ProfilerSession] = None
    # when set (and a tracer is armed), close() exports the Chrome trace
    # JSON here — so Engine.close() flushes *every* sink, even when the
    # driving loop raised
    trace_sink: Optional[str] = None
    # sparsity-quality probes (repro.obs.quality): shadow dense probes,
    # online reconstruction error, saliency drift, roofline counters.
    # Armed by Engine.warmup(); None (the default) keeps the engine's
    # quality path to a single `is not None` check per decode step.
    quality: Optional[QualityMonitor] = None
    # flight recorder (repro.obs.flight): deterministic capture of the
    # engine's nondeterministic inputs (submissions + clock reads) and
    # resulting decisions for bit-identical incident replay.  The engine
    # attaches it at construction (wrapping its injected clock); None
    # keeps every emit site to an `is not None` check.
    flight: Optional[FlightRecorder] = None
    # the program's spans (repro.obs.spans): made here when annotations
    # or a tracer ask for them, None otherwise
    spans: Optional[SpanStore] = dataclasses.field(default=None,
                                                   init=False)

    def __post_init__(self):
        if self.annotate_dispatch or self.tracer is not None:
            self.spans = SpanStore(annotate=self.annotate_dispatch)
            self.spans.watch_gc()
        if self.tracer is not None:
            self.tracer.bind(self.spans)

    @property
    def enabled(self) -> bool:
        return (self.tracer is not None or self.events is not None
                or self.annotate_dispatch or self.profiler is not None
                or self.quality is not None or self.flight is not None)

    def annotate(self, name: str, request_id: Optional[int] = None,
                 **args):
        """Context manager for one span of the program: a stored span
        (and a profiler TraceAnnotation carrying ``args``) when armed,
        the shared null context otherwise."""
        spans = self.spans
        if spans is None:
            return NULL_CONTEXT
        return spans.span(name, request_id, args)

    @classmethod
    def full(cls, events_sink=None, profile_dir: Optional[str] = None,
             event_capacity: int = 4096,
             quality: Optional[QualityConfig] = None) -> "Telemetry":
        """Everything on: tracer + event log (+ optional JSONL sink) +
        dispatch annotations (+ a capture session when ``profile_dir``
        is given, left for the caller to start; + quality probes when a
        :class:`QualityConfig` is given)."""
        return cls(
            tracer=SpanTracer(),
            events=EventLog(capacity=event_capacity, sink=events_sink),
            annotate_dispatch=True,
            profiler=ProfilerSession(profile_dir) if profile_dir else None,
            quality=QualityMonitor(quality) if quality is not None else None)

    def close(self) -> None:
        """Flush and close every armed sink.  Idempotent: profiler stop,
        event-log close and trace re-export all tolerate repeat calls.
        The profiler stops last, so a failed capture (which raises)
        still leaves every other sink flushed."""
        if self.tracer is not None and self.trace_sink is not None:
            self.tracer.export(self.trace_sink)
        if self.spans is not None:
            self.spans.unwatch_gc()
        if self.events is not None:
            self.events.close()
        if self.flight is not None:
            self.flight.close()
        if self.profiler is not None:
            self.profiler.stop()


NULL_TELEMETRY = Telemetry()

__all__ = [
    "Telemetry", "NULL_TELEMETRY", "now", "now_ns", "to_wall",
    "SystemClock", "SYSTEM_CLOCK", "ReplayClock", "ReplayDivergence",
    "SpanTracer", "validate_chrome_trace",
    "EventLog", "FlightRecorder",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "log_buckets",
    "engine_registry", "engine_exposition", "parse_exposition",
    "validate_exposition", "serve_metrics",
    "ProfilerSession", "annotation", "NULL_CONTEXT",
    "QualityConfig", "QualityMonitor", "SpanStore",
]
