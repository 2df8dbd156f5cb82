"""JAX profiler integration: dispatch annotations + capture windows.

Two layers, both opt-in:

* :func:`annotation` — a ``jax.profiler.TraceAnnotation`` labelling the
  host-side dispatch of one executable (decode, chunk, verify, prefix
  extract/write) so engine phases show up as named slices in a captured
  profile.  When telemetry is off the engine gets the shared
  :data:`NULL_CONTEXT` instead — a reusable, reentrant
  ``contextlib.nullcontext`` (no allocation on the hot path).
  Annotations wrap the *dispatch*, never the traced function, so they
  cannot perturb jit cache keys — ``decode_retraces_after_warmup == 0``
  holds with annotations enabled (tested).

* :class:`ProfilerSession` — an explicit capture window around
  ``jax.profiler.start_trace``/``stop_trace`` writing a TensorBoard-
  loadable profile to a directory.  A capture that was asked for and
  fails raises: a run that claims a trace it does not have would be
  read as one that has it.
"""
from __future__ import annotations

import contextlib

NULL_CONTEXT = contextlib.nullcontext()


def annotation(name: str):
    """A profiler trace annotation context for one dispatch."""
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


class ProfilerSession:
    """Opt-in profiler capture window writing to ``out_dir``.

    ``start()``/``stop()`` are idempotent; a profiler-backend error
    propagates."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.active = False

    def start(self) -> None:
        if self.active:
            return
        import jax.profiler
        jax.profiler.start_trace(self.out_dir)
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        import jax.profiler
        jax.profiler.stop_trace()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
