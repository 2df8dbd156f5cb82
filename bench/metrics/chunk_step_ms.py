"""chunk_step_ms (layer: model step, chunked prefill): median host-clock
time of the window's engine steps that ran a prefill chunk (blocking)."""
from bench import window


def read(run):
    v = window.pct([s.t1 - s.t0 for s in window.steps(run, "prefill")], 50)
    return None if v is None else v * 1e3
