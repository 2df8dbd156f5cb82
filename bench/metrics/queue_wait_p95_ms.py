"""queue_wait_p95_ms (layer: scheduler): 95th percentile of the queue
waits the engine recorded for admissions inside the window
(EngineStats.queue_wait_s, a program counter)."""
from bench import window


def read(run):
    v = window.pct(run.queue_waits, 95)
    return None if v is None else v * 1e3
