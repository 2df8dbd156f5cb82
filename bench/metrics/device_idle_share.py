"""device_idle_share (layer: device): 1 - the union of device-op
intervals over the traced part of the window, in % (profiler trace)."""
from bench import trace


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    v = trace.idle_share(run.trace, run.trace_lo, run.trace_hi)
    return None if v is None else 100.0 * v
