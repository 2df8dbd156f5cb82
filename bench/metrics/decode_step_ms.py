"""decode_step_ms (layer: model step, decode): median host-clock time of
the window's engine steps that ran the decode program, which blocks on
its argmax, so the time is bound by the device."""
from bench import window


def read(run):
    v = window.pct([s.t1 - s.t0 for s in window.steps(run, "decode")], 50)
    return None if v is None else v * 1e3
