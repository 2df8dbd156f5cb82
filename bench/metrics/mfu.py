"""mfu (layer: model step, whole): model FLOPs of every token row the
window's steps processed (dense count from the configuration,
bench/counts.py) over the steps' summed wall time at the chip's bf16
peak, in %.  The same count on every rung."""
from bench import window


def read(run):
    return window.mfu(run)
