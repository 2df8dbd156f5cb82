"""mfu.decode (layer: model step, decode): model FLOPs of every token
row the window's decode steps processed (dense count from the
configuration, bench/counts.py) over those steps' summed wall time at
the chip's bf16 peak, in %.  The whole decode step's share of the peak,
beside the sparse kernel's roofline: it bounds what a kernel change can
claim on ``itl_p95_ms``."""
from bench import window


def read(run):
    return window.mfu(run, "decode")
