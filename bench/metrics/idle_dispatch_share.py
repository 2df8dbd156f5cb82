"""idle_dispatch_share (layer: dispatch): the device's idle time, in %
of the traced window, while the host was dispatching a step or reading
it back: a phase's ``launch`` (the jitted call with its argument
conversions) and ``readback`` (argmax to numpy, or the block), the
chunk's ``first_token``, and ``repro/decode`` / ``repro/prefill_chunk``
outside them (the program's spans on the trace's clock,
bench/spans.py)."""
from bench import spans


def read(run):
    return spans.share(run, "dispatch")
