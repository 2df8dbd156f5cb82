"""idle_gc_share (layer: Python runtime): the device's idle time, in %
of the traced window, while Python's garbage collector ran (the
program's ``python/gc`` spans on the trace's clock, bench/spans.py),
wherever in the step it fell."""
from bench import spans


def read(run):
    return spans.share(run, "gc")
