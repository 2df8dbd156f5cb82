"""batch_occupancy (layer: scheduler): mean over the window's decode
steps of the rows that got a token over the pool's slots, in %."""
from bench import window


def read(run):
    st = window.steps(run, "decode")
    if not st:
        return None
    return 100.0 * sum(s.rows for s in st) / (len(st) * run.slots)
