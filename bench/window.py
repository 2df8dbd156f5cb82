"""What fell inside a run's window: the helpers the metric readers
share."""
from __future__ import annotations

from typing import List

import numpy as np


def pct(values, q: float):
    """The q-th percentile (linear interpolation), None when empty."""
    v = np.asarray(list(values), np.float64)
    return None if v.size == 0 else float(np.percentile(v, q))


def steps(run, action: str = None) -> List:
    """Steps that started inside the window."""
    return [s for s in run.client.steps
            if run.t_open <= s.t0 < run.t_close
            and (action is None or s.action == action)]


def token_times(run) -> List[float]:
    return [t for r in run.client.reqs for t in r.times
            if run.t_open <= t < run.t_close]


def gaps(run) -> List[float]:
    """Inter-token gaps whose later token arrived inside the window."""
    out = []
    for r in run.client.reqs:
        t = r.times
        out.extend(t[i] - t[i - 1] for i in range(1, len(t))
                   if run.t_open <= t[i] < run.t_close)
    return out


def due_in_window(run) -> List:
    return [r for r in run.client.reqs if run.t_open <= r.due < run.t_close]


def mfu(run, action: str = None):
    """Model FLOPs of every token row the window's steps (of ``action``,
    or all) processed over their summed wall time at the chip's bf16
    peak, in %."""
    from bench import counts
    if run.rehearse:
        return None
    st = steps(run, action)
    busy = sum(s.t1 - s.t0 for s in st)
    if busy <= 0:
        return None
    flops = sum(counts.step_flops(run.shape, s.rows, s.context) for s in st)
    return 100.0 * flops / (busy * counts.peaks(run.device_kind).flops_bf16)
