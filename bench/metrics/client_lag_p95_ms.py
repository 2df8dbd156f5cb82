"""client_lag_p95_ms (layer: client, bench/traffic.py): 95th percentile
of send time minus due time over the requests due inside the window --
how late the open-loop generator ran behind the blocking engine step
(host clock).  Nothing to read in a closed loop."""
from bench import window


def read(run):
    if run.cell.traffic["loop"] != "open":
        return None
    v = window.pct([r.sent - r.due for r in window.due_in_window(run)], 95)
    return None if v is None else v * 1e3
