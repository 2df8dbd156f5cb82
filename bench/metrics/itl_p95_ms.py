"""itl_p95_ms: 95th percentile of all inter-token gaps whose later
token arrived inside the window, as the client saw them (host clock)."""
from bench import window


def read(run):
    v = window.pct(window.gaps(run), 95)
    return None if v is None else v * 1e3
