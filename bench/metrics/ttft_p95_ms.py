"""ttft_p95_ms: 95th percentile, over the requests due inside the
window, of due time to first token (host clock).  A request with no
first token counts as failed, not here."""
from bench import window


def read(run):
    v = window.pct([r.first - r.due for r in window.due_in_window(run)
                    if r.first is not None], 95)
    return None if v is None else v * 1e3
