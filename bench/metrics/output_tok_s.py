"""output_tok_s: every output token that arrived at the client inside
the window, over the window's seconds (host clock)."""
from bench import window


def read(run):
    return len(window.token_times(run)) / run.seconds
