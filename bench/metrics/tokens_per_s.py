"""Output tokens completed in the window over the window's seconds; work that
straddles the window's end counts in proportion to its part inside."""
from bench.lib.stats import rate_in_window


def read(run):
    w = run.window
    if not w.token_events:
        return None
    return rate_in_window(w.token_events, w.start, w.deadline)
