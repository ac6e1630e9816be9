"""Output tokens produced inside the window over the window's length."""
from harness.stats import rate


def read(run):
    return rate(sum(r.in_window for r in run.reqs), run.seconds)
