"""Device: 1 - busy union / traced window, from the profiler's trace, %."""
from harness.stats import share


def read(run):
    if run.trace is None:
        return None
    return share(run.trace.window_s - run.trace.mean_busy_s,
                 run.trace.window_s)
