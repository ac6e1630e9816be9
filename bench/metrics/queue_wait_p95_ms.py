"""Scheduler: 95th percentile of admission (the program's
``RequestTiming.admitted_s``) minus scheduled arrival, in milliseconds."""
from harness.stats import percentile


def read(run):
    return percentile([1e3 * (r.admitted - r.sched)
                       for r in run.window_reqs() if r.admitted is not None],
                      95)
