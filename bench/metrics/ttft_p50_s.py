"""Median over every request of the window of first-token time minus
scheduled arrival (seconds; in a closed loop, arrival is the submission)."""
from harness.stats import percentile


def read(run):
    return percentile(run.ttfts(), 50)
