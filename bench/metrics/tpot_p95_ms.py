"""95th percentile over requests of (finish - first token) / (tokens - 1),
in milliseconds."""
from harness.stats import percentile


def read(run):
    per = [1e3 * (r.finish - r.first) / (len(r.tokens) - 1)
           for r in run.reqs if r.finish is not None and len(r.tokens) > 1]
    return percentile(per, 95)
