"""Backend: mean host span of ``backend.decode_step`` inside the window,
milliseconds."""
from harness.stats import mean


def read(run):
    return mean([1e3 * (c.t1 - c.t0) for c in run.window_calls("decode")])
