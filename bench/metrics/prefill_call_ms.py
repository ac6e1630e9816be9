"""Backend: mean host span of ``backend.prefill`` inside the window (the
call ends in the host copy of the logits), milliseconds."""
from harness.stats import mean


def read(run):
    spans = [1e3 * (c.t1 - c.t0) for c in run.window_calls("prefill")]
    return mean(spans)
