"""Model step: model FLOPs of the true prompt tokens of the window's
prefills (no pad rows or columns) over their summed host spans times the
chip's bf16 peak, %."""
from harness.stats import share


def read(run):
    calls = run.window_calls("prefill")
    busy = sum(c.t1 - c.t0 for c in calls)
    return share(sum(c.flops for c in calls),
                 busy * run.peaks["bf16_flops"])
