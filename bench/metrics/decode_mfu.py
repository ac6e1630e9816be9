"""Model step: model FLOPs of the live tokens of the window's decode steps
(2 x layer and head parameters per token, plus attention over each slot's
context) over their summed host spans times the chip's bf16 peak, %."""
from harness.stats import share


def read(run):
    calls = run.window_calls("decode")
    busy = sum(c.t1 - c.t0 for c in calls)
    return share(sum(c.flops for c in calls),
                 busy * run.peaks["bf16_flops"])
