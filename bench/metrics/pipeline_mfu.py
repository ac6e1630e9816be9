"""Model step: model FLOPs of the tokens fed through the pipeline in the
window's ticks over their summed host spans times the stage chips' bf16
peak, %."""
from harness.stats import share


def read(run):
    calls = run.window_calls("tick")
    busy = sum(c.t1 - c.t0 for c in calls)
    return share(sum(c.flops for c in calls),
                 busy * run.peaks["bf16_flops"] * run.chips_used)
