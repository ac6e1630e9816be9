"""Pallas paged decode kernel inside the pipeline's tick: the least time
for the traced ticks' live context over the kernel's device time summed
over the stage chips, %."""
from harness.roofline import least_time
from harness.stats import share

KERNEL = r"^paged_decode_attention"


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.kernel_s(KERNEL)
    if spent <= 0:
        return None
    need = sum(least_time(c.kernel[0], c.kernel[1], run.peaks)
               for c in run.traced_calls("tick"))
    return share(need, spent)
