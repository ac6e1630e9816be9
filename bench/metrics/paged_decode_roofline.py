"""Pallas paged decode kernel: the least time the chip could take for the
traced decode steps' live context (every key and value read once per layer,
at the slots' positions) over the kernel's device time in the trace, %."""
from harness.roofline import least_time
from harness.stats import share

#: the kernel's custom call in the device trace
KERNEL = r"^paged_decode_attention"


def read(run, kind="decode"):
    if run.trace is None:
        return None
    spent = run.trace.kernel_s(KERNEL)
    if spent <= 0:
        return None
    calls = run.traced_calls(kind)
    need = sum(least_time(c.kernel[0], c.kernel[1], run.peaks) for c in calls)
    return share(need, spent)
