"""Reduce a profiler trace (``.xplane.pb``) to device busy time, operation
times and idle gaps, on the profiler's one clock.

The traced window is the host annotation ``bench.trace_window``.  Device
planes are ``/device:TPU:<n>``; their operations are the events of the
``XLA Ops`` line, named by their HLO instruction (a Pallas kernel by its
custom call, e.g. ``paged_decode_attention.7``); a loop that holds other
operations counts as busy but not as an operation of its own.  Busy time is the union of those operations' intervals
inside the window; every gap between them is put down to the innermost
``bench.*`` host span that was open at the gap's middle (what the host was
doing while the device waited).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.trace_window"
OPS_LINES = ("XLA Ops",)
DEVICE = re.compile(r"^/device:TPU:(\d+)")
#: operations that hold others (a scanned layer loop): busy, not an op
CONTAINERS = re.compile(r"^(while|conditional|call)\b")

Interval = Tuple[float, float]


@dataclass
class Trace:
    window_s: float
    busy_s: Dict[int, float]                     # chip -> seconds busy
    op_s: Dict[str, float]                       # op name -> seconds, all chips
    idle_by_host: Dict[str, float] = field(default_factory=dict)
    detail: Dict[str, str] = field(default_factory=dict)   # op -> its HLO

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def kernel_s(self, pattern: str) -> float:
        """Seconds of every operation whose name or HLO text matches
        ``pattern``, summed over chips."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items()
                   if rx.search(name) or rx.search(self.detail.get(name, "")))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost host span open at ``t`` (the shortest that holds it)."""
    best: Optional[Tuple[float, str]] = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[0]):
            best = (b - a, name)
    return best[1] if best else "host.outside_spans"


def reduce_events(device_ops: Dict[int, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]]) -> Trace:
    """The reduction proper, on (name, start_ns, end_ns) events."""
    win = [(a, b) for name, a, b in host_spans if name == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    lo, hi = win[0]
    inner = [s for s in host_spans if s[0] != WINDOW]
    busy_s, op_s, idle = {}, {}, {}
    for chip, ops in device_ops.items():
        ivs = clip([(a, b) for _, a, b in ops], lo, hi)
        busy = union(ivs)
        busy_s[chip] = sum(b - a for a, b in busy) * 1e-9
        for name, a, b in ops:
            c = min(b, hi) - max(a, lo)
            if c > 0 and not CONTAINERS.match(name):
                op_s[name] = op_s.get(name, 0.0) + c * 1e-9
        for a, b in gaps(busy, lo, hi):
            lab = label_at(inner, 0.5 * (a + b))
            idle[lab] = idle.get(lab, 0.0) + (b - a) * 1e-9 / len(device_ops)
    return Trace(window_s=(hi - lo) * 1e-9, busy_s=busy_s, op_s=op_s,
                 idle_by_host=idle)


def short(op: str) -> str:
    """An operation's instruction name (``%fusion.86 = bf16[...] ...`` ->
    ``fusion.86``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def read(path: str) -> Trace:
    """Read an ``.xplane.pb`` file and reduce it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    detail: Dict[str, str] = {}
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in OPS_LINES:
                ops = device_ops.setdefault(int(m.group(1)), [])
                for e in line.events:
                    name = short(e.name)
                    ops.append((name, e.start_ns, e.start_ns + e.duration_ns))
                    if name not in detail:
                        detail[name] = e.name
            elif not m and plane.name.startswith("/host"):
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    if not device_ops:
        raise ValueError(f"{path}: no device operations in the trace")
    trace = reduce_events(device_ops, host)
    trace.detail = detail
    return trace
