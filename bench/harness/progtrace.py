"""The program's own spans and named programs in a profiler trace.

The serving path records ``repro.*`` host spans (``src/repro/obs.py``) and
its jitted programs carry fixed names (``jit_prefill``,
``jit_prefill_scatter``, ``jit_decode_step``, ...) on the ``XLA Modules``
line of each ``/device:TPU:<n>`` plane.  ``read`` keeps both, apart from the
``bench.*`` spans that ``xplane.reduce_events`` labels gaps with, and the
reductions below use them:

- ``apportion``: the device's idle time, each gap split over the innermost
  program span open over each part of it (a gap that runs from a logits
  copy through sampling into the next dispatch counts under all three);
- ``sched_self_ms``, ``prefill_device_ms``, ``decode_device_ms``,
  ``decode_idle_ms``: the per-layer quantities a trace of the program can
  give, each None where the trace holds no ``repro.*`` span or no named
  program, as a trace of a program without them does.

All times are on the profiler's one clock, in nanoseconds, inside the
traced window (``bench.trace_window``, or the whole trace without one).
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from harness import xplane

PREFIX = "repro."
#: spans of one call into a backend (their children are pager, dispatch
#: and fetch)
CALLS = ("repro.backend.prefill", "repro.backend.decode_step",
         "repro.backend.prefill_chunk", "repro.backend.verify_step",
         "repro.backend.tick")
STEP = "repro.sched.step"
OUTSIDE = "host.outside_spans"
#: a module event's name ends in its program's fingerprint
FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class Span:
    name: str
    start: float
    end: float

    @property
    def ns(self) -> float:
        return self.end - self.start


@dataclass
class ProgramTrace:
    window: Tuple[float, float]
    spans: List[Span]                                  # repro.*, by start
    modules: Dict[int, List[Tuple[str, float, float]]]  # chip -> programs
    busy: Dict[int, List[xplane.Interval]]             # chip -> busy union

    def inside(self, name: str) -> List[Span]:
        """The spans called ``name`` that lie wholly inside the window."""
        lo, hi = self.window
        return [s for s in self.spans
                if s.name == name and s.start >= lo and s.end <= hi]


def module_name(event_name: str) -> str:
    """``jit_decode_step(1044...)`` -> ``jit_decode_step``."""
    return FINGERPRINT.sub("", event_name)


def build(spans: List[Span],
          modules: Dict[int, List[Tuple[str, float, float]]],
          ops: Dict[int, List[Tuple[str, float, float]]],
          window: Optional[Tuple[float, float]] = None) -> ProgramTrace:
    """A trace from events: ``spans`` (any host spans; ``repro.*`` ones are
    kept), per-chip module and op events as (name, start_ns, end_ns)."""
    if window is None:
        ends = [(a, b) for evs in list(ops.values()) + list(modules.values())
                for _, a, b in evs] + [(s.start, s.end) for s in spans]
        window = (min(a for a, _ in ends), max(b for _, b in ends)) \
            if ends else (0.0, 0.0)
    lo, hi = window
    busy = {chip: xplane.union(xplane.clip([(a, b) for _, a, b in evs],
                                           lo, hi))
            for chip, evs in ops.items()}
    mine = sorted((s for s in spans if s.name.startswith(PREFIX)),
                  key=lambda s: (s.start, -s.end))
    return ProgramTrace(window=window, spans=mine, modules=modules, busy=busy)


def read(path: str) -> ProgramTrace:
    """Read an ``.xplane.pb`` file: the ``repro.*`` host spans, and each
    chip's ``XLA Modules`` and ``XLA Ops`` events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans: List[Span] = []
    modules: Dict[int, List[Tuple[str, float, float]]] = {}
    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    window = None
    for plane in pd.planes:
        m = xplane.DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Modules":
                modules.setdefault(int(m.group(1)), []).extend(
                    (module_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events)
            elif m and line.name in xplane.OPS_LINES:
                ops.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name == xplane.WINDOW:
                        window = (e.start_ns, end)
                    elif e.name.startswith(PREFIX):
                        spans.append(Span(e.name, e.start_ns, end))
    return build(spans, modules, ops, window)


def _segments(spans: List[Span], lo: float, hi: float):
    """Cut ``[lo, hi]`` where spans open and close; yields (a, b, path) with
    ``path`` the names of the spans open over ``[a, b]``, outermost first
    (spans of one thread nest, so the last one is the innermost)."""
    edges = []
    for i, s in enumerate(spans):
        if s.end > max(lo, s.start) and s.start < hi:
            edges.append((max(s.start, lo), 1, i))
            edges.append((min(s.end, hi), 0, i))
    edges.sort()
    open_: List[int] = []
    t = lo
    for x, opening, i in edges:
        if x > t:
            yield t, x, tuple(spans[j].name for j in open_)
            t = x
        if opening:
            open_.append(i)
        else:
            open_.remove(i)
    if hi > t:
        yield t, hi, tuple(spans[j].name for j in open_)


def apportion(trace: ProgramTrace, by_path: bool = False
              ) -> Dict[str, float]:
    """Idle seconds of the device (mean over chips) under the innermost
    program span over each part of each gap, or under the chain of open
    spans (``outer/.../inner``) with ``by_path``; ``host.outside_spans``
    where no program span was open."""
    lo, hi = trace.window
    segs = list(_segments(trace.spans, lo, hi))   # [lo, hi] in order
    out: Dict[str, float] = {}
    for busy in trace.busy.values():
        i = 0
        for a, b in xplane.gaps(busy, lo, hi):
            while segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                x, y, path = segs[j]
                key = ("/".join(path) if by_path else path[-1]) \
                    if path else OUTSIDE
                out[key] = out.get(key, 0.0) + \
                    (min(y, b) - max(x, a)) * 1e-9 / len(trace.busy)
                j += 1
    return out


def _children_ns(spans: List[Span], starts: List[float], parent: Span,
                 names) -> float:
    """Summed spans called ``names`` that lie inside ``parent``."""
    ns = 0.0
    for s in spans[bisect.bisect_left(starts, parent.start):]:
        if s.start >= parent.end:
            break
        if s.name in names and s.end <= parent.end:
            ns += s.ns
    return ns


def sched_self_ms(trace: ProgramTrace) -> Optional[float]:
    """Mean over the traced scheduler steps of the step's span less the
    backend calls it made: the scheduler's own host time, ms."""
    steps = trace.inside(STEP)
    if not steps:
        return None
    starts = [s.start for s in trace.spans]
    return 1e-6 * sum(s.ns - _children_ns(trace.spans, starts, s, CALLS)
                      for s in steps) / len(steps)


def _module_ns(trace: ProgramTrace, names) -> Tuple[float, int]:
    """Device nanoseconds (mean over chips) and executions (per chip) of
    the programs called ``names``, inside the window."""
    lo, hi = trace.window
    if not trace.modules:
        return 0.0, 0
    ns, n = 0.0, 0
    for evs in trace.modules.values():
        for name, a, b in evs:
            if name in names and b > lo and a < hi:
                ns += min(b, hi) - max(a, lo)
                n += 1
    k = len(trace.modules)
    return ns / k, n // k


def prefill_device_ms(trace: ProgramTrace) -> Optional[float]:
    """Device time of the prefill and its scatter into the pool, over the
    traced ``repro.backend.prefill`` calls, ms per call."""
    calls = trace.inside("repro.backend.prefill")
    ns, n = _module_ns(trace, ("jit_prefill", "jit_prefill_scatter"))
    if not calls or not n:
        return None
    return 1e-6 * ns / len(calls)


def decode_device_ms(trace: ProgramTrace) -> Optional[float]:
    """Mean device time of one ``jit_decode_step`` execution, ms."""
    ns, n = _module_ns(trace, ("jit_decode_step",))
    return 1e-6 * ns / n if n else None


def decode_idle_ms(trace: ProgramTrace) -> Optional[float]:
    """Mean over the traced ``repro.backend.decode_step`` calls of the
    call's time in which the device ran no program (mean over chips), ms."""
    calls = trace.inside("repro.backend.decode_step")
    if not calls or not trace.modules:
        return None
    idle = 0.0
    for evs in trace.modules.values():
        busy = xplane.union([(a, b) for _, a, b in evs])
        for c in calls:
            idle += sum(b - a for a, b in xplane.gaps(
                xplane.clip(busy, c.start, c.end), c.start, c.end))
    return 1e-6 * idle / len(trace.modules) / len(calls)
