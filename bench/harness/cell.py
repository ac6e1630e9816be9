"""One run of one cell: set-up, the measured window, the metrics, and the
check that decides ``correct``."""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

from harness import check, gen, serve, xplane
from harness.roofline import ModelCost
from harness.spec import Cell

#: backend calls a ``--trace 1`` run traces from the window's opening
#: (a few seconds of steady work; a longer trace takes minutes to write)
TRACE_CALLS = 300
#: seconds after the window closes that in-flight requests may take
DRAIN_S = 60.0


@dataclass
class Run:
    """What a metric reader may read."""

    cell: Cell
    cost: ModelCost
    peaks: dict
    setup_s: float
    t0: float                           # window open (perf_counter)
    t1: float                           # window closed
    reqs: List[serve.Req]
    calls: List[serve.Call]
    drain_end: float
    chips_used: int
    stages: List[int] = field(default_factory=list)
    compiles: int = 0                   # compilations inside the window
    peak_bytes: int = 0                 # fullest chip's peak, after the window
    trace: Optional[xplane.Trace] = None
    trace_span: tuple = (0.0, 0.0)      # traced part of the window (host)
    gaps: Optional[object] = None       # the check's per-token logit gaps

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def end(self) -> float:
        """Where the host readings stop: the window's close, or in a traced
        run the trace's end (writing the trace stalls the host after it)."""
        return self.trace_span[1] or self.t1

    def window_calls(self, kind: str) -> List[serve.Call]:
        return [c for c in self.calls if c.kind == kind
                and c.t0 >= self.t0 and c.t1 <= self.end]

    def window_reqs(self) -> List[serve.Req]:
        """Requests due before ``end`` (all of them in an untraced run)."""
        return [r for r in self.reqs if r.sched < self.end]

    def traced_calls(self, kind: str) -> List[serve.Call]:
        lo, hi = self.trace_span
        return [c for c in self.calls if c.kind == kind
                and c.t0 >= lo and c.t1 <= hi]

    def ttfts(self) -> List[float]:
        """First token minus scheduled arrival, every request of the
        window (one that never got a token counts until the drain ended)."""
        return [(r.first if r.first is not None else self.drain_end) - r.sched
                for r in self.reqs]


class CompileCounter:
    """Times of every XLA compilation (or load from the persistent cache)
    while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.at: List[float] = []

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.at.append(time.perf_counter())

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.at)


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class _Tracer:
    """Starts the profiler as the window opens and stops it once the
    backend has taken ``calls`` calls, or when the window closes."""

    def __init__(self, spans: serve.Spans, calls: list, limit: int,
                 out: Path):
        self.spans, self.calls, self.limit, self.out = spans, calls, limit, out
        self.span = [0.0, 0.0]
        self._window = contextlib.ExitStack()
        self._first = 0

    def __call__(self, elapsed: float) -> None:
        import jax
        if not self.span[0]:
            jax.profiler.start_trace(str(self.out), profiler_options=_options())
            self._window.enter_context(
                jax.profiler.TraceAnnotation(xplane.WINDOW))
            self.spans.annotate = True
            self.span[0] = time.perf_counter()
            self._first = len(self.calls)
        elif not self.span[1] and len(self.calls) - self._first >= self.limit:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.span[0] and not self.span[1]:
            self.span[1] = time.perf_counter()
            self.spans.annotate = False
            self._window.close()
            jax.profiler.stop_trace()


def _serve(cell: Cell, seed: int, seconds: float, devices, t_start: float,
           log, trace_dir: Optional[Path], trace_calls: int,
           drain_s: float) -> Run:
    """Set-up and the window; returns what the readers read, with the
    program's state already dropped."""
    from harness import model as M
    from repro.serving import LLM

    c, traffic = cell.config, cell.traffic
    dep = c["deployment"]
    cfg = M.program_config(c)
    cost = ModelCost.from_config(c)
    with CompileCounter() as counter:
        backend, stages = serve.build(c, cfg, seed, devices)
        used = devices[:len(stages)] if dep["kind"] == "pipeline" \
            else devices[:1]
        spans = serve.Spans()
        inst = serve.Instrument(backend, cost, dep["kind"], spans)
        widths = serve.buckets(c, traffic, backend)
        serve.warm_up(backend, dep["kind"], widths)
        inst.calls.clear()
        llm = LLM(backend, seed=seed, min_bucket=int(dep.get("min_bucket", 1)))
        items = gen.make_items(traffic, seed, seconds, c["vocab_size"])
        win = serve.Window(llm, spans, traffic, items, seconds)
        tracer = None
        if trace_dir is not None:
            tracer = win.on_tick = _Tracer(spans, inst.calls, trace_calls,
                                           trace_dir)
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s!r} s; deployment {dep['kind']} stages "
            f"{stages}; prefill widths {widths}; {len(items)} requests")
        win.run(drain_s=drain_s)
        if tracer is not None:
            tracer.stop()
        drain_end = time.perf_counter()
        compiles = counter.between(win.t0, win.t1)
    peak = peak_bytes(used)
    reqs = list(win.reqs.values())
    log(f"window {win.t1 - win.t0!r} s, {win.steps} scheduler steps, "
        f"{len(reqs)} requests, drained {win.drained}, {compiles} "
        f"compilations inside the window, peak {peak} bytes")
    late = sorted(r.submitted - r.sched for r in reqs)
    if late:
        log(f"generator lateness: median {late[len(late) // 2]!r} s, "
            f"max {late[-1]!r} s over {len(late)} requests")
    rec = Run(cell=cell, cost=cost, peaks={}, setup_s=setup_s, t0=win.t0,
              t1=win.t1, reqs=reqs, calls=inst.calls, drain_end=drain_end,
              chips_used=len(used), stages=stages, compiles=compiles,
              peak_bytes=peak)
    if tracer is not None:
        rec.trace_span = tuple(tracer.span)
    del llm, win, inst, backend
    gc.collect()
    return rec


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        peaks: dict, t_start: float, keep_trace: Optional[str] = None,
        log=print, probe=None, trace_calls: int = TRACE_CALLS,
        drain_s: float = DRAIN_S) -> dict:
    """One run; ``probe(config, seed, sampled_requests, run)``, where
    given, is called after the check and its answer kept under "probe"."""
    import jax

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        rec = _serve(cell, seed, seconds, devices, t_start, log,
                     Path(tmp) if trace else None, trace_calls, drain_s)
        rec.peaks = peaks
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": rec.peak_bytes}
        if trace:
            files = sorted(Path(tmp).rglob("*.xplane.pb"))
            if keep_trace:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(files[-1], Path(keep_trace) /
                            f"{cell.name}.{seed}.xplane.pb")
            rec.trace = xplane.read(str(files[-1]))
            device["busy_s"] = rec.trace.mean_busy_s
            device["window_s"] = rec.trace.window_s

    metrics = {}
    for name, m in cell.metrics(trace).items():
        value = cell.reader(name)(rec)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": m["unit"]}

    # correctness: the reference, once the program's state is gone
    c, lim, reqs = cell.config, cell.checks, rec.reqs
    with jax.default_device(devices[0]):
        picked = check.sample(reqs, seed, lim["sample_tokens"],
                              lim["sample_requests"])
        if picked:
            rec.gaps = check.served_gaps(c, seed, picked)
    log(f"reference compared {sum(len(r.tokens) for r in picked)} served "
        f"tokens of {len(picked)} requests")
    gaps = check.stats(rec.gaps) if picked else {}
    finished = sum(r.finish is not None for r in reqs)
    short = sum(r.finish is not None and len(r.tokens) != r.max_tokens
                for r in reqs)
    checks = {k: {"value": gaps.get(k), "limit": lim[k]}
              for k in ("logit_gap", "mean_gap")}
    checks["wrong_length"] = {"value": short, "limit": 0}
    checks["unfinished"] = {"value": len(reqs) - finished, "limit": 0}
    correct = all(v["value"] is not None and v["value"] <= v["limit"]
                  for v in checks.values())
    result = {"correct": correct, "attempted": len(reqs),
              "failed": len(reqs) - finished + short, "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = rec.trace.breakdown()
    result["deployment"] = {"stages": rec.stages,
                            "compiles_in_window": rec.compiles}
    if probe is not None:
        with jax.default_device(devices[0]):
            result["probe"] = probe(c, seed, picked, rec)
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    return result


def _options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    return o
