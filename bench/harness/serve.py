"""Build the deployment through the planner, warm it up, and serve traffic
through ``LLM.submit`` / ``LLM.step`` / ``LLM.poll``.

Spans are taken here, in the benchmark's own code, around the public calls
into each layer: ``LLM.step`` (scheduler), and the backend's ``prefill``,
``decode_step`` and ``free_slot`` (the backend object's methods are
wrapped on that one instance; nothing in the program is changed).  The
wrapper also keeps, from the calls' own arguments, each slot's live
context, so that model FLOPs and the paged kernel's bytes can be counted
from the slots' positions.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from harness import gen
from harness.roofline import ModelCost


@dataclass
class Call:
    """One call into the backend, on the host's clock."""

    kind: str                   # "prefill" | "decode" | "tick" | "free"
    t0: float
    t1: float
    flops: float = 0.0          # model FLOPs of the live work in the call
    kernel: tuple = (0.0, 0.0)  # paged decode kernel (FLOPs, bytes), live
    rows: int = 0               # live rows (prefill) or live slots (decode)


@dataclass
class Req:
    uid: int
    sched: float                # when it was due (perf_counter)
    submitted: float
    plen: int
    max_tokens: int
    prompt: np.ndarray
    first: Optional[float] = None
    finish: Optional[float] = None
    admitted: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    in_window: int = 0          # of its tokens, how many came in the window


class Spans:
    """Host spans: wall-clock records, and while a trace is being taken,
    the same names as profiler annotations (``bench.<name>``)."""

    def __init__(self):
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield


class Instrument:
    """Wraps one backend instance's public calls with spans and keeps the
    live context of every slot."""

    def __init__(self, backend, cost: ModelCost, kind: str, spans: Spans):
        self.b, self.cost, self.kind, self.spans = backend, cost, kind, spans
        self.calls: List[Call] = []
        self.ctx: Dict[int, int] = {}          # slot -> keys in its cache
        self.turn: Dict[int, List[int]] = {}   # pipeline slot -> [plen, rounds]
        self.ticks = 0
        self._prefill, self._decode = backend.prefill, backend.decode_step
        self._free = backend.free_slot
        backend.prefill = self.prefill
        backend.decode_step = self.decode_step
        backend.free_slot = self.free_slot

    def prefill(self, slots, prompts, prompt_lens=None):
        lens = [int(n) for n in (prompt_lens if prompt_lens is not None
                                 else [np.asarray(prompts).shape[1]] * len(slots))]
        t0 = time.perf_counter()
        with self.spans.span("prefill"):
            out = self._prefill(slots, prompts, prompt_lens)
        t1 = time.perf_counter()
        if self.kind == "pipeline":
            for s, n in zip(slots, lens):
                self.turn[s] = [n, 0]
            self.calls.append(Call("prefill", t0, t1, rows=len(slots)))
        else:
            for s, n in zip(slots, lens):
                self.ctx[s] = n
            self.calls.append(Call(
                "prefill", t0, t1, rows=len(slots),
                flops=sum(self.cost.prompt_flops(n) for n in lens)))
        return out

    def decode_step(self, feeds):
        if self.kind == "pipeline":
            return self._tick(feeds)
        t0 = time.perf_counter()
        with self.spans.span("decode_step"):
            out = self._decode(feeds)
        t1 = time.perf_counter()
        live = [s for s in feeds if s in self.ctx]
        for s in live:
            self.ctx[s] += 1
        ctxs = [self.ctx[s] for s in live]
        self.calls.append(Call(
            "decode", t0, t1, rows=len(live),
            flops=sum(self.cost.token_flops(n) for n in ctxs),
            kernel=self.cost.decode_attn(ctxs)))
        return out

    def _tick(self, feeds):
        """One pipeline tick feeds the slot ``tick % n_slots``: a prompt
        token while its prompt lasts, then its last sampled token."""
        slot = self.ticks % self.b.n_slots
        t0 = time.perf_counter()
        with self.spans.span("tick"):
            out = self._decode(feeds)
        t1 = time.perf_counter()
        self.ticks += 1
        st = self.turn.get(slot)
        call = Call("tick", t0, t1)
        if st is not None and (st[1] < st[0] or slot in feeds):
            st[1] += 1
            keys = st[1]                       # positions 0..round attended
            call.rows = 1
            call.flops = self.cost.token_flops(keys)
            call.kernel = self.cost.decode_attn([keys])
        self.calls.append(call)
        return out

    def free_slot(self, slot):
        self.ctx.pop(slot, None)
        self.turn.pop(slot, None)
        with self.spans.span("free_slot"):
            return self._free(slot)


def build(c: dict, cfg, seed: int, devices):
    """Plan the deployment, make the weights from ``seed`` and bring up the
    backend.  Returns (backend, stages)."""
    import jax
    import jax.numpy as jnp

    from harness import model as M
    from harness import weights as W
    from repro.core.devices import tpu_pod_cluster
    from repro.core.planner import plan_deployment
    from repro.core.profile import Workload
    from repro.runtime import from_deployment

    dep = c["deployment"]
    cluster = tpu_pod_cluster(n_chips=dep["chips"])
    workload = Workload(**dep["planner_workload"])
    plan = plan_deployment(cfg, cluster, workload, objective=dep["objective"])
    if not plan.ok:
        raise RuntimeError(f"the planner found no feasible plan: {plan}")
    stages = [s.end - s.start + 1 for s in plan.plan.stages]
    mesh, shardings = None, None
    if dep["kind"] == "pipeline":
        from repro.sharding import make_mesh
        mesh = make_mesh((1, len(stages)), ("data", "model"),
                         devices=devices[:len(stages)])
        shardings = M.shardings_on(cfg, mesh)
    params = W.make(c, seed, shardings)
    W.check_layout(params, M.program_params(cfg)[0])
    with jax.default_device(devices[0]):
        backend = from_deployment(
            plan, cluster, cfg, kind=dep["kind"], params=params,
            workload=workload, mesh=mesh, n_slots=dep.get("n_slots"),
            max_len=dep["max_len"], cache_dtype=jnp.dtype(dep["cache_dtype"]),
            impl=dep["impl"], cache_layout=dep["cache_layout"],
            block_size=dep["block_size"], num_blocks=dep.get("num_blocks"))
    if dep["kind"] == "pipeline":
        stages = list(backend.spec.periods_per_stage)
    return backend, stages


def buckets(c: dict, traffic: dict, backend) -> List[int]:
    """Every prefill width the cell's traffic can reach: the batcher's
    power-of-two buckets from the shortest prompt up to the longest prompt,
    or, where the pool can run dry and preempt, up to the longest prompt
    plus generated tokens that a resumed request re-prefills."""
    info = backend.info
    lo = int(traffic["prompt"]["min"])
    hi = int(traffic["prompt"]["max"])
    if info.total_blocks < info.n_slots * info.max_ctx_blocks:
        hi = gen.longest(traffic)
    out, b = [], 1 << max(lo - 1, 0).bit_length()
    while True:
        out.append(min(b, info.max_len))
        if b >= hi or b >= info.max_len:
            return out
        b *= 2


def warm_up(backend, kind: str, widths: List[int]) -> None:
    """Run every program the window will use once, so that nothing compiles
    inside it: each prefill width with its scatter and a decode step, or
    one request through the pipeline's tick."""
    if kind == "pipeline":
        backend.prefill([0], np.ones((1, 2), np.int32), [2])
        for _ in range(4 * backend.n_slots + 8):
            if backend.decode_step({0: 1}):
                break
        backend.decode_step({0: 1})
        backend.free_slot(0)
        return
    for w in widths:
        backend.prefill([0], np.ones((1, w), np.int32), [w])
        if w < backend.info.max_len:
            backend.decode_step({0: 1})
        backend.free_slot(0)


class Window:
    """Serve a list of requests through ``llm`` for ``seconds`` of wall
    time, then drain what is in flight."""

    def __init__(self, llm, spans: Spans, traffic: dict, items: List[gen.Item],
                 seconds: float):
        self.llm, self.spans, self.traffic = llm, spans, traffic
        self.items, self.seconds = items, seconds
        self.reqs: Dict[int, Req] = {}
        self.t0 = self.t1 = 0.0
        self.drained = True
        self.steps = 0
        self._next = 0
        self.on_tick = None            # hook(now) called between steps

    def _submit(self, item: gen.Item, sched: float) -> None:
        from repro.serving import SamplingParams
        with self.spans.span("submit"):
            uid = self.llm.submit(item.prompt,
                                  SamplingParams(max_tokens=item.max_tokens))
        self.reqs[uid] = Req(uid=uid, sched=sched,
                             submitted=time.perf_counter(),
                             plen=len(item.prompt), max_tokens=item.max_tokens,
                             prompt=item.prompt)

    def _step(self, in_window: bool) -> List[int]:
        with self.spans.span("llm_step"):
            events = self.llm.step()
        self.steps += 1
        now = time.perf_counter()
        finished = []
        for ev in events:
            r = self.reqs[ev.uid]
            if ev.index == 0:
                r.first = now
            r.tokens.append(int(ev.token))
            r.in_window += int(in_window)
            if ev.finished:
                r.finish = now
                finished.append(ev.uid)
        return finished

    def run(self, drain_s: float = 60.0) -> None:
        closed = self.traffic["loop"] == "closed"
        self.t0 = time.perf_counter()
        end = self.t0 + self.seconds
        pending = deque(self.items)
        if closed:
            for _ in range(int(self.traffic["clients"])):
                self._submit_closed(self.t0)
        while True:
            now = time.perf_counter()
            if now >= end:
                # arrivals due inside the window are sent, however late
                while not closed and pending \
                        and pending[0].at_s < self.seconds:
                    item = pending.popleft()
                    self._submit(item, self.t0 + item.at_s)
                break
            if self.on_tick is not None:
                self.on_tick(now - self.t0)
            while not closed and pending and self.t0 + pending[0].at_s <= now:
                item = pending.popleft()
                self._submit(item, self.t0 + item.at_s)
            if self.llm.has_work:
                done = self._step(True)
                if closed:
                    for _ in done:
                        if time.perf_counter() < end:
                            self._submit_closed(time.perf_counter())
            elif not closed:
                nxt = self.t0 + pending[0].at_s if pending else end
                with self.spans.span("wait"):
                    time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        self.t1 = time.perf_counter()
        if self.on_tick is not None:
            self.on_tick(self.t1 - self.t0)
        deadline = self.t1 + drain_s
        while self.llm.has_work and time.perf_counter() < deadline:
            self._step(False)
        self.drained = not self.llm.has_work
        for uid, r in self.reqs.items():
            out = self.llm.poll(uid)
            if out is not None:
                r.admitted = out.timing.admitted_s

    def _submit_closed(self, now: float) -> None:
        item = self.items[self._next % len(self.items)]
        self._next += 1
        self._submit(item, now)
