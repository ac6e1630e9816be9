"""Request-lifecycle serving API: LLM facade, bucketed variable-length
admission, streaming, stop conditions, per-request PRNG determinism.

Backend-only behavior (stop sequences, uid rules, max_steps accounting) runs
over a deterministic in-process FakeBackend — no jax, instant.  Sampling and
bucketing determinism run over the real TensorBackend; the cross-backend
facade test re-execs in a subprocess with 8 fake XLA devices (same pattern
as test_runtime.py).
"""
import os
import subprocess
import sys
import textwrap
from typing import Dict, List, Sequence

import numpy as np
import pytest

from repro.runtime.base import BackendInfo, InferenceBackend, SlotEvent
from repro.serving import (LLM, ContinuousBatcher, IncompleteServeError,
                           Request, SamplingParams)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(body: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # the children never ask for a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


class FakeBackend(InferenceBackend):
    """Deterministic logits backend: slot emits ``pattern`` cyclically,
    via one-hot logits (so the scheduler's sampling path is exercised)."""

    def __init__(self, pattern: Sequence[int], n_slots: int = 2,
                 vocab: int = 16, max_len: int = 1 << 20):
        self.pattern = list(pattern)
        self.vocab = vocab
        self._count: Dict[int, int] = {}
        self._info = BackendInfo(n_slots=n_slots, max_len=max_len)

    @property
    def info(self) -> BackendInfo:
        return self._info

    def _logits(self, slot: int) -> np.ndarray:
        tok = self.pattern[self._count[slot] % len(self.pattern)]
        out = np.zeros(self.vocab, np.float32)
        out[tok] = 1.0
        return out

    def prefill(self, slots, prompts, prompt_lens=None) -> List[SlotEvent]:
        assert prompts.ndim == 2 and prompts.shape[0] == len(slots)
        if prompt_lens is not None:        # scheduler passes true lengths
            assert len(prompt_lens) == len(slots)
            assert all(1 <= n <= prompts.shape[1] for n in prompt_lens)
        for s in slots:
            self._count[s] = 0
        return [SlotEvent(slot=s, logits=self._logits(s)) for s in slots]

    def decode_step(self, feeds) -> List[SlotEvent]:
        out = []
        for s in sorted(feeds):
            if s in self._count:
                self._count[s] += 1
                out.append(SlotEvent(slot=s, logits=self._logits(s)))
        return out

    def free_slot(self, slot: int) -> None:
        self._count.pop(slot, None)


# --------------------------------------------------------------------------- #
# stop conditions (types + scheduler, no jax)
# --------------------------------------------------------------------------- #

def test_stop_sequence_terminates():
    llm = LLM.from_backend(FakeBackend([5, 7]))        # emits 5,7,5,7,...
    [out] = llm.generate([[1, 2, 3]],
                         SamplingParams(max_tokens=64,
                                        stop_sequences=((7, 5),)))
    assert out.tokens == [5, 7, 5]
    assert out.finish_reason == "stop"


def test_eos_and_min_tokens():
    # eos fires immediately ...
    [a] = LLM.from_backend(FakeBackend([5, 7])).generate(
        [[1]], SamplingParams(max_tokens=64, eos_id=5))
    assert a.tokens == [5] and a.finish_reason == "stop"
    # ... unless min_tokens suppresses it until the next occurrence
    [b] = LLM.from_backend(FakeBackend([5, 7])).generate(
        [[1]], SamplingParams(max_tokens=64, eos_id=5, min_tokens=2))
    assert b.tokens == [5, 7, 5] and b.finish_reason == "stop"
    # max_tokens is never suppressed
    [c] = LLM.from_backend(FakeBackend([5, 7])).generate(
        [[1]], SamplingParams(max_tokens=4, min_tokens=99))
    assert len(c.tokens) == 4 and c.finish_reason == "length"


# --------------------------------------------------------------------------- #
# uid rules + run() accounting
# --------------------------------------------------------------------------- #

def test_duplicate_uid_rejected():
    b = ContinuousBatcher(FakeBackend([1]))
    b.submit(Request(np.array([1, 2]), uid=7))
    with pytest.raises(ValueError, match="duplicate request uid 7"):
        b.submit(Request(np.array([3, 4]), uid=7))
    # a finished uid stays taken (it keys .done and the PRNG stream)
    b.run()
    with pytest.raises(ValueError, match="duplicate"):
        b.submit(Request(np.array([5]), uid=7))


def test_auto_uids_are_unique():
    uids = {Request(np.array([1])).uid for _ in range(50)}
    assert len(uids) == 50


def test_auto_and_explicit_uids_mix():
    """Auto uids live in a disjoint namespace, so explicit small ints never
    collide with them in one batcher."""
    llm = LLM.from_backend(FakeBackend([1], n_slots=4))
    u_auto1 = llm.submit([1], SamplingParams(max_tokens=1))
    llm.submit([2], SamplingParams(max_tokens=1), uid=0)
    llm.submit([3], SamplingParams(max_tokens=1), uid=1)
    u_auto2 = llm.submit([4], SamplingParams(max_tokens=1))
    assert len({u_auto1, u_auto2, 0, 1}) == 4
    while llm.has_work:
        llm.step()
    assert sorted(llm.batcher.done) == sorted([0, 1, u_auto1, u_auto2])


def test_release_evicts_and_frees_uid():
    llm = LLM.from_backend(FakeBackend([2], n_slots=2))
    llm.submit([1, 2], SamplingParams(max_tokens=2), uid=5)
    while llm.has_work:
        llm.step()
    out = llm.poll(5, release=True)
    assert out.tokens == [2, 2]
    assert llm.poll(5) is None and 5 not in llm.batcher.done
    # the uid is reusable after release
    llm.submit([9], SamplingParams(max_tokens=1), uid=5)
    while llm.has_work:
        llm.step()
    assert llm.poll(5).n_generated == 1


def test_on_token_callback_sees_consistent_finish_state():
    """A finished=True callback must observe the request already finished:
    in .done, finish_reason set — so servers can poll() from the hook."""
    backend = FakeBackend([3], n_slots=1)
    seen = []

    def hook(ev):
        if ev.finished:
            req = b.done.get(ev.uid)
            seen.append((req is not None, req.finish_reason if req else None))

    b = ContinuousBatcher(backend, on_token=hook)
    b.submit(Request(np.array([1]), SamplingParams(max_tokens=3), uid=0))
    b.run()
    assert seen == [(True, "length")]


def test_facade_importable_and_servable_without_jax():
    """The LLM facade over SimBackend (the planner/benchmark path) must not
    require jax — the engine and sampling import lazily."""
    r = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import sys
        class Block:
            def find_module(self, name, path=None):
                if name == "jax" or name.startswith("jax."):
                    raise ImportError("jax blocked")
        sys.meta_path.insert(0, Block())
        import numpy as np
        from repro.core.simulator import StageCosts
        from repro.runtime import SimBackend
        from repro.serving import LLM, SamplingParams
        costs = StageCosts(prefill=np.array([.01]), decode=np.array([.001]),
                           comm_prefill=np.zeros(0), comm_decode=np.zeros(0),
                           return_comm=0.0)
        outs = LLM.from_backend(SimBackend(costs, n_slots=2)).generate(
            [[1, 2, 3], [4]], SamplingParams(max_tokens=4))
        assert all(o.n_generated == 4 for o in outs)
        print("OK")
        """)], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")),
        timeout=120)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr


def test_run_max_steps_raises_with_partial_results():
    b = ContinuousBatcher(FakeBackend([3], n_slots=1))
    b.submit(Request(np.array([1]), SamplingParams(max_tokens=2), uid=0))
    b.submit(Request(np.array([2]), SamplingParams(max_tokens=500), uid=1))
    with pytest.raises(IncompleteServeError) as ei:
        b.run(max_steps=10)
    assert b.stats.exhausted
    assert 0 in ei.value.done and 1 not in ei.value.done   # partial salvaged
    # draining the rest afterwards still works
    b.run()
    assert sorted(b.done) == [0, 1]


def test_submit_rejects_oversized_and_empty_prompts():
    b = ContinuousBatcher(FakeBackend([1], max_len=16))
    with pytest.raises(ValueError, match="exceeds"):
        b.submit(Request(np.arange(17)))
    with pytest.raises(ValueError, match="empty"):
        b.submit(Request(np.zeros(0, np.int32)))
    # true prompt + max_tokens overflowing the KV cache would silently
    # corrupt every token past max_len — rejected up front instead
    with pytest.raises(ValueError, match="overflows"):
        b.submit(Request(np.arange(6),              # 6 + 12 - 1 = 17 > 16
                         SamplingParams(max_tokens=12)))
    # the check uses the TRUE length, not the padded bucket: a request that
    # fits unpadded is admissible even when bucket + max_tokens would not be
    b.submit(Request(np.arange(3),                  # bucket 4; 3+12-1 <= 16
                     SamplingParams(max_tokens=12)))
    b.submit(Request(np.arange(14),                 # 14 + 3 - 1 == 16: fits
                     SamplingParams(max_tokens=3)))


# --------------------------------------------------------------------------- #
# stepping interface (submit mid-flight, poll)
# --------------------------------------------------------------------------- #

def test_submit_step_poll_midflight():
    llm = LLM.from_backend(FakeBackend([4, 9], n_slots=2))
    u1 = llm.submit([1, 2, 3], SamplingParams(max_tokens=8))
    for _ in range(3):
        llm.step()
    assert llm.poll(u1) is None
    assert llm.batcher.status(u1) == "running"
    u2 = llm.submit([6], SamplingParams(max_tokens=2))   # joins mid-flight
    while llm.has_work:
        llm.step()
    o1, o2 = llm.poll(u1), llm.poll(u2)
    assert o1.n_generated == 8 and o2.n_generated == 2
    assert o2.timing.admit_step >= 3         # admitted after u1 was running
    assert o1.timing.ttft_s is not None and o1.timing.e2e_s >= 0
    assert llm.batcher.status(u1) == "finished"


def test_streaming_event_order():
    llm = LLM.from_backend(FakeBackend([2, 3, 4], n_slots=2))
    events = list(llm.stream([[1, 2], [3, 4, 5, 6, 7]],
                             SamplingParams(max_tokens=5)))
    by_uid: Dict[int, List] = {}
    for ev in events:
        by_uid.setdefault(ev.uid, []).append(ev)
    assert len(by_uid) == 2
    for evs in by_uid.values():
        assert [e.index for e in evs] == list(range(5))   # in-order, gapless
        assert [e.finished for e in evs] == [False] * 4 + [True]
        assert evs[-1].finish_reason == "length"
        assert [e.token for e in evs] == [2, 3, 4, 2, 3]
    # events interleave across requests as slots decode in the same steps
    steps_a, steps_b = ([e.step for e in evs] for evs in by_uid.values())
    assert steps_a == steps_b


# --------------------------------------------------------------------------- #
# variable-length buckets + sampling determinism (real TensorBackend)
# --------------------------------------------------------------------------- #

def _tiny_llm(n_slots=2, max_len=64, seed=0):
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, LLM.from_backend(
        TensorBackend(cfg, params, n_slots=n_slots, max_len=max_len),
        seed=seed)


def test_variable_length_prompts_one_batch():
    """Mixed-length prompts serve in one continuous batch with a bounded set
    of prefill shapes, and each request's tokens depend only on its own
    prompt (not on batch composition or padding of others)."""
    cfg, llm = _tiny_llm(n_slots=3)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 5, 9, 12, 2)]
    outs = llm.generate(prompts, SamplingParams(max_tokens=4))
    assert [o.n_prompt for o in outs] == [3, 5, 9, 12, 2]
    assert all(o.n_generated == 4 for o in outs)
    # bucketed admission: every prefill shape is a power-of-two bucket
    # (min_bucket defaults to 1 now that masked prefill is pad-neutral)
    assert set(llm.stats.prefill_shapes) <= {2, 4, 8, 16}
    # determinism: the length-5 prompt served alone yields identical tokens
    _, solo = _tiny_llm(n_slots=3)
    [ref] = solo.generate([prompts[1]], SamplingParams(max_tokens=4))
    assert ref.tokens == outs[1].tokens
    # pad-neutrality: a coarser bucket floor pads the same prompt wider yet
    # produces identical tokens (pads are masked, not fed)
    from repro.runtime import TensorBackend
    import jax
    from repro.models import transformer as T
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    wide = LLM.from_backend(TensorBackend(cfg, params, n_slots=3, max_len=64),
                            min_bucket=16)
    [w] = wide.generate([prompts[1]], SamplingParams(max_tokens=4))
    assert set(wide.stats.prefill_shapes) == {16}
    assert w.tokens == outs[1].tokens


def test_sampling_determinism_under_reordering():
    """Same seed + same uids => identical stochastic outputs regardless of
    submission order, arrival step, or slot count/assignment (per-request
    PRNG streams are isolated)."""
    cfg, llm_a = _tiny_llm(n_slots=2, seed=11)
    rng = np.random.default_rng(4)
    prompts = {uid: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for uid, n in enumerate((4, 6, 8, 5))}
    sp = SamplingParams(max_tokens=6, temperature=0.9, top_k=8)

    for uid in range(4):
        llm_a.submit(prompts[uid], sp, uid=uid)
    while llm_a.has_work:
        llm_a.step()

    _, llm_b = _tiny_llm(n_slots=3, seed=11)     # different slot layout
    for i, uid in enumerate(reversed(range(4))):  # reversed + staggered
        llm_b.submit(prompts[uid], sp, uid=uid, at_step=2 * i)
    while llm_b.has_work:
        llm_b.step()

    for uid in range(4):
        a, b = llm_a.poll(uid), llm_b.poll(uid)
        assert a.tokens == b.tokens, uid
    # sanity: stochastic sampling actually diverges across seeds
    _, llm_c = _tiny_llm(n_slots=2, seed=12)
    for uid in range(4):
        llm_c.submit(prompts[uid], sp, uid=uid)
    while llm_c.has_work:
        llm_c.step()
    assert any(llm_c.poll(u).tokens != llm_a.poll(u).tokens for u in range(4))


def test_stream_matches_generate():
    cfg, llm = _tiny_llm(n_slots=2)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 7)]
    streamed: Dict[int, List[int]] = {}
    for ev in llm.stream(prompts, SamplingParams(max_tokens=5)):
        streamed.setdefault(ev.uid, []).append(ev.token)
    _, ref = _tiny_llm(n_slots=2)
    outs = ref.generate(prompts, SamplingParams(max_tokens=5))
    # auto-uids increase in submission order on both facades
    assert [streamed[u] for u in sorted(streamed)] == [o.tokens for o in outs]


# --------------------------------------------------------------------------- #
# paged overcommit stress (real TensorBackend)
# --------------------------------------------------------------------------- #

def _tiny_paged_llm(num_blocks, n_slots=3, max_len=32, seed=0):
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, LLM.from_backend(
        TensorBackend(cfg, params, n_slots=n_slots, max_len=max_len,
                      cache_layout="paged", num_blocks=num_blocks),
        seed=seed)


def test_overcommit_stress_submit_step_poll():
    """Overcommit acceptance: aggregate KV demand far exceeds the pool
    (10 requests x 2 worst-case blocks over a 4-block pool, more requests
    than slots), driven through the non-blocking submit/step/poll server
    interface.  Everything completes, preemptions are recorded in
    SchedulerStats (and per request), and every output is identical to a
    serial one-request-at-a-time run."""
    from repro.serving import SamplingParams
    cfg, llm = _tiny_paged_llm(num_blocks=4)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, 3 + (i * 3) % 10
                            ).astype(np.int32) for i in range(10)]
    sp = SamplingParams(max_tokens=12)      # bucket + 12 tokens > 1 block

    # serial reference: one request at a time, fresh contiguous backend
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    ref = []
    serial = LLM.from_backend(TensorBackend(cfg, params, n_slots=3,
                                            max_len=32))
    for p in prompts:
        [o] = serial.generate([p], sp)
        ref.append(o.tokens)

    uids = [llm.submit(p, sp) for p in prompts]
    steps = 0
    while llm.has_work:
        llm.step()
        steps += 1
        assert steps < 2000, "overcommitted workload failed to drain"
    outs = [llm.poll(u) for u in uids]
    assert all(o is not None and o.finish_reason == "length" for o in outs)
    assert llm.stats.preemptions > 0, \
        "a 4-block pool under 20-block demand must preempt"
    assert llm.stats.resumes > 0
    assert sum(o.timing.preemptions for o in outs) == llm.stats.preemptions
    for o, r in zip(outs, ref):
        assert o.tokens == r, (o.uid, o.tokens, r)
    # the pool drains fully: every block back on the free list
    info = llm.backend.info
    assert info.free_blocks == info.total_blocks
    # and the admission budget never let prefill outrun the pool
    assert info.total_blocks < 10 * info.blocks_for_len(32), "no overcommit?"


def test_submit_rejects_request_larger_than_pool():
    """A single request whose worst-case block demand exceeds the whole pool
    can never be served (preemption cannot help) — rejected at submit."""
    from repro.serving import SamplingParams
    _, llm = _tiny_paged_llm(num_blocks=1)
    with pytest.raises(ValueError, match="KV blocks"):
        llm.submit(np.arange(3), SamplingParams(max_tokens=20))


# --------------------------------------------------------------------------- #
# facade over both real backends (subprocess: needs 8 XLA devices)
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_llm_facade_pipeline_matches_tensor_varlen():
    """Acceptance: LLM.from_plan over the no-bubbles PipelineBackend serves
    variable-length prompts and matches LLM.from_backend(TensorBackend)
    token-for-token; stream() works over the pipeline too."""
    run_subprocess("""
import jax, numpy as np
from repro import runtime
from repro.configs import get_config
from repro.core.devices import tpu_pod_cluster
from repro.core.profile import Workload
from repro.models import transformer as T
from repro.serving import LLM, SamplingParams

cfg = get_config("qwen3-0.6b").reduced(n_layers=4)
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(2)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
           for n in (3, 6, 4, 5)]
sp = SamplingParams(max_tokens=4)

pipe = LLM.from_plan(cfg, tpu_pod_cluster(n_chips=2), Workload(dtype_bytes=2),
                     objective="throughput", kind="pipeline", params=params,
                     max_len=32)
assert pipe.backend.spec.n_stages >= 2
pipe_out = pipe.generate(prompts, sp)

tens = LLM.from_backend(runtime.TensorBackend(cfg, params, n_slots=3,
                                              max_len=32))
tens_out = tens.generate(prompts, sp)
for p, t in zip(pipe_out, tens_out):
    assert p.tokens == t.tokens, (p.uid, p.tokens, t.tokens)
assert len(np.unique([t for o in tens_out for t in o.tokens])) > 2

# streaming over the pipeline: same tokens, token-by-token
pipe2 = LLM.from_plan(cfg, tpu_pod_cluster(n_chips=2), Workload(dtype_bytes=2),
                      objective="throughput", kind="pipeline", params=params,
                      max_len=32)
got = {}
for ev in pipe2.stream(prompts[:2], sp):
    got.setdefault(ev.uid, []).append(ev.token)
assert sorted(got.values()) == sorted(t.tokens for t in tens_out[:2])
print("facade parity OK")
""")
