"""Spans and named programs of the serving path (``repro.obs``), and the
wall-clock queue wait the scheduler counts.

A profiler trace of a tiny model served on the CPU must hold the fixed
``repro.*`` spans nested as the serving path calls them, with each wave's
request uids; the jitted programs must carry their fixed names; and a span
taken with no profiler running must cost next to nothing.
"""
import glob
import time

import numpy as np
import pytest

from repro import obs
from repro.core.simulator import StageCosts
from repro.runtime.sim import SimBackend
from repro.serving import ContinuousBatcher, Fleet, Request, SamplingParams

MAX_LEN = 64


def _tiny_backend(n_slots=3):
    import jax

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, TensorBackend(cfg, params, n_slots=n_slots, max_len=MAX_LEN,
                              cache_layout="paged")


def _req(uid, plen=6, gen=4, base=1):
    return Request(prompt=np.arange(base, base + plen, dtype=np.int32),
                   params=SamplingParams(max_tokens=gen), uid=uid)


def _host_spans(log_dir):
    """(name, start, end, stats) of every ``repro.*`` host event."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _within(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_spans_nest_in_a_profiler_trace(tmp_path):
    import jax
    _, backend = _tiny_backend()
    cb = ContinuousBatcher(backend)
    with jax.profiler.trace(str(tmp_path)):
        for u in (11, 12):
            cb.submit(_req(u, base=u))
        cb.run()
    spans = _host_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    for name in ("repro.sched.step", "repro.sched.admit", "repro.sched.sample",
                 "repro.backend.prefill", "repro.backend.decode_step",
                 "repro.backend.pager", "repro.backend.dispatch",
                 "repro.backend.fetch"):
        assert by.get(name), name
    steps = by["repro.sched.step"]
    calls = by["repro.backend.prefill"] + by["repro.backend.decode_step"]
    for s in by["repro.sched.admit"] + by["repro.backend.decode_step"] \
            + by["repro.sched.sample"]:
        assert _within(s, steps), s
    for s in by["repro.backend.prefill"]:
        assert _within(s, by["repro.sched.admit"]), s
    for s in by["repro.backend.pager"] + by["repro.backend.dispatch"] \
            + by["repro.backend.fetch"]:
        assert _within(s, calls), s
    # both requests share one bucket, so one wave admits them and its span
    # carries their uids as ints
    (wave,) = by["repro.sched.admit"]
    assert wave[3]["rows"] == 2 and wave[3]["bucket"] == 8
    assert {wave[3]["uid0"], wave[3]["uid1"]} == {11, 12}
    assert [s[3]["step"] for s in steps] == list(range(len(steps)))


def test_prefill_span_carries_rows_and_width(tmp_path):
    """Each prefill call's span names its wave's rows and width, for a
    lone wide row and for short waves of 1..n_slots rows."""
    import jax

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    n_slots, wide = 3, 256
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    b = TensorBackend(cfg, params, n_slots=n_slots, max_len=wide + 16,
                      cache_layout="paged")
    waves = [(1, wide)] + [(k, 8) for k in range(1, n_slots + 1)]
    with jax.profiler.trace(str(tmp_path)):
        for k, w in waves:
            b.prefill(list(range(k)), np.ones((k, w), np.int32), [w] * k)
            for s in range(k):
                b.free_slot(s)
    spans = sorted((s for s in _host_spans(tmp_path)
                    if s[0] == "repro.backend.prefill"), key=lambda s: s[1])
    assert [(s[3]["rows"], s[3]["width"]) for s in spans] == waves


def test_programs_have_fixed_names():
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    cfg, b = _tiny_backend(n_slots=2)
    w = 8
    fresh = T.init_caches(cfg, 2, w, b.cache_dtype)
    tokens = jnp.zeros((2, w), jnp.int32)
    lens = jnp.full((2,), w, jnp.int32)
    prefill = b._prefill_fn.lower(b.params, tokens, caches=fresh,
                                  prompt_lens=lens)
    _, dense, _ = jax.eval_shape(
        lambda p, t, c, n: b._prefill_fn(p, t, caches=c, prompt_lens=n),
        b.params, tokens, fresh, lens)
    scatter = b._scatter_fn.lower(b.caches, dense, jnp.zeros(2, jnp.int32),
                                  jnp.zeros((2, b.pager.table.shape[1]),
                                            jnp.int32))
    decode = b._decode_fn.lower(b.params, jnp.zeros(2, jnp.int32), b.caches,
                                jnp.ones(2, bool))
    for lowered, name in ((prefill, "jit_prefill"),
                          (scatter, "jit_prefill_scatter"),
                          (decode, "jit_decode_step")):
        assert lowered.as_text().startswith(f"module @{name} "), name


def test_span_cost_without_a_profiler():
    """About ten spans a scheduler step must stay far below the 0.25 ms
    bound on the 95th percentile time per output token."""
    n = 2000
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        for i in range(n):
            with obs.span("repro.sched.admit", rows=2) as sp:
                sp.set_metadata(bucket=i, **obs.uids((i, i + 1)))
        best = min(best, (time.perf_counter() - t) / n)
    assert best < 10e-6


def test_uids_one_key_per_row():
    assert obs.uids([5, 9, 2]) == {"uid0": 5, "uid1": 9, "uid2": 2}
    assert obs.uids(range(40))["uid39"] == 39


# --------------------------------------------------------------------------- #
# wall-clock queue wait
# --------------------------------------------------------------------------- #

def _costs():
    return StageCosts(prefill=np.full(1, 1e-3), decode=np.full(1, 1e-3),
                      comm_prefill=np.zeros(0), comm_decode=np.zeros(0),
                      return_comm=0.0)


class _Slow(SimBackend):
    """A sim whose calls take wall time."""

    prefill_s = 0.0
    decode_s = 0.0

    def prefill(self, slots, prompts, prompt_lens=None):
        time.sleep(self.prefill_s)
        return super().prefill(slots, prompts, prompt_lens)

    def decode_step(self, feeds):
        time.sleep(self.decode_s)
        return super().decode_step(feeds)


def test_queue_wait_excludes_the_waves_own_prefill():
    be = _Slow(_costs(), n_slots=2, max_len=256)
    be.prefill_s = 0.05
    cb = ContinuousBatcher(be)
    for u in (1, 2):
        cb.submit(_req(u, base=u))
    done = cb.run()
    for r in done.values():
        assert r.timing.queue_s < 0.05
        assert r.timing.ttft_s >= 0.05
        assert r.timing.queued_s == r.timing.queue_s
    assert cb.stats.queue_wait_s == pytest.approx(
        sum(r.timing.queue_s for r in done.values()))


def test_queue_wait_sums_every_admission_across_preemptions():
    """``queue_wait_s`` is each admission's wait summed: from submission for
    the first, from the eviction for a resume, so the time a request ran
    before it was preempted never counts as queueing."""
    be = _Slow(_costs(), n_slots=3, max_len=256, cache_layout="paged",
               num_blocks=7)
    be.decode_s = 0.001
    cb = ContinuousBatcher(be, reserve_blocks=0, max_preemptions=100)
    reqs = {u: _req(u, plen=4, gen=80, base=u) for u in (1, 2, 3)}
    for r in reqs.values():
        cb.submit(r)
    lo = hi = 0.0
    evicted = {}
    running = set()
    while cb.has_work:
        t0 = time.perf_counter()
        cb.step()
        t1 = time.perf_counter()
        now = set(cb.running)
        for u in now - running:
            timing = reqs[u].timing
            if u in evicted:            # the eviction fell inside its step
                a, b = evicted.pop(u)
                lo += timing.admitted_s - b
                hi += timing.admitted_s - a
            else:
                lo += timing.queue_s
                hi += timing.queue_s
        for u in running - now:
            if u not in cb.done:
                evicted[u] = (t0, t1)
        running = now
    assert cb.stats.preemptions >= 2 and cb.stats.resumes >= 2
    assert lo - 1e-9 <= cb.stats.queue_wait_s <= hi + 1e-9
    assert cb.stats.queue_wait_s == pytest.approx(
        sum(r.timing.queued_s for r in reqs.values()))
    # after a resume, queue_s (last admission - submission) also holds the
    # decode steps the request ran before its eviction; queued_s does not
    resumed = [r for r in reqs.values() if r.timing.preemptions]
    assert resumed and all(r.timing.queued_s < r.timing.queue_s
                           for r in resumed)
    assert "queue_wait_s=" in str(cb.stats)


def test_fleet_sums_queue_wait():
    fleet = Fleet([SimBackend(_costs(), n_slots=1, max_len=256)
                   for _ in range(2)])
    for u in range(4):
        fleet.submit(_req(u, base=u + 1, gen=6))
    fleet.run()
    per = [b.stats.queue_wait_s for b in fleet.batchers]
    assert sum(per) > 0
    assert fleet.stats.queue_wait_s == pytest.approx(sum(per))
