"""Speculative decoding end to end: greedy spec serving must be token-
bit-identical to non-speculative serving on every backend (drafts only
change how many verify quanta the same tokens take), rejected drafts'
KV writes must be invalidated (including across preempt -> resume), and
unsupported backends must warn and degrade to plain decode.
"""
import warnings

import numpy as np
import pytest

from repro.serving.spec import (CallableDraft, NGramDraft, OracleDraft,
                                make_draft)

MAX_LEN = 64
GEN = 10


# --------------------------------------------------------------------------- #
# draft sources (jax-free)
# --------------------------------------------------------------------------- #

def test_ngram_draft_proposes_continuation_of_repeated_pattern():
    d = NGramDraft(max_ngram=3)
    ctx = np.array([5, 6, 7, 8, 9, 5, 6, 7], np.int32)
    # trailing 3-gram [5,6,7] matched at offset 0 -> propose what followed
    assert d.propose(0, ctx, 0, 2) == [8, 9]
    assert d.propose(0, np.array([1, 2, 3], np.int32), 0, 2) == []
    assert d.propose(0, ctx, 0, 0) == []


def test_ngram_draft_prefers_most_recent_match():
    d = NGramDraft(max_ngram=2)
    ctx = np.array([4, 1, 2, 9, 1, 2, 7, 1, 2], np.int32)
    assert d.propose(0, ctx, 0, 1) == [7]       # the later [1,2] wins


def test_oracle_draft_replays_and_corrupts():
    cont = {0: [10, 11, 12, 13]}
    exact = OracleDraft(cont, accept_prob=1.0)
    assert exact.propose(0, np.zeros(3, np.int32), 1, 2) == [11, 12]
    noisy = OracleDraft(cont, accept_prob=0.0, seed=3, vocab_size=100)
    prop = noisy.propose(0, np.zeros(3, np.int32), 0, 4)
    assert len(prop) == 4 and all(p != t for p, t in zip(prop, cont[0]))


def test_make_draft_resolution():
    assert make_draft(None) is None and make_draft("off") is None
    assert isinstance(make_draft("ngram"), NGramDraft)
    assert make_draft("ngram:5").max_ngram == 5
    src = NGramDraft()
    assert make_draft(src) is src
    assert isinstance(make_draft(lambda ctx, k: [1] * k), CallableDraft)
    with pytest.raises(ValueError):
        make_draft("bogus")


# --------------------------------------------------------------------------- #
# serving parity: greedy spec == non-spec, bit exact
# --------------------------------------------------------------------------- #

def _mk_tensor(layout="paged", num_blocks=None, n_slots=3, max_len=MAX_LEN):
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return TensorBackend(cfg, params, n_slots=n_slots, max_len=max_len,
                         cache_layout=layout, block_size=8,
                         num_blocks=num_blocks)


def _mk_sim(n_slots=3, max_len=MAX_LEN):
    from repro.core.simulator import StageCosts
    from repro.runtime import SimBackend
    costs = StageCosts(prefill=np.array([.01, .02]),
                       decode=np.array([.001, .002]),
                       comm_prefill=np.array([.001]),
                       comm_decode=np.array([.0001]),
                       return_comm=.0001)
    return SimBackend(costs, n_slots=n_slots, max_len=max_len,
                      cache_layout="paged", block_size=8,
                      num_blocks=n_slots * (max_len // 8))


def _serve(backend, prompts, *, gen=GEN, spec_k=0, draft="ngram"):
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    b = ContinuousBatcher(backend, spec_k=spec_k, draft=draft)
    for uid, p in enumerate(prompts):
        b.submit(Request(np.asarray(p, np.int32),
                         SamplingParams(max_tokens=gen), uid=uid))
    done = b.run()
    return {u: done[u].generated for u in range(len(prompts))}, b.stats


def _prompts(n=3, seed=0, lens=(5, 9, 7)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1000, k).astype(np.int32)
            for k in lens[:n]]


@pytest.mark.parametrize("mk", [_mk_sim, _mk_tensor],
                         ids=["sim", "tensor"])
def test_spec_greedy_bitexact_with_corrupted_oracle(mk):
    """Oracle drafts at 75% per-token accept probability: every rejection
    exercises rollback, yet tokens match plain decode exactly and fewer
    quanta are spent."""
    prompts = _prompts()
    ref, ref_stats = _serve(mk(), prompts)
    oracle = OracleDraft(dict(ref), accept_prob=0.75, seed=1)
    got, stats = _serve(mk(), prompts, spec_k=4, draft=oracle)
    assert got == ref
    assert stats.spec_drafted > 0 and stats.spec_accepted > 0
    assert 0.0 < stats.spec_acceptance < 1.0    # some rollbacks happened
    assert stats.decode_steps < ref_stats.decode_steps


@pytest.mark.parametrize("mk", [_mk_sim, _mk_tensor],
                         ids=["sim", "tensor"])
def test_spec_greedy_bitexact_with_ngram_selfspec(mk):
    prompts = _prompts()
    ref, _ = _serve(mk(), prompts)
    got, stats = _serve(mk(), prompts, spec_k=4, draft=NGramDraft())
    assert got == ref
    if mk is _mk_tensor:
        # the untrained model's repetitive output gives the n-gram draft
        # real matches; sim tokens are crc-pseudo-random, so no proposals
        # there (the quantum legitimately degenerates to 1-token verify)
        assert stats.spec_drafted > 0


def test_spec_rejected_kv_invalidated_under_preempt_resume():
    """The hard case: corrupted drafts force rollbacks AND an undersized
    pool forces preempt -> recompute-on-resume in the same run.  Any
    rejected-position KV left behind as a valid cache key would poison the
    resumed stream; exact parity with an uninterrupted contiguous run
    proves the ring/key_pos invalidation holds."""
    prompts = _prompts(n=5, lens=(6, 9, 4, 7, 5))
    ref, _ = _serve(_mk_tensor("contiguous", max_len=32), prompts, gen=12)
    # 3 slots x (32/8)=4 worst-case blocks each; a 7-block pool must
    # overcommit, so verify quanta hit PoolExhausted mid-run
    oracle = OracleDraft(dict(ref), accept_prob=0.6, seed=2)
    got, stats = _serve(_mk_tensor(num_blocks=7, max_len=32), prompts,
                        gen=12, spec_k=4, draft=oracle)
    assert got == ref
    assert stats.preemptions > 0 and stats.resumes > 0
    assert stats.spec_drafted > stats.spec_accepted > 0


def test_spec_on_unsupported_backend_warns_and_serves():
    prompts = _prompts(n=1)
    be = _mk_tensor("contiguous")
    assert not be.info.spec_decode
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got, stats = _serve(be, prompts, spec_k=4)
    assert any("speculative" in str(x.message) for x in w)
    assert len(got[0]) == GEN and stats.spec_drafted == 0


def test_spec_k_validation():
    from repro.serving import ContinuousBatcher
    with pytest.raises(ValueError):
        ContinuousBatcher(_mk_sim(), spec_k=-1)


# --------------------------------------------------------------------------- #
# pipeline: spec parity + temperature>0 via logits-through-the-ring (slow)
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_pipeline_spec_parity_and_host_sampling():
    from test_backend_conformance import run_subprocess
    run_subprocess("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np
    from repro.configs import get_config
    from repro.core import pipeline as PL
    from repro.models import transformer as T
    from repro.runtime import PipelineBackend
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    from repro.serving.spec import OracleDraft
    from repro.sharding import make_mesh

    cfg = get_config("qwen3-0.6b").reduced(n_layers=4)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    spec = PL.even_pipeline_spec(cfg, 2)
    mesh = make_mesh((1, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 7)]

    def mk():
        return PipelineBackend(cfg, params, spec, mesh, n_slots=2,
                               max_len=64, cache_layout="paged",
                               block_size=8)

    def serve(be, spec_k=0, draft="ngram", temperature=0.0):
        b = ContinuousBatcher(be, spec_k=spec_k, draft=draft)
        for uid, p in enumerate(prompts):
            b.submit(Request(p, SamplingParams(max_tokens=8,
                                               temperature=temperature),
                             uid=uid))
        done = b.run()
        return {u: done[u].generated for u in range(len(prompts))}, b.stats

    be = mk()
    assert be.info.spec_decode and not be.info.samples_in_backend
    ref, ref_stats = serve(be)
    oracle = OracleDraft(dict(ref), accept_prob=0.75, seed=1)
    got, stats = serve(mk(), spec_k=4, draft=oracle)
    assert got == ref, (got, ref)
    assert stats.spec_accepted > 0
    assert stats.decode_steps < ref_stats.decode_steps

    # temperature>0 now serves on the pipeline (host sampling from ring
    # logits; the old scheduler hard-reject for in-SPMD samplers is gone)
    hot, _ = serve(mk(), temperature=1.0)
    assert all(len(v) == 8 for v in hot.values())
    assert hot != ref, "temperature=1 should diverge from greedy"
    print("pipeline spec parity + host sampling OK")
    """)
