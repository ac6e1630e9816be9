"""Shared-prefix KV reuse + chunked prefill: semantic-neutrality suite.

The tentpole contract (docs/runtime.md): prefix caching and chunked
prefill are *transparent* runtime optimizations —

- greedy outputs with the prefix cache on are token-identical to off;
- chunked prefill is token-identical to monolithic, any chunk size;
- both compose, and survive preempt -> resume with shared prefixes;
- stats surface the reuse (nonzero hits / hit tokens / chunk passes);
- the gate is honest: contiguous layouts report ``prefix_caching=False``
  and record zero hits while still serving exact tokens.

Unit tests cover the PrefixCache index itself (chained keys, first-writer
wins, eviction cascade).  Tensor/Sim parity runs inline on CPU; the
pipeline backend re-execs in a subprocess with fake XLA devices (same
pattern as test_backend_conformance.py).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.runtime.base import BlockAllocator, SlotPager
from repro.runtime.prefix_cache import PrefixCache

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


# --------------------------------------------------------------------------- #
# PrefixCache unit tests (jax-free)
# --------------------------------------------------------------------------- #

def _pool(num_blocks=8, bs=4):
    al = BlockAllocator(num_blocks)
    return al, PrefixCache(al, bs)


def test_chained_lookup_is_exact():
    al, pc = _pool()
    toks = np.arange(12, dtype=np.int32)
    blocks = al.alloc(3)
    assert pc.register(toks, blocks) == 3
    assert pc.lookup(toks) == blocks
    assert pc.lookup(toks[:8]) == blocks[:2]
    assert pc.matched_tokens(toks, cap=8) == 8
    # same middle block content under a different first block: no alias —
    # the chained parent key distinguishes left contexts
    other = np.concatenate([toks[4:8], toks[4:8]]).astype(np.int32)
    assert pc.lookup(other) == []
    # partial trailing block never matches (block-aligned runs only)
    assert pc.lookup(toks[:10]) == blocks[:2]


def test_first_writer_wins():
    al, pc = _pool()
    toks = np.arange(8, dtype=np.int32)
    first = al.alloc(2)
    dup = al.alloc(2)
    assert pc.register(toks, first) == 2
    assert pc.register(toks, dup) == 0       # duplicate copy stays private
    assert pc.lookup(toks) == first
    al.free(dup)                             # plain free: was never indexed
    assert al.cached_blocks == 0
    al.free(first)                           # indexed: parks cached-free
    assert al.cached_blocks == 2
    assert pc.lookup(toks) == first          # still adoptable


def test_eviction_cascades_over_children():
    al, pc = _pool(num_blocks=3)
    toks = np.arange(12, dtype=np.int32)
    blocks = al.alloc(3)
    pc.register(toks, blocks)
    al.free(blocks)                          # all parked cached-free
    # pool dry: alloc(1) evicts the LRU block — the chain head — and the
    # index drops the whole (now unreachable) chain
    (b,) = al.alloc(1)
    assert b == blocks[0]
    assert pc.n_indexed == 0
    assert pc.lookup(toks) == []
    # the children's *blocks* are still cached-free until repurposed
    assert al.cached_blocks == 2


def test_adopt_resurrects_cached_chain():
    al, pc = _pool()
    pager = SlotPager(n_slots=2, num_blocks=8, block_size=4,
                      max_ctx_blocks=4)
    pc = PrefixCache(pager.allocator, 4)
    toks = np.arange(10, dtype=np.int32)
    pager.ensure(0, len(toks) - 1)
    held = pager.table[0, :2].tolist()
    pc.register(toks, held)
    pager.release(0)
    assert pager.allocator.cached_blocks == 2
    got = pc.lookup(toks[:8])
    assert got == held
    pager.adopt(1, got)                      # zero-copy resurrection
    assert pager.allocator.cached_blocks == 0
    assert (pager.allocator.refcount[held] == 1).all()


# --------------------------------------------------------------------------- #
# serving parity: tensor backend (inline) and sim accounting
# --------------------------------------------------------------------------- #

def _shared_prefix_prompts(vocab, seed=0, n_shared=16, tails=(5, 7, 3, 9)):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, n_shared).astype(np.int32)
    return [np.concatenate([shared,
                            rng.integers(0, vocab, n).astype(np.int32)])
            for n in tails]


def test_tensor_prefix_and_chunked_parity():
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    from repro.serving import LLM, SamplingParams

    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))

    def mk(prefix=False, chunk=None, num_blocks=24, n_slots=2,
           layout="paged"):
        be = TensorBackend(cfg, params, n_slots=n_slots, max_len=64,
                           cache_layout=layout, block_size=8,
                           num_blocks=num_blocks, prefix_cache=prefix)
        return LLM.from_backend(be, prefill_chunk=chunk)

    prompts = _shared_prefix_prompts(cfg.vocab_size)
    sp = SamplingParams(max_tokens=5)
    ref = [o.tokens for o in mk().generate(prompts, sp)]
    assert len(set(t for ts in ref for t in ts)) > 2, "degenerate reference"

    # prefix cache on: identical tokens, nonzero hits (slots < prompts, so
    # the first wave registers before later admissions look up)
    llm = mk(prefix=True)
    assert [o.tokens for o in llm.generate(prompts, sp)] == ref
    assert llm.stats.prefix_hits >= 2
    assert llm.stats.prefix_hit_tokens >= 2 * 16
    assert llm.backend.info.prefix_caching

    # chunked prefill alone: identical, chunk passes recorded
    llm = mk(chunk=4)
    assert [o.tokens for o in llm.generate(prompts, sp)] == ref
    assert llm.stats.prefill_chunks > len(prompts)

    # composed
    llm = mk(prefix=True, chunk=4)
    assert [o.tokens for o in llm.generate(prompts, sp)] == ref
    assert llm.stats.prefix_hits >= 2

    # preempt -> resume with shared prefixes: a pool too small for three
    # concurrent streams forces preemption; outputs stay serial-identical
    llm = mk(prefix=True, num_blocks=7, n_slots=3)
    assert [o.tokens for o in llm.generate(prompts, sp)] == ref
    assert llm.stats.preemptions >= 1
    assert llm.stats.resumes >= 1

    # honest gate: contiguous layout serves exact tokens with zero hits
    llm = mk(prefix=True, chunk=4, layout="contiguous")
    assert [o.tokens for o in llm.generate(prompts, sp)] == ref
    assert not llm.backend.info.prefix_caching
    assert llm.stats.prefix_hits == 0


def test_sim_backend_accounting_path():
    from repro.core.simulator import StageCosts
    from repro.runtime import SimBackend
    from repro.serving import LLM, SamplingParams

    costs = StageCosts(prefill=np.array([.01, .02]),
                       decode=np.array([.001, .002]),
                       comm_prefill=np.array([.001]),
                       comm_decode=np.array([.0001]), return_comm=.0001)
    sim = SimBackend(costs, n_slots=2, max_len=64, cache_layout="paged",
                     block_size=8, num_blocks=64, prefix_cache=True)
    llm = LLM.from_backend(sim, prefill_chunk=4)
    prompts = _shared_prefix_prompts(512)
    outs = llm.generate(prompts, SamplingParams(max_tokens=5))
    assert all(o.n_generated == 5 for o in outs)
    assert llm.stats.prefix_hits >= 2
    assert llm.stats.prefill_chunks > len(prompts)
    # all streams done: every block is free or cached-free (pool is whole)
    assert sim.info.free_blocks == sim.info.total_blocks
    assert sim.info.prefix_blocks_cached > 0


# --------------------------------------------------------------------------- #
# pipeline backend (subprocess: fake XLA devices)
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_pipeline_prefix_and_chunked_parity():
    run_subprocess("""
        import numpy as np, jax
        from repro.configs import get_config
        from repro.core import pipeline as PL
        from repro.models import transformer as T
        from repro.serving import LLM, SamplingParams
        from repro.runtime.pipeline_backend import PipelineBackend
        from repro.sharding import make_mesh

        cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
        params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
        spec = PL.even_pipeline_spec(cfg, 2)
        mesh = make_mesh((1, 2), ("data", "model"))

        def mk(layout="paged", prefix=False, chunk=None):
            be = PipelineBackend(cfg, params, spec, mesh, n_slots=2,
                                 max_len=64, cache_layout=layout,
                                 block_size=8, num_blocks=24,
                                 prefix_cache=prefix)
            return LLM.from_backend(be, prefill_chunk=chunk)

        rng = np.random.default_rng(0)
        shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, n).astype(np.int32)])
            for n in (5, 7, 3, 9)]
        sp = SamplingParams(max_tokens=5)

        ref = [o.tokens for o in mk().generate(prompts, sp)]
        assert len(set(t for ts in ref for t in ts)) > 2

        llm = mk(prefix=True)
        assert [o.tokens for o in llm.generate(prompts, sp)] == ref
        assert llm.stats.prefix_hits >= 2, llm.stats
        assert llm.stats.prefix_hit_tokens >= 32, llm.stats

        llm = mk(chunk=4)
        assert [o.tokens for o in llm.generate(prompts, sp)] == ref
        assert llm.stats.prefill_chunks > len(prompts), llm.stats

        llm = mk(prefix=True, chunk=4)
        assert [o.tokens for o in llm.generate(prompts, sp)] == ref
        assert llm.stats.prefix_hits >= 2, llm.stats

        # contiguous pipeline: gate off, chunked streaming still exact
        llm = mk("contiguous", prefix=True, chunk=4)
        assert [o.tokens for o in llm.generate(prompts, sp)] == ref
        assert llm.stats.prefix_hits == 0, llm.stats
        print("OK")
    """)
