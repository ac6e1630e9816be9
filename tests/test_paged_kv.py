"""Unit tests for the paged KV cache itself: the host-side block allocator
and pager (jax-free), block-table growth across page boundaries, pool
exhaustion -> preemption -> resume determinism, windowed ring semantics,
and the ``window > max_len`` clamp regression.
"""
import dataclasses

import numpy as np
import pytest

from repro.runtime.base import (BackendInfo, BlockAllocator, PoolExhausted,
                                SlotPager)

# --------------------------------------------------------------------------- #
# allocator: alloc / free / refcount (jax-free)
# --------------------------------------------------------------------------- #


def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(4)
    assert a.free_blocks == 4
    got = a.alloc(3)
    assert len(set(got)) == 3 and a.free_blocks == 1
    a.free(got[:2])
    assert a.free_blocks == 3
    # freed ids recycle
    again = a.alloc(3)
    assert a.free_blocks == 0
    assert set(again) <= set(range(4))


def test_allocator_exhaustion_is_atomic():
    a = BlockAllocator(2)
    a.alloc(1)
    with pytest.raises(PoolExhausted) as ei:
        a.alloc(2)
    assert ei.value.needed == 2 and ei.value.free == 1
    assert a.free_blocks == 1               # nothing was taken


def test_allocator_refcounts_shared_blocks():
    """Refcounts support future prefix sharing: a block freed once but still
    referenced stays allocated; double-free of a free block asserts."""
    a = BlockAllocator(2)
    [b] = a.alloc(1)
    a.incref(b)
    a.free([b])
    assert a.free_blocks == 1               # still held by the second ref
    a.free([b])
    assert a.free_blocks == 2
    with pytest.raises(AssertionError):
        a.free([b])


# --------------------------------------------------------------------------- #
# pager: table growth across page boundaries, ring reuse, release
# --------------------------------------------------------------------------- #


def test_pager_grows_tables_at_block_boundaries():
    p = SlotPager(n_slots=2, num_blocks=6, block_size=4, max_ctx_blocks=3)
    assert p.blocks_for_len(0) == 0
    assert p.blocks_for_len(1) == 1
    assert p.blocks_for_len(4) == 1
    assert p.blocks_for_len(5) == 2
    assert p.blocks_for_len(999) == 3       # clamped at max_ctx_blocks
    # growth happens exactly when a position crosses into a new block
    assert p.ensure(0, 0)                   # pos 0 -> first block
    for pos in range(1, 4):
        assert not p.ensure(0, pos)
    assert p.ensure(0, 4)                   # second block
    assert int(p.n_alloc[0]) == 2
    # ring reuse past max_ctx_blocks * block_size allocates nothing
    assert p.ensure(0, 8) and int(p.n_alloc[0]) == 3
    for pos in range(9, 40):
        assert not p.ensure(0, pos)
    # tables are per-slot and disjoint
    p.ensure(1, 0)
    held0 = set(p.table[0, :3].tolist())
    held1 = {int(p.table[1, 0])}
    assert not held0 & held1
    assert p.free_blocks == 2
    # release returns everything and clears the table row
    assert p.release(0)
    assert p.free_blocks == 5 and int(p.n_alloc[0]) == 0
    assert (p.table[0] == -1).all()
    assert not p.release(0)                 # idempotent


def test_pager_exhaustion_mutates_nothing():
    p = SlotPager(n_slots=2, num_blocks=1, block_size=2, max_ctx_blocks=4)
    p.ensure(0, 0)
    with pytest.raises(PoolExhausted):
        p.ensure(1, 0)
    assert int(p.n_alloc[1]) == 0 and (p.table[1] == -1).all()


def test_backend_info_paged_accounting_fields():
    info = BackendInfo(n_slots=2, max_len=64, cache_layout="paged",
                       block_size=16, total_blocks=8, free_blocks=5,
                       bytes_per_block=1024, max_ctx_blocks=4)
    assert info.paged
    assert info.blocks_per_token == pytest.approx(1 / 16)
    assert info.blocks_for_len(17) == 2
    assert info.blocks_for_len(10 ** 9) == 4


# --------------------------------------------------------------------------- #
# device-side: growth across a page boundary preserves the key stream
# --------------------------------------------------------------------------- #


def _tiny_llm(layout, num_blocks=None, n_slots=2, max_len=64, n_layers=2,
              cfg=None):
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    from repro.serving import LLM
    cfg = cfg or get_config("qwen3-0.6b").reduced(n_layers=n_layers)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    backend = TensorBackend(cfg, params, n_slots=n_slots, max_len=max_len,
                            cache_layout=layout, num_blocks=num_blocks)
    return cfg, LLM.from_backend(backend)


def test_generation_across_page_boundary_matches_contiguous():
    """A stream long enough to span several blocks (prompt 5 + 40 generated
    > 2 x 16-token blocks) stays token-identical to the contiguous ring."""
    from repro.serving import SamplingParams
    cfg, contig = _tiny_llm("contiguous")
    _, paged = _tiny_llm("paged")
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 5).astype(np.int32)
    sp = SamplingParams(max_tokens=40)
    [a] = contig.generate([prompt], sp)
    [b] = paged.generate([prompt], sp)
    assert a.tokens == b.tokens
    assert paged.backend.pager.free_blocks == paged.backend.pager.total_blocks


def test_bf16_params_over_f32_pool_decode_both_impls():
    """The serving path's default pairing — bf16 params over a float32
    pool — decodes on both impls and they agree (GQA, g=2).  The reference
    used to widen the residual stream to float32 and fail to trace."""
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(n_layers=2),
                              dtype="bfloat16", n_kv_heads=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    logits = {}
    for impl in ("xla", "pallas"):
        be = TensorBackend(cfg, params, n_slots=2, max_len=64,
                           cache_layout="paged", impl=impl)
        be.prefill([0, 1], prompts, [8, 5])
        events = be.decode_step({0: 3, 1: 4})
        logits[impl] = np.stack([np.asarray(e.logits) for e in events])
    assert np.isfinite(logits["xla"]).all()
    np.testing.assert_allclose(logits["pallas"], logits["xla"],
                               rtol=2e-2, atol=2e-2)


def test_pool_exhaustion_preempts_and_resumes_identically():
    """With a pool too small for all concurrent streams, serving preempts
    (recompute-on-resume) yet every request's tokens match an uninterrupted
    contiguous run; the pool drains back to full afterwards."""
    from repro.serving import SamplingParams
    cfg, ref_llm = _tiny_llm("contiguous", n_slots=3, max_len=32)
    # 3 slots x 2 worst-case blocks = 6; a 4-block pool must overcommit
    _, llm = _tiny_llm("paged", num_blocks=4, n_slots=3, max_len=32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9, 4, 7, 5)]
    sp = SamplingParams(max_tokens=12)
    ref = ref_llm.generate(prompts, sp)
    outs = llm.generate(prompts, sp)
    assert llm.stats.preemptions > 0
    assert llm.stats.resumes > 0
    for o, r in zip(outs, ref):
        assert o.tokens == r.tokens, (o.uid, o.tokens, r.tokens)
    preempted = [o for o in outs if o.timing.preemptions]
    assert preempted, "per-request preemption count must be surfaced"
    assert llm.backend.pager.free_blocks == llm.backend.pager.total_blocks


# --------------------------------------------------------------------------- #
# windowed attention: ring semantics + the window > max_len clamp
# --------------------------------------------------------------------------- #


def _windowed_cfg(window):
    import dataclasses as dc
    from repro.configs import get_config
    cfg = get_config("gemma2-2b").reduced(n_layers=4)
    pattern = tuple(dc.replace(s, window=window) if s.window else s
                    for s in cfg.pattern)
    return dc.replace(cfg, pattern=pattern)


def test_windowed_ring_semantics_preserved():
    """Sliding-window layers keep ring-buffer eviction under paging: long
    generations that wrap the window match the contiguous layout exactly."""
    from repro.serving import SamplingParams
    cfg = _windowed_cfg(window=16)
    _, contig = _tiny_llm("contiguous", cfg=cfg)
    _, paged = _tiny_llm("paged", cfg=cfg)
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 6).astype(np.int32)
    sp = SamplingParams(max_tokens=40)      # wraps the 16-token window twice
    [a] = contig.generate([prompt], sp)
    [b] = paged.generate([prompt], sp)
    assert a.tokens == b.tokens


def test_window_larger_than_max_len_clamps_consistently():
    """Regression (ISSUE 3 bugfix): a window wider than max_len silently
    clamps to max_len — the paged pool, ``blocks_for_len``, and
    ``cache_bytes_per_slot`` must all account at the *clamped* length, and
    decode parity must hold through the clamp."""
    from repro.models import kvcache as KV
    from repro.serving import SamplingParams
    cfg = _windowed_cfg(window=128)         # max_len below is 32
    for spec in cfg.pattern:
        assert KV.attn_cache_len(spec, 32) == 32
        assert KV.paged_cache_len(spec, 32, 16) == 32
    assert KV.max_ctx_blocks(cfg, 32, 16) == 2      # ceil(32/16), not 128/16
    _, contig = _tiny_llm("contiguous", max_len=32, cfg=cfg)
    _, paged = _tiny_llm("paged", max_len=32, cfg=cfg)
    info = paged.backend.info
    assert info.max_ctx_blocks == 2
    # the pool was provisioned for the clamped window, so worst-case
    # per-slot demand == blocks_for_len(max_len), and the two layouts
    # agree on per-slot bytes up to block-rounding + scratch overhead
    assert info.blocks_for_len(10 ** 9) == 2
    assert info.total_blocks == 2 * 2               # n_slots * clamped blocks
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, 6).astype(np.int32)
    sp = SamplingParams(max_tokens=20)
    [a] = contig.generate([prompt], sp)
    [b] = paged.generate([prompt], sp)
    assert a.tokens == b.tokens


def test_key_pos_masked_tail_when_cache_len_unaligned():
    """When the clamped cache length is not a block multiple the gathered
    width rounds up; the tail stays masked (never attended) so outputs still
    match the contiguous ring exactly."""
    from repro.models import kvcache as KV
    from repro.serving import SamplingParams
    cfg = _windowed_cfg(window=16)
    spec = cfg.pattern[0]
    # 24-token max_len: full-attn layers pad 24 -> 32 gathered width
    assert KV.attn_cache_len(dataclasses.replace(spec, window=None), 24) == 24
    assert KV.paged_cache_len(dataclasses.replace(spec, window=None),
                              24, 16) == 32
    _, contig = _tiny_llm("contiguous", max_len=24, cfg=cfg)
    _, paged = _tiny_llm("paged", max_len=24, cfg=cfg)
    prompt = np.random.default_rng(4).integers(
        0, cfg.vocab_size, 5).astype(np.int32)
    sp = SamplingParams(max_tokens=16)
    [a] = contig.generate([prompt], sp)
    [b] = paged.generate([prompt], sp)
    assert a.tokens == b.tokens


# --------------------------------------------------------------------------- #
# prefill: one one-row program per request of a wave
# --------------------------------------------------------------------------- #

N_SLOTS = 5
WIDE = 256
WAVE_MAX_LEN = WIDE + 16


@pytest.fixture(scope="module")
def wave_model():
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _wave_backend(wave_model, layout, num_blocks=None):
    from repro.runtime import TensorBackend
    cfg, params = wave_model
    return TensorBackend(cfg, params, n_slots=N_SLOTS, max_len=WAVE_MAX_LEN,
                         cache_layout=layout, num_blocks=num_blocks)


def _wave(cfg, k, width, seed=0):
    """k left-padded prompts of distinct true lengths in a ``width`` bucket."""
    rng = np.random.default_rng(seed)
    lens = np.array([width - width // 8 * i for i in range(k)], np.int32)
    prompts = np.zeros((k, width), np.int32)
    for i, n in enumerate(lens):
        prompts[i, width - n:] = rng.integers(1, cfg.vocab_size, n)
    return prompts, lens


def _slot_state(be, slot):
    """The slot's cache contents: its pool blocks (paged) or storage row."""
    import jax
    if be.pager is None:
        return [np.asarray(x[slot]) for x in jax.tree.leaves(be.caches)]
    blocks = be.pager.table[slot, :int(be.pager.n_alloc[slot])]
    return [np.asarray(c[name][:, blocks if name.endswith("_pool") else slot])
            for c in be.caches["stack"].values()
            for name in ("k_pool", "v_pool", "key_pos", "pos")]


def _spy(be):
    """Record the rows of every prefill and scatter program ``be`` runs."""
    rows = {"prefill": [], "scatter": []}
    prefill, scatter = be._prefill_fn, be._scatter_fn

    def prefill_spy(params, tokens, **kw):
        rows["prefill"].append(tokens.shape[0])
        return prefill(params, tokens, **kw)

    def scatter_spy(storage, new, idx, *rest):
        rows["scatter"].append(idx.shape[0])
        return scatter(storage, new, idx, *rest)
    be._prefill_fn, be._scatter_fn = prefill_spy, scatter_spy
    return rows, prefill, scatter


@pytest.mark.parametrize("k", range(1, N_SLOTS + 1))
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_wide_wave_matches_each_prompt_alone(wave_model, layout, k):
    """A k-row wave at a wide width gives each request the last-position
    logits, cache contents and greedy tokens of its prompt prefilled
    alone, with one one-row program per request."""
    cfg, _ = wave_model
    prompts, lens = _wave(cfg, k, WIDE, seed=k)
    slots = list(range(N_SLOTS - k, N_SLOTS))
    wave, alone = (_wave_backend(wave_model, layout) for _ in range(2))
    rows, _, _ = _spy(wave)
    got = wave.prefill(slots, prompts, lens)
    assert rows == {"prefill": [1] * k, "scatter": [1] * k}
    want = [alone.prefill([s], prompts[i:i + 1], lens[i:i + 1])[0]
            for i, s in enumerate(slots)]
    for g, w in zip(got, want):
        assert g.slot == w.slot
        np.testing.assert_allclose(g.logits, w.logits, rtol=1e-5, atol=1e-5)
        assert int(np.argmax(g.logits)) == int(np.argmax(w.logits))
    for s in slots:
        for a, b in zip(_slot_state(wave, s), _slot_state(alone, s)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    feeds = {e.slot: int(np.argmax(e.logits)) for e in want}
    for _ in range(2):
        g = wave.decode_step(feeds)
        w = alone.decode_step(feeds)
        assert [int(np.argmax(e.logits)) for e in g] == \
            [int(np.argmax(e.logits)) for e in w]
        feeds = {e.slot: int(np.argmax(e.logits)) for e in w}


@pytest.mark.parametrize("width", [WIDE, 8])
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_waves_add_no_program_shape(wave_model, layout, width):
    """After one one-row prefill per width (the benchmark's warm-up), waves
    of 2..n_slots rows compile nothing new: every wave, wide or narrow,
    runs one-row programs."""
    cfg, _ = wave_model
    be = _wave_backend(wave_model, layout)
    rows, prefill, scatter = _spy(be)
    be.prefill([0], *_wave(cfg, 1, width))
    be.free_slot(0)
    compiled = (prefill._cache_size(), scatter._cache_size())
    assert compiled == (1, 1)
    for k in range(2, N_SLOTS + 1):
        be.prefill(list(range(k)), *_wave(cfg, k, width))
        for s in range(k):
            be.free_slot(s)
    assert (prefill._cache_size(), scatter._cache_size()) == compiled
    n_rows = sum(range(1, N_SLOTS + 1))      # the 1-row warm-up, then 2..5
    assert rows["prefill"] == rows["scatter"] == [1] * n_rows


@pytest.mark.parametrize("k", range(1, N_SLOTS + 1))
def test_pool_exhausted_wide_wave_mutates_nothing(wave_model, k):
    """A k-row wide wave the pool cannot hold raises PoolExhausted before
    any program runs: the pager and the pool are as they were."""
    import jax
    cfg, _ = wave_model
    blocks = WIDE // 16
    resident = k < N_SLOTS
    be = _wave_backend(wave_model, "paged",
                       num_blocks=blocks * k - 1 + int(resident))
    if resident:                    # one short request already decoding
        be.prefill([N_SLOTS - 1], np.arange(1, 9, dtype=np.int32)[None], [8])
    pager = (be.pager.table.copy(), be.pager.n_alloc.copy(),
             be.pager.free_blocks)
    pool = [np.asarray(x) for x in jax.tree.leaves(be.caches)]
    prompts, _ = _wave(cfg, k, WIDE)
    with pytest.raises(PoolExhausted):
        be.prefill(list(range(k)), prompts, [WIDE] * k)
    np.testing.assert_array_equal(be.pager.table, pager[0])
    np.testing.assert_array_equal(be.pager.n_alloc, pager[1])
    assert be.pager.free_blocks == pager[2]
    for a, b in zip(jax.tree.leaves(be.caches), pool):
        np.testing.assert_array_equal(np.asarray(a), b)
