"""Backend-conformance suite: one parametrized contract check run against
every ``InferenceBackend`` × cache layout combination.

The contract under test (``runtime/base.py`` + docs/runtime.md):

- slot lifecycle: prefill into free slots, recycle released slots, tolerate
  quanta between free and re-prefill;
- ``BackendInfo`` accounting invariants (contiguous and paged);
- greedy decode parity: paged and contiguous layouts produce token-identical
  outputs for identical prompts/seeds;
- determinism under slot permutation: a request's tokens do not depend on
  which slot serves it or who shares the batch.

Real-model backends run a tiny qwen3 on CPU; multi-device pipeline variants
re-exec in a subprocess with fake XLA devices (same pattern as
test_runtime.py).  SimBackend rows run jax-free.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LEN = 32
GEN = 5


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
# backend builders (lazy: jax only when a real backend is requested)
# --------------------------------------------------------------------------- #

def _tiny_cfg_params():
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def make_backend(kind: str, layout: str, n_slots: int = 3, impl: str = "xla"):
    if kind == "tensor":
        from repro.runtime import TensorBackend
        cfg, params = _tiny_cfg_params()
        return cfg, TensorBackend(cfg, params, n_slots=n_slots,
                                  max_len=MAX_LEN, cache_layout=layout,
                                  impl=impl)
    if kind == "sim":
        from repro.core.simulator import StageCosts
        from repro.runtime import SimBackend
        costs = StageCosts(prefill=np.array([.01, .02]),
                           decode=np.array([.001, .002]),
                           comm_prefill=np.array([.001]),
                           comm_decode=np.array([.0001]),
                           return_comm=.0001)
        return None, SimBackend(costs, n_slots=n_slots, max_len=MAX_LEN,
                                cache_layout=layout,
                                num_blocks=n_slots * (MAX_LEN // 16))
    raise ValueError(kind)


def serve_prompts(backend, prompts, uids=None, gen=GEN, seed=0,
                  min_bucket=1, return_batcher=False):
    """Greedy-serve prompts; returns {uid: tokens}."""
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    b = ContinuousBatcher(backend, seed=seed, min_bucket=min_bucket)
    uids = uids if uids is not None else list(range(len(prompts)))
    for uid, p in zip(uids, prompts):
        b.submit(Request(np.asarray(p, np.int32),
                         SamplingParams(max_tokens=gen), uid=uid))
    done = b.run()
    assert sorted(done) == sorted(uids)
    out = {u: done[u].generated for u in uids}
    return (out, b) if return_batcher else out


def greedy_exact(backend, prompt, gen=GEN):
    """Unbatched exact-length serial reference: drive the backend directly
    with an unpadded single prompt (no batcher, no bucketing, no pads)."""
    toks, feeds = [], {}

    def absorb(evs):
        for ev in evs:
            toks.append(int(np.argmax(ev.logits)) if ev.logits is not None
                        else int(ev.token))
            feeds[0] = toks[-1]

    absorb(backend.prefill([0], np.asarray(prompt, np.int32)[None, :]))
    for _ in range(100 * gen):              # pipelined backends skew
        if len(toks) >= gen:
            break
        absorb(backend.decode_step(feeds))
    assert len(toks) >= gen, toks
    backend.free_slot(0)
    return toks[:gen]


KINDS = [("tensor", "contiguous"), ("tensor", "paged"),
         ("sim", "contiguous"), ("sim", "paged")]


# --------------------------------------------------------------------------- #
# slot lifecycle: acquire / release / recycle
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("kind,layout", KINDS)
def test_slot_acquire_release_recycle(kind, layout):
    """More requests than slots: every slot is recycled at least once, every
    request finishes, and (paged) all blocks return to the pool."""
    cfg, backend = make_backend(kind, layout, n_slots=2)
    vocab = cfg.vocab_size if cfg else 100
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in (4, 6, 3, 5, 7)]
    outs = serve_prompts(backend, prompts)
    assert all(len(t) == GEN for t in outs.values())
    info = backend.info
    if info.paged:
        assert info.free_blocks == info.total_blocks, \
            "released slots must return every block to the pool"


@pytest.mark.parametrize("kind,layout", KINDS)
def test_free_slot_tolerates_quanta_before_reuse(kind, layout):
    """The protocol requires backends to tolerate decode quanta between
    free_slot and the next prefill of that slot."""
    cfg, backend = make_backend(kind, layout, n_slots=2)
    vocab = cfg.vocab_size if cfg else 100
    rng = np.random.default_rng(1)
    evs = backend.prefill([0, 1], rng.integers(0, vocab, (2, 4)).astype(np.int32))
    feeds = {0: 1, 1: 2}
    for _ in range(4):
        for e in backend.decode_step(feeds):
            tok = e.token if e.token is not None else int(np.argmax(e.logits))
            feeds[e.slot] = int(tok)
    backend.free_slot(0)
    del feeds[0]
    for _ in range(3):                      # quanta with a freed slot
        backend.decode_step(feeds)
    # recycling the freed slot still works
    backend.prefill([0], rng.integers(0, vocab, (1, 4)).astype(np.int32))


# --------------------------------------------------------------------------- #
# BackendInfo accounting invariants
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("kind,layout", KINDS)
def test_backend_info_invariants(kind, layout):
    cfg, backend = make_backend(kind, layout)
    info = backend.info
    assert info.n_slots == 3
    assert info.cache_bytes == info.n_slots * info.cache_bytes_per_slot
    assert info.paged == (layout == "paged")
    if layout == "paged":
        assert info.block_size > 0 and info.total_blocks > 0
        assert 0 <= info.free_blocks <= info.total_blocks
        assert info.blocks_per_token == pytest.approx(1 / info.block_size)
        # blocks_for_len: ceil-div, clamped at max_ctx_blocks
        assert info.blocks_for_len(1) == 1
        assert info.blocks_for_len(info.block_size) == 1
        assert info.blocks_for_len(info.block_size + 1) == 2
        assert info.blocks_for_len(10 ** 9) == info.max_ctx_blocks
    else:
        assert info.block_size == 0 and info.total_blocks == 0
        assert info.blocks_for_len(100) == 0


def test_paged_info_not_worst_case():
    """Acceptance: with an overcommitted pool, the paged layout's
    cache_bytes_per_slot is the provisioned share — strictly below the
    contiguous worst-case max_len figure."""
    from repro.runtime import TensorBackend
    cfg, params = _tiny_cfg_params()
    contig = TensorBackend(cfg, params, n_slots=4, max_len=MAX_LEN)
    half = 4 * (MAX_LEN // 16) // 2
    paged = TensorBackend(cfg, params, n_slots=4, max_len=MAX_LEN,
                          cache_layout="paged", num_blocks=half)
    assert paged.info.cache_bytes_per_slot < contig.info.cache_bytes_per_slot
    # and the dominant pool storage scales with blocks, not slots*max_len
    assert paged.info.bytes_per_block * paged.info.total_blocks < \
        contig.info.cache_bytes


# --------------------------------------------------------------------------- #
# greedy decode parity: paged <-> contiguous (acceptance criterion)
# --------------------------------------------------------------------------- #

def test_tensor_paged_contiguous_parity():
    cfg, backend_c = make_backend("tensor", "contiguous")
    _, backend_p = make_backend("tensor", "paged")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 8, 5, 6, 4)]
    a = serve_prompts(backend_c, prompts)
    b = serve_prompts(backend_p, prompts)
    assert a == b
    assert len(np.unique([t for ts in a.values() for t in ts])) > 2, \
        "degenerate reference"


def test_tensor_impl_parity_paged_pallas():
    """Acceptance: greedy decode is token-identical across contiguous-pallas,
    paged-xla, and paged-pallas — the fused block-table kernel (interpreted
    on CPU) must be a pure dataflow change, not a semantic one."""
    cfg, _ = _tiny_cfg_params()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 8, 5, 6, 4)]
    outs = {}
    for name, (layout, impl) in {
            "contiguous-pallas": ("contiguous", "pallas"),
            "paged-xla": ("paged", "xla"),
            "paged-pallas": ("paged", "pallas")}.items():
        _, backend = make_backend("tensor", layout, impl=impl)
        outs[name] = serve_prompts(backend, prompts)
    assert outs["contiguous-pallas"] == outs["paged-xla"] \
        == outs["paged-pallas"], outs
    assert len(np.unique([t for ts in outs["paged-pallas"].values()
                          for t in ts])) > 2, "degenerate reference"


@pytest.mark.slow
def test_pipeline_paged_contiguous_parity():
    """Acceptance: paged and contiguous layouts match token-for-token on the
    no-bubbles PipelineBackend too (subprocess: needs multiple devices)."""
    run_subprocess("""
import jax, numpy as np
from repro.configs import get_config
from repro.core import pipeline as PL
from repro.models import transformer as T
from repro.runtime import PipelineBackend, TensorBackend
from repro.serving import ContinuousBatcher, Request, SamplingParams
from repro.sharding import make_mesh

cfg = get_config("qwen3-0.6b").reduced(n_layers=4)
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
spec = PL.even_pipeline_spec(cfg, 2)
mesh = make_mesh((1, 2), ("data", "model"))
rng = np.random.default_rng(0)
prompts = rng.integers(0, cfg.vocab_size, (5, 6)).astype(np.int32)

def serve(be):
    b = ContinuousBatcher(be)
    for uid in range(5):
        b.submit(Request(prompts[uid], SamplingParams(max_tokens=5), uid=uid))
    done = b.run()
    return [done[u].generated for u in range(5)]

tens = serve(TensorBackend(cfg, params, n_slots=3, max_len=32))
contig = serve(PipelineBackend(cfg, params, spec, mesh, n_slots=3,
                               max_len=32))
paged = serve(PipelineBackend(cfg, params, spec, mesh, n_slots=3, max_len=32,
                              cache_layout="paged"))
pallas = serve(PipelineBackend(cfg, params, spec, mesh, n_slots=3, max_len=32,
                               cache_layout="paged", impl="pallas"))
assert contig == paged, (contig, paged)
assert tens == paged, (tens, paged)     # and across backends
assert paged == pallas, (paged, pallas) # fused block-table kernel in the tick
print("pipeline parity OK")
""")


# --------------------------------------------------------------------------- #
# determinism under slot permutation
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_tensor_determinism_under_slot_permutation(layout):
    """A request's greedy tokens must not depend on submission order, slot
    assignment, or batch companions (same-bucket prompts so padding is
    identical across runs)."""
    cfg, backend_a = make_backend("tensor", layout)
    rng = np.random.default_rng(4)
    prompts = {uid: rng.integers(0, cfg.vocab_size, 5 + uid % 3
                                 ).astype(np.int32) for uid in range(5)}
    a = serve_prompts(backend_a, [prompts[u] for u in range(5)],
                      uids=list(range(5)))
    _, backend_b = make_backend("tensor", layout, n_slots=2)  # other layout
    order = [3, 1, 4, 0, 2]
    b = serve_prompts(backend_b, [prompts[u] for u in order], uids=order)
    assert a == b


# --------------------------------------------------------------------------- #
# bucket invariance: pad tokens must not change outputs (acceptance criterion)
# --------------------------------------------------------------------------- #

BUCKET_LENS = (1, 3, 5, 8, 13)      # crosses buckets 1/2/4/8/16 at min_bucket=1


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_tensor_bucket_invariance(layout):
    """Masked prefill makes length bucketing semantically neutral: the same
    prompts produce token-identical outputs for min_bucket in {1, 8, 64}
    (64 > max_len exercises the bucket cap) AND match an unbatched
    exact-length serial run with no padding at all."""
    rng = np.random.default_rng(6)
    cfg, _ = make_backend("tensor", layout)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in BUCKET_LENS]
    runs = {}
    for mb in (1, 8, 64):
        _, backend = make_backend("tensor", layout)
        runs[mb], b = serve_prompts(backend, prompts, min_bucket=mb,
                                    return_batcher=True)
        floor = min(mb, MAX_LEN)
        assert all(s >= floor for s in b.stats.prefill_shapes), \
            (mb, b.stats.prefill_shapes)
    assert runs[1] == runs[8] == runs[64], runs
    assert len(np.unique([t for ts in runs[1].values() for t in ts])) > 2, \
        "degenerate reference"
    # exact-length unpadded serial reference, one request at a time
    for uid, p in enumerate(prompts):
        _, backend = make_backend("tensor", layout, n_slots=1)
        assert greedy_exact(backend, p) == runs[1][uid], uid


def test_tensor_submit_accepts_request_near_context_limit():
    """Regression: the submit-time capacity check must use the TRUE prompt
    length, not the padded bucket — a prompt whose unpadded length +
    max_tokens fits max_len exactly is admissible and serves fully."""
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    cfg, backend = make_backend("tensor", "contiguous", n_slots=1)
    rng = np.random.default_rng(8)
    plen, gen = MAX_LEN - GEN + 1, GEN          # plen + gen - 1 == max_len
    assert (1 << (plen - 1).bit_length()) + gen - 1 > MAX_LEN, \
        "the padded bucket would overflow: the old check rejected this"
    b = ContinuousBatcher(backend)
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
    b.submit(Request(prompt, SamplingParams(max_tokens=gen), uid=0))
    done = b.run()
    assert len(done[0].generated) == gen
    assert done[0].finish_reason == "length"


@pytest.mark.slow
def test_pipeline_bucket_invariance():
    """Bucket invariance on the no-bubbles pipeline (pads are stripped at
    admission): min_bucket in {1, 8, 64} identical, equal to TensorBackend
    and to the unbatched exact-length serial run (subprocess: devices)."""
    run_subprocess("""
import jax, numpy as np
from repro.configs import get_config
from repro.core import pipeline as PL
from repro.models import transformer as T
from repro.runtime import PipelineBackend, TensorBackend
from repro.serving import ContinuousBatcher, Request, SamplingParams
from repro.sharding import make_mesh

cfg = get_config("qwen3-0.6b").reduced(n_layers=4)
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
spec = PL.even_pipeline_spec(cfg, 2)
mesh = make_mesh((1, 2), ("data", "model"))
rng = np.random.default_rng(0)
lens = (1, 3, 5, 8, 13)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]

def serve(be, min_bucket):
    b = ContinuousBatcher(be, min_bucket=min_bucket)
    for uid, p in enumerate(prompts):
        b.submit(Request(p, SamplingParams(max_tokens=5), uid=uid))
    done = b.run()
    return [done[u].generated for u in range(len(prompts))]

def pipe(layout):
    return lambda mb: serve(PipelineBackend(
        cfg, params, spec, mesh, n_slots=3, max_len=32,
        cache_layout=layout), mb)

for layout in ("contiguous", "paged"):
    runs = {mb: pipe(layout)(mb) for mb in (1, 8, 64)}
    assert runs[1] == runs[8] == runs[64], (layout, runs)

tens = serve(TensorBackend(cfg, params, n_slots=3, max_len=32), 1)
assert tens == pipe("contiguous")(1), "pipeline != tensor under min_bucket=1"

# unbatched exact-length serial reference over the pipeline itself
be = PipelineBackend(cfg, params, spec, mesh, n_slots=2, max_len=32)
for uid, p in enumerate(prompts):
    toks, feeds = [], {}
    def absorb(evs):
        for ev in evs:
            toks.append(int(np.argmax(ev.logits)) if ev.logits is not None
                        else int(ev.token))
            feeds[0] = toks[-1]
    absorb(be.prefill([0], p[None, :]))
    while len(toks) < 5:
        absorb(be.decode_step(feeds))
    be.free_slot(0)
    assert toks[:5] == tens[uid], (uid, toks, tens[uid])
print("bucket invariance OK")
""")


def test_preempt_resume_across_bucket_boundary():
    """Preempt -> resume where the resume prefix crosses a power-of-two
    bucket boundary: outputs still match an uninterrupted contiguous run,
    and every resume prefill shape is a shared bucket (no per-length XLA
    shapes — the ROADMAP follow-up unlocked by masked prefill)."""
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    rng = np.random.default_rng(9)
    cfg, ref_backend = make_backend("tensor", "contiguous")
    # prompts of length 6 (bucket 8) generating 12 tokens: any preemption
    # after 3 generated tokens resumes with a prefix in bucket 16
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(5)]
    ref = {}
    for uid, p in enumerate(prompts):       # serial uninterrupted reference
        _, be = make_backend("tensor", "contiguous", n_slots=1)
        ref[uid] = greedy_exact(be, p, gen=12)
    import jax
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    # 8-token blocks: the first boundary falls at position 8, so a length-6
    # (bucket-8) request preempted there resumes with a 9..16-token prefix
    # — squarely in the NEXT bucket (16)
    backend = TensorBackend(cfg, params, n_slots=3, max_len=MAX_LEN,
                            cache_layout="paged", block_size=8, num_blocks=4)
    outs, b = serve_prompts(backend, prompts, gen=12, return_batcher=True)
    assert b.stats.preemptions > 0 and b.stats.resumes > 0, \
        "a 4-block pool under this demand must preempt"
    assert outs == ref
    pow2_or_cap = {1 << i for i in range(12)} | {MAX_LEN}
    assert set(b.stats.prefill_shapes) <= pow2_or_cap, \
        f"resume prefills must reuse bucketed shapes: {b.stats.prefill_shapes}"
    assert 8 in b.stats.prefill_shapes and 16 in b.stats.prefill_shapes, \
        f"expected a resume crossing the 8->16 bucket boundary: " \
        f"{b.stats.prefill_shapes}"
