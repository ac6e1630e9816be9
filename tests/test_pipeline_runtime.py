"""EdgeShard shard_map pipeline runtime vs single-device reference.

These tests need >1 XLA device, so they re-exec themselves in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the flag must be set
before jax initializes, and the main test process must keep seeing 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

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


COMMON = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.core import pipeline as PL
from repro.sharding import make_mesh
cfg = get_config("qwen3-0.6b").reduced(n_layers=6)
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
mesh = make_mesh((2, 4), ("data", "model"))
"""


def test_stage_params_built_on_their_own_devices():
    """init_params_on_mesh builds each leaf in place (same values as a
    jitted init_params); with a mesh, stack_stage_params puts each stage's
    slab on that stage's devices only, equal to the traceable restack."""
    run_subprocess(COMMON + """
sharded = T.init_params_on_mesh(cfg, jax.random.PRNGKey(0), mesh)
plain = jax.jit(lambda k: T.init_params(cfg, k)[0])(jax.random.PRNGKey(0))
for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(plain)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
wq = sharded["stack"]["p0"]["mixer"]["wq"]
assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 4
spec = PL.PipelineSpec(4, (1, 2, 2, 1))
want, mask = PL.stack_stage_params(cfg, plain, spec)
got, mask2 = PL.stack_stage_params(cfg, sharded, spec, mesh=mesh)
np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask2))
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
stage_of = {d: i for i, d in enumerate(mesh.devices[0])}
stage_of.update({d: i for i, d in enumerate(mesh.devices[1])})
for leaf in jax.tree.leaves(got["stack"]):
    for shard in leaf.addressable_shards:
        s = stage_of[shard.device]
        assert shard.index[0] == slice(s, s + 1), (shard.index, s)
""")


@pytest.mark.slow
def test_pipeline_forward_matches_reference_uneven_stages():
    run_subprocess(COMMON + """
spec = PL.PipelineSpec(4, (1, 2, 2, 1))
stage_params, mask = PL.stack_stage_params(cfg, params, spec)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)
ref, _, _ = T.forward(cfg, params, tokens, mode="train")
with mesh:
    out = PL.pipeline_forward(cfg, stage_params, mask, tokens, spec, mesh,
                              n_microbatches=4)
np.testing.assert_allclose(np.asarray(out, np.float32),
                           np.asarray(ref, np.float32), rtol=3e-4, atol=3e-4)
""")


@pytest.mark.slow
def test_pipeline_forward_other_stage_layouts():
    run_subprocess(COMMON + """
for sizes in [(3, 1, 1, 1), (1, 1, 1, 3), (2, 2, 1, 1)]:
    spec = PL.PipelineSpec(4, sizes)
    stage_params, mask = PL.stack_stage_params(cfg, params, spec)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, cfg.vocab_size)
    ref, _, _ = T.forward(cfg, params, tokens, mode="train")
    with mesh:
        out = PL.pipeline_forward(cfg, stage_params, mask, tokens, spec, mesh,
                                  n_microbatches=2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-4, atol=3e-4)
""")


@pytest.mark.slow
def test_pipeline_decode_matches_reference_with_diverse_streams():
    """Feed externally-chosen random tokens so each micro-batch builds a
    distinct KV history; sampled outputs must match per-mb references."""
    run_subprocess(COMMON + """
spec = PL.PipelineSpec(4, (2, 1, 2, 1))
stage_params, mask = PL.stack_stage_params(cfg, params, spec)
M, mb, max_len, gen = 4, 2, 32, 6
rng = np.random.default_rng(0)
feeds = rng.integers(0, cfg.vocab_size, size=(M, gen, mb)).astype(np.int32)

ref_tokens = []
for m in range(M):
    caches = T.init_caches(cfg, batch=mb, max_len=max_len, dtype=jnp.float32)
    seq = []
    for g in range(gen):
        logits, caches = T.decode_step(cfg, params, jnp.asarray(feeds[m, g]), caches)
        seq.append(np.asarray(jnp.argmax(logits, -1)))
    ref_tokens.append(np.stack(seq))
ref_tokens = np.stack(ref_tokens)

with mesh:
    state = PL.init_pipeline_decode_state(cfg, spec, M, mb, max_len,
                                          dtype=jnp.float32)
    rounds = {m: 0 for m in range(M)}
    got = {m: [] for m in range(M)}
    for t in range(M * gen + spec.n_stages + 4):
        f = t % M
        feed = jnp.asarray(feeds[f, min(rounds[f], gen - 1)])
        rounds[f] += 1
        state = PL.pipeline_decode_tick(cfg, stage_params, mask, state, feed,
                                        spec, mesh)
        dm = (t - (spec.n_stages - 1)) % M
        if t >= spec.n_stages - 1 and len(got[dm]) < gen:
            got[dm].append(np.argmax(np.asarray(state.logits_out[dm]),
                                     -1).astype(np.int32))
        if all(len(got[m]) >= gen for m in range(M)):
            break
pipe_tokens = np.stack([np.stack(got[m][:gen]) for m in range(M)])
assert len(np.unique(ref_tokens)) > 2, "degenerate reference"
np.testing.assert_array_equal(pipe_tokens, ref_tokens)
""")


@pytest.mark.slow
def test_moe_expert_parallel_matches_ragged():
    """EP all_to_all path == dropless ragged path (capacity generous)."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.models import transformer as T, moe as M
from repro.sharding.rules import make_mesh, use_mesh
cfg = get_config("granite-moe-1b-a400m").reduced(n_layers=2)
moe = cfg.pattern[0].moe
assert moe is not None and moe.num_experts % 4 == 0
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
moe_params = params["stack"]["p0"]["ffn"]
moe_params = jax.tree.map(lambda x: x[0], moe_params)
x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
y_ragged, aux_r = M.moe_ragged(moe_params, moe, x)
mesh = make_mesh((2, 4), ("data", "model"))
with use_mesh(mesh):
    y_ep, aux_e = M.moe_ep(moe_params, moe, x, capacity_factor=8.0)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ragged),
                           rtol=2e-4, atol=2e-4)
""")


@pytest.mark.slow
def test_full_model_pjit_sharded_matches_unsharded():
    """Whole-model forward under a (data, model) mesh with sharding
    constraints == unsharded forward (MoE uses the EP path)."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.sharding.rules import make_mesh, use_mesh, param_sharding_tree
for name in ["qwen3-0.6b", "granite-moe-1b-a400m", "gemma2-2b"]:
    cfg = get_config(name).reduced(n_layers=4)
    params, axes = T.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    ref, _, _ = T.forward(cfg, params, tokens, mode="train")
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        shardings = param_sharding_tree(axes)
        params_s = jax.device_put(params, shardings)
        fn = jax.jit(lambda p, t: T.forward(cfg, p, t, mode="train")[0])
        out = fn(params_s, tokens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-4, atol=5e-4)
    print(name, "sharded OK")
""")


@pytest.mark.slow
def test_pipeline_decode_vocab_sharded_matches_plain():
    """§Perf-C2: stage-axis vocab-sharded embed/head tick == plain tick
    (embedding psum reconstruction + scatter/psum logits reassembly).

    The returned full logits are compared elementwise — a strictly
    stronger check than the argmax equality the pre-logits-ring version
    used, and free of that version's flakiness on near-tied logits."""
    run_subprocess(COMMON + """
spec = PL.PipelineSpec(4, (2, 1, 2, 1))
assert cfg.vocab_size % spec.n_stages == 0
stage_params, mask = PL.stack_stage_params(cfg, params, spec)
M, mb, max_len = 4, 2, 32
rng = np.random.default_rng(0)
with mesh:
    s_plain = PL.init_pipeline_decode_state(cfg, spec, M, mb, max_len,
                                            dtype=jnp.float32)
    s_vs = PL.init_pipeline_decode_state(cfg, spec, M, mb, max_len,
                                         dtype=jnp.float32)
    for t in range(12):
        feed = jnp.asarray(rng.integers(0, cfg.vocab_size, mb), jnp.int32)
        s_plain = PL.pipeline_decode_tick(cfg, stage_params, mask, s_plain,
                                          feed, spec, mesh)
        s_vs = PL.pipeline_decode_tick(cfg, stage_params, mask, s_vs,
                                       feed, spec, mesh, vocab_sharded=True)
    np.testing.assert_array_equal(np.asarray(s_plain.token_ready),
                                  np.asarray(s_vs.token_ready))
    np.testing.assert_allclose(np.asarray(s_plain.logits_out),
                               np.asarray(s_vs.logits_out),
                               rtol=2e-3, atol=2e-3)
    for a, b in zip(jax.tree.leaves(s_plain.caches),
                    jax.tree.leaves(s_vs.caches)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-4)
    assert len(np.unique(np.argmax(np.asarray(s_vs.logits_out), -1))) > 1
""")


def test_spec_from_plan_property():
    """Any DP plan (arbitrary contiguous stage sizes) maps to a valid
    PipelineSpec: all periods covered, n_stages respected."""
    import numpy as np
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.configs import get_config
    from repro.core.partition import Plan
    from repro.core.pipeline import spec_from_plan

    cfg = get_config("starcoder2-7b")           # 32 homogeneous layers

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.integers(1, 20), min_size=1, max_size=8),
           st.integers(2, 16))
    def body(sizes, n_stages):
        # build a contiguous assignment over units [embed + 32 blocks + head]
        n_units = cfg.n_layers + 2
        sizes = np.asarray(sizes, float)
        bounds = np.cumsum(sizes / sizes.sum() * n_units).astype(int)
        bounds[-1] = n_units
        assignment = np.zeros(n_units, int)
        start = 0
        for dev, end in enumerate(bounds):
            assignment[start:end] = dev
            start = end
        plan = Plan(assignment, 1.0, "throughput")
        spec = spec_from_plan(cfg, plan, n_stages)
        assert spec.n_stages == n_stages
        assert spec.n_periods == cfg.n_full_periods
        assert all(p >= 0 for p in spec.periods_per_stage)

    body()
