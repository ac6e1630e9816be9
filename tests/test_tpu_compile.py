"""Compile guards: the main-path Pallas kernels at qwen3-0.6b widths, in
bf16, compiled (``interpret=False``) for a described TPU v5e.

Interpret-mode tests validate the kernel bodies but cannot see the TPU
lowering's tiling rules or its VMEM limit; these compiles can.  Nothing
runs — the compiler only has to accept each kernel and emit a
``tpu_custom_call``.  The topology is described inside a module fixture
(never at import), so every pytest-xdist worker collects the same tests and
only the worker given this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

CFG = get_config("qwen3-0.6b")
H, KH, D = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
BS = 16                        # KV block size of the paged pool
SLOTS, MAX_LEN = 8, 512
NBS = MAX_LEN // BS            # blocks per slot
NB = SLOTS * NBS               # pool blocks (+1 scratch)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)
    return make


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_compiles(shape, cache_dtype):
    """bf16 queries against a bf16 pool, and against the float32 pool the
    serving path allocates by default."""
    pool = shape((NB + 1, BS, KH, D), cache_dtype)
    _assert_kernel(ops.paged_decode_attention.lower(
        shape((SLOTS, H, D), jnp.bfloat16), pool, pool,
        shape((SLOTS, NBS), jnp.int32), shape((SLOTS, MAX_LEN), jnp.int32),
        shape((SLOTS,), jnp.int32), interpret=False))


def test_paged_verify_compiles(shape):
    pool = shape((NB + 1, BS, KH, D), jnp.bfloat16)
    _assert_kernel(ops.paged_verify_attention.lower(
        shape((SLOTS, 4, H, D), jnp.bfloat16), pool, pool,
        shape((SLOTS, NBS), jnp.int32), shape((SLOTS, MAX_LEN), jnp.int32),
        shape((SLOTS,), jnp.int32), interpret=False))


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.float32])
def test_contiguous_decode_compiles(shape, cache_dtype):
    """The contiguous float32 cache is ``TensorBackend``'s default."""
    cache = shape((SLOTS, MAX_LEN, KH, D), cache_dtype)
    _assert_kernel(ops.decode_attention.lower(
        shape((SLOTS, H, D), jnp.bfloat16), cache, cache,
        shape((SLOTS, MAX_LEN), jnp.int32), shape((SLOTS,), jnp.int32),
        interpret=False))


def test_flash_prefill_compiles(shape):
    s = 512
    _assert_kernel(ops.flash_attention.lower(
        shape((2, s, H, D), jnp.bfloat16), shape((2, s, KH, D), jnp.bfloat16),
        shape((2, s, KH, D), jnp.bfloat16), interpret=False))


def test_paged_decode_kernel_keeps_its_name(shape):
    """The benchmark finds the paged decode kernel in the device trace by
    its custom call, ``paged_decode_attention.N``.  Inside a program of
    another name the call keeps that name through the kernel's own."""
    from repro.kernels.decode_attention import paged_decode_attention_bhd
    pool = shape((NB + 1, BS, KH, D), jnp.float32)

    def decode_step(q, k_pool, v_pool, bt, mask):
        return paged_decode_attention_bhd(q, k_pool, v_pool, bt, mask,
                                          interpret=False)
    text = jax.jit(decode_step).lower(
        shape((SLOTS, H, D), jnp.bfloat16), pool, pool,
        shape((SLOTS, NBS), jnp.int32),
        shape((SLOTS, MAX_LEN), jnp.int32)).compile().as_text()
    calls = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert calls
    assert all(c.startswith("paged_decode_attention") for c in calls), calls


def _pool_shuffles(text, pool_shapes):
    """(name, opcode, shape) of every copy, dynamic-slice or
    dynamic-update-slice — bare or fused (a fusion named after it) — whose
    result has one of ``pool_shapes`` (dims as ``"a,b,..."``)."""
    inst = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]"
                      r"(?:\{[^}]*\})?\s+([\w\-]+)\(")
    moves = ("copy", "dynamic-slice", "dynamic-update-slice")
    found = []
    for line in text.splitlines():
        m = inst.match(line)
        if not m or m.group(2) not in pool_shapes:
            continue
        name, dims, op = m.groups()
        if op in moves or (op == "fusion" and any(w in name for w in moves)):
            found.append((name, op, dims))
    return found


def test_paged_decode_step_keeps_the_pool_in_place(shape, monkeypatch):
    """``decode_step`` over a stacked float32 paged pool at qwen3-0.6b's
    widths and depth, caches donated as the serving path donates them: the
    compiled program updates the pool in place.  No layer's pool
    ``[NB+1, 16, 8, 128]`` is sliced out or written back, and the stacked
    pool ``[28, NB+1, 16, 8, 128]`` is never copied; the kernel still runs
    under its own name."""
    from repro.models import transformer as T
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    slots, nbs = 5, 32
    nb = slots * nbs
    params = jax.eval_shape(
        lambda: T.init_params(CFG, jax.random.PRNGKey(0))[0])
    caches = jax.eval_shape(lambda: T.init_paged_caches(
        CFG, slots, nbs * BS, nb, BS, jnp.float32))
    params, caches = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                                  (params, caches))

    def decode_step(params, tokens, caches, write_mask):
        return T.decode_step(CFG, params, tokens, caches, impl="pallas",
                             write_mask=write_mask)
    text = jax.jit(decode_step, donate_argnums=(2,)).lower(
        params, shape((slots,), jnp.int32), caches,
        shape((slots,), jnp.bool_)).compile().as_text()
    assert re.search(r"paged_decode_attention[.\d]* = ", text)
    layer = f"{nb + 1},{BS},{KH},{D}"
    assert _pool_shuffles(text, {layer, f"{CFG.n_layers},{layer}"}) == []
