"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:        # only the property-based sweep needs hypothesis
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels.int8_matmul import quantize_int8

K = jax.random.PRNGKey


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kh,d", [
    (1, 128, 4, 4, 64),       # MHA, exact block multiple
    (2, 200, 4, 2, 64),       # GQA, padded seq
    (1, 384, 8, 1, 128),      # MQA, d=128
    (1, 96, 2, 2, 32),        # seq < block
])
def test_flash_attention_matches_ref(b, s, h, kh, d, dtype):
    ks = jax.random.split(K(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
    out = ops.flash_attention(q, k, v, interpret=True)
    want = ref.flash_attention_ref(jnp.swapaxes(q, 1, 2),
                                   jnp.swapaxes(k, 1, 2),
                                   jnp.swapaxes(v, 1, 2))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(jnp.swapaxes(want, 1, 2), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("window", [16, 64, 128])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_flash_attention_window_softcap(window, softcap):
    b, s, h, kh, d = 1, 256, 4, 2, 64
    ks = jax.random.split(K(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    out = ops.flash_attention(q, k, v, window=window, softcap=softcap,
                              interpret=True)
    want = ref.flash_attention_ref(jnp.swapaxes(q, 1, 2),
                                   jnp.swapaxes(k, 1, 2),
                                   jnp.swapaxes(v, 1, 2),
                                   window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(want, 1, 2)),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_block_shape_independence():
    """Result must not depend on the BlockSpec tiling."""
    b, s, h, kh, d = 1, 512, 2, 2, 64
    ks = jax.random.split(K(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    a = ops.flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    bq = ops.flash_attention(q, k, v, block_q=256, block_k=128, interpret=True)
    c = ops.flash_attention(q, k, v, block_q=128, block_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bq), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# decode attention
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kh,d,c,valid", [
    (2, 4, 2, 64, 512, 512),
    (1, 8, 1, 128, 700, 650),     # padded cache, partially filled
    (4, 2, 2, 32, 64, 10),
])
def test_decode_attention_matches_ref(b, h, kh, d, c, valid, dtype):
    ks = jax.random.split(K(3), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, c, kh, d), dtype)
    vc = jax.random.normal(ks[2], (b, c, kh, d), dtype)
    key_pos = jnp.where(jnp.arange(c) < valid, jnp.arange(c), -1).astype(jnp.int32)
    pos = jnp.asarray(valid - 1, jnp.int32)
    out = ops.decode_attention(q, kc, vc, key_pos, pos, block_c=256,
                               interpret=True)
    mask = (key_pos >= 0) & (key_pos <= pos)
    want = ref.decode_attention_ref(q, kc, vc, mask[None])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_ring_buffer_window():
    """Ring-buffer semantics: slots hold non-monotonic positions."""
    b, h, kh, d, c = 1, 2, 1, 32, 128
    ks = jax.random.split(K(4), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, c, kh, d))
    vc = jax.random.normal(ks[2], (b, c, kh, d))
    pos = jnp.asarray(200, jnp.int32)           # wrapped: slot = pos % 128
    key_pos = ((jnp.arange(c) + (201 // c) * c)
               - jnp.where(jnp.arange(c) > 200 % c, c, 0)).astype(jnp.int32)
    window = 50
    out = ops.decode_attention(q, kc, vc, key_pos, pos, window=window,
                               interpret=True)
    mask = (key_pos >= 0) & (key_pos <= pos) & (key_pos > pos - window)
    want = ref.decode_attention_ref(q, kc, vc, mask[None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------------------- #
# paged decode attention (block-table indirection fused into the kernel)
# --------------------------------------------------------------------------- #

def _paged_case(b, kh, d, bs, nbs, num_blocks, lens, seed):
    """Pools + a block table with the last entry of row 0 unmapped (-1)."""
    c = nbs * bs
    ks = jax.random.split(K(seed), 3)
    k_pool = jax.random.normal(ks[0], (num_blocks + 1, bs, kh, d))
    v_pool = jax.random.normal(ks[1], (num_blocks + 1, bs, kh, d))
    rng = np.random.default_rng(seed)
    bt = rng.permutation(num_blocks)[:b * nbs].reshape(b, nbs).astype(np.int32)
    bt[0, -1] = -1                      # unmapped tail: must read as masked
    lens = np.asarray(lens)
    key_pos = np.where(np.arange(c)[None] < lens[:, None],
                       np.arange(c)[None], -1).astype(np.int32)
    key_pos[0, (nbs - 1) * bs:] = -1    # nothing valid in the unmapped block
    pos = (lens - 1).astype(np.int32)
    return (k_pool, v_pool, jnp.asarray(bt), jnp.asarray(key_pos),
            jnp.asarray(pos), ks[2])


def _paged_gather_ref(q, k_pool, v_pool, bt, mask, *, softcap=None):
    """Oracle: dense gather through the table, then masked sdpa per row."""
    b, nbs = bt.shape
    bs, kh, d = k_pool.shape[1:]
    read = jnp.clip(bt, 0, None)
    ck = k_pool[read].reshape(b, nbs * bs, kh, d)
    cv = v_pool[read].reshape(b, nbs * bs, kh, d)
    return jnp.concatenate(
        [ref.decode_attention_ref(q[i:i + 1], ck[i:i + 1], cv[i:i + 1],
                                  mask[i:i + 1], softcap=softcap)
         for i in range(b)], axis=0)


@pytest.mark.parametrize("softcap", [None, 30.0])
@pytest.mark.parametrize("b,h,kh,d,bs,nbs,lens", [
    (2, 4, 2, 64, 16, 4, (40, 25)),      # GQA, per-slot positions
    (3, 8, 1, 32, 16, 3, (48, 1, 17)),   # MQA, a fresh slot and a full one
    (1, 2, 2, 128, 32, 2, (33, )),       # MHA, bigger blocks
])
def test_paged_decode_matches_gather_ref(b, h, kh, d, bs, nbs, lens, softcap):
    """Kernel reads through the block table == dense gather + masked sdpa,
    with every row at its own position (per-slot semantics)."""
    k_pool, v_pool, bt, key_pos, pos, kq = _paged_case(
        b, kh, d, bs, nbs, num_blocks=b * nbs + 2, lens=lens, seed=20)
    q = jax.random.normal(kq, (b, h, d))
    out = ops.paged_decode_attention(q, k_pool, v_pool, bt, key_pos, pos,
                                     softcap=softcap, interpret=True)
    mask = (key_pos >= 0) & (key_pos <= pos[:, None])
    want = _paged_gather_ref(q, k_pool, v_pool, bt, mask, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_paged_decode_ring_wraparound_window():
    """Positions past C_pad wrap the ring: slots hold non-monotonic
    key_pos, and the window mask must follow positions, not slot order."""
    b, h, kh, d, bs, nbs = 1, 2, 1, 32, 16, 4
    c = nbs * bs                                  # 64
    ks = jax.random.split(K(21), 3)
    k_pool = jax.random.normal(ks[0], (nbs + 1, bs, kh, d))
    v_pool = jax.random.normal(ks[1], (nbs + 1, bs, kh, d))
    q = jax.random.normal(ks[2], (b, h, d))
    bt = jnp.arange(nbs, dtype=jnp.int32)[None]
    pos = jnp.asarray([150], jnp.int32)           # wrapped: slot = pos % 64
    wrap = 150 % c
    key_pos = (jnp.arange(c) + (150 // c) * c
               - jnp.where(jnp.arange(c) > wrap, c, 0)).astype(jnp.int32)[None]
    window = 40
    out = ops.paged_decode_attention(q, k_pool, v_pool, bt, key_pos, pos,
                                     window=window, interpret=True)
    mask = (key_pos >= 0) & (key_pos <= pos[:, None]) \
        & (key_pos > pos[:, None] - window)
    assert 0 < int(mask.sum()) < c, "window must mask a strict subset"
    want = _paged_gather_ref(q, k_pool, v_pool, bt, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_paged_decode_fully_masked_row_is_finite():
    """An idle slot (every key_pos == -1, table unmapped) must produce
    finite output (exact zeros), not NaN from an empty softmax."""
    b, h, kh, d, bs, nbs = 2, 4, 2, 32, 16, 2
    k_pool, v_pool, bt, key_pos, pos, kq = _paged_case(
        b, kh, d, bs, nbs, num_blocks=b * nbs, lens=(20, 5), seed=22)
    q = jax.random.normal(kq, (b, h, d))
    key_pos = key_pos.at[1].set(-1)               # row 1: never written
    bt = bt.at[1].set(-1)
    out = ops.paged_decode_attention(q, k_pool, v_pool, bt, key_pos, pos,
                                     interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  np.zeros_like(np.asarray(out[1])))
    # the live row is unaffected by its dead neighbour
    solo = ops.paged_decode_attention(q[:1], k_pool, v_pool, bt[:1],
                                      key_pos[:1], pos[:1], interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(solo[0]),
                               rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------------------- #
# paged verify attention (KQ draft tokens per slot, one block-streaming pass)
# --------------------------------------------------------------------------- #

def _paged_verify_gather_ref(q, k_pool, v_pool, bt, mask, *, softcap=None):
    """Oracle: per q row, the single-token gather reference with that
    row's causality mask."""
    kq = q.shape[1]
    return jnp.stack(
        [_paged_gather_ref(q[:, i], k_pool, v_pool, bt, mask[:, i],
                           softcap=softcap) for i in range(kq)], axis=1)


def _verify_case(b, kh, d, bs, nbs, kq, lens, seed, unmapped_tail=False):
    """Pools + table where each slot holds ``lens[i] + kq - 1`` scattered
    keys (the history plus the verify quantum's own drafts) and ``pos`` is
    the first fed token's position, matching the runtime's scatter-then-
    attend order."""
    c = nbs * bs
    assert max(lens) + kq - 1 <= c
    ks = jax.random.split(K(seed), 3)
    num_blocks = b * nbs + 2
    k_pool = jax.random.normal(ks[0], (num_blocks + 1, bs, kh, d))
    v_pool = jax.random.normal(ks[1], (num_blocks + 1, bs, kh, d))
    rng = np.random.default_rng(seed)
    bt = rng.permutation(num_blocks)[:b * nbs].reshape(b, nbs).astype(np.int32)
    valid = np.asarray(lens)[:, None] + kq - 1
    key_pos = np.where(np.arange(c)[None] < valid,
                       np.arange(c)[None], -1).astype(np.int32)
    if unmapped_tail:
        bt[0, -1] = -1
        key_pos[0, (nbs - 1) * bs:] = -1
    pos = (np.asarray(lens) - 1).astype(np.int32)
    return (k_pool, v_pool, jnp.asarray(bt), jnp.asarray(key_pos),
            jnp.asarray(pos), ks[2])


@pytest.mark.parametrize("softcap", [None, 30.0])
@pytest.mark.parametrize("b,h,kh,d,bs,nbs,kq,lens", [
    (2, 4, 2, 64, 16, 4, 4, (40, 25)),    # GQA, per-slot positions
    (2, 8, 1, 32, 16, 3, 4, (15, 30)),    # MQA; row 0's drafts straddle the
                                          # block-0/1 boundary (15-1+4 > 16)
    (1, 2, 2, 64, 16, 2, 5, (20, )),      # kq > typical draft count
])
def test_paged_verify_matches_gather_ref(b, h, kh, d, bs, nbs, kq, lens,
                                         softcap):
    """KQ-row verify == per-row gather reference under per-row causality:
    row i admits keys with key_pos <= pos + i (later drafts see earlier
    drafts' freshly-scattered keys, never their own future)."""
    k_pool, v_pool, bt, key_pos, pos, kr = _verify_case(
        b, kh, d, bs, nbs, kq, lens, seed=30)
    q = jax.random.normal(kr, (b, kq, h, d))
    out = ops.paged_verify_attention(q, k_pool, v_pool, bt, key_pos, pos,
                                     softcap=softcap, interpret=True)
    pos_i = pos[:, None, None] + jnp.arange(kq)[None, :, None]
    mask = (key_pos[:, None, :] >= 0) & (key_pos[:, None, :] <= pos_i)
    want = _paged_verify_gather_ref(q, k_pool, v_pool, bt, mask,
                                    softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # per-row causality is strict: row 0 must NOT see row kq-1's keys
    m0, mk = mask[:, 0], mask[:, kq - 1]
    assert int(m0.sum()) < int(mk.sum())


def test_paged_verify_unmapped_blocks_masked():
    """An unmapped (-1) table entry reads as fully masked — the scratch
    block's garbage never reaches a verify row's softmax."""
    b, h, kh, d, bs, nbs, kq = 2, 4, 2, 32, 16, 3, 3
    k_pool, v_pool, bt, key_pos, pos, kr = _verify_case(
        b, kh, d, bs, nbs, kq, lens=(20, 10), seed=31, unmapped_tail=True)
    q = jax.random.normal(kr, (b, kq, h, d))
    out = ops.paged_verify_attention(q, k_pool, v_pool, bt, key_pos, pos,
                                     interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    pos_i = pos[:, None, None] + jnp.arange(kq)[None, :, None]
    mask = (key_pos[:, None, :] >= 0) & (key_pos[:, None, :] <= pos_i)
    want = _paged_verify_gather_ref(q, k_pool, v_pool, bt, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # corrupting the scratch block (last pool row) must not change outputs
    out2 = ops.paged_verify_attention(
        q, k_pool.at[-1].set(1e6), v_pool.at[-1].set(-1e6), bt, key_pos, pos,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_paged_verify_kq1_bitexact_with_decode():
    """A 1-token verify IS the decode kernel: identical online-softmax
    order makes the outputs bit-identical, which is what lets the runtime
    route plain decode through the verify path without drift."""
    b, h, kh, d, bs, nbs = 3, 4, 2, 64, 16, 4
    k_pool, v_pool, bt, key_pos, pos, kr = _paged_case(
        b, kh, d, bs, nbs, num_blocks=b * nbs + 2, lens=(40, 25, 7), seed=32)
    q = jax.random.normal(kr, (b, h, d))
    dec = ops.paged_decode_attention(q, k_pool, v_pool, bt, key_pos, pos,
                                     interpret=True)
    ver = ops.paged_verify_attention(q[:, None], k_pool, v_pool, bt,
                                     key_pos, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(ver[:, 0]))


def test_paged_verify_ring_wraparound_window():
    """Wrapped ring + sliding window: each verify row's window follows its
    own position pos+i over non-monotonic key_pos."""
    b, h, kh, d, bs, nbs, kq = 1, 2, 1, 32, 16, 4, 3
    c = nbs * bs                                  # 64
    ks = jax.random.split(K(33), 3)
    k_pool = jax.random.normal(ks[0], (nbs + 1, bs, kh, d))
    v_pool = jax.random.normal(ks[1], (nbs + 1, bs, kh, d))
    q = jax.random.normal(ks[2], (b, kq, h, d))
    bt = jnp.arange(nbs, dtype=jnp.int32)[None]
    first = 150                                   # wrapped: slot = pos % 64
    wrap = (first + kq - 1) % c
    key_pos = (jnp.arange(c) + ((first + kq - 1) // c) * c
               - jnp.where(jnp.arange(c) > wrap, c, 0)).astype(jnp.int32)[None]
    pos = jnp.asarray([first], jnp.int32)
    window = 40
    out = ops.paged_verify_attention(q, k_pool, v_pool, bt, key_pos, pos,
                                     window=window, interpret=True)
    pos_i = pos[:, None, None] + jnp.arange(kq)[None, :, None]
    mask = (key_pos[:, None, :] >= 0) & (key_pos[:, None, :] <= pos_i) \
        & (key_pos[:, None, :] > pos_i - window)
    counts = [int(mask[0, i].sum()) for i in range(kq)]
    assert all(0 < n < c for n in counts), counts
    want = _paged_verify_gather_ref(q, k_pool, v_pool, bt, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_paged_kernel_reads_the_given_layer_of_a_stacked_pool(kind):
    """Pools stacked over layers ``[L, NB+1, bs, KH, D]`` and read at
    ``layer`` through the kv index map give bit for bit what the kernel
    gives on ``pool[layer]``, for every layer: each layer (its scratch
    block too) holds other values, and row 0's last table entry is
    unmapped, so it reads the scratch block."""
    b, h, kh, d, bs, nbs, kq, n_layers = 2, 4, 2, 32, 16, 3, 3, 3
    _, _, bt, key_pos, pos, kr = _paged_case(
        b, kh, d, bs, nbs, num_blocks=b * nbs + 2, lens=(40, 25), seed=33)
    ks = jax.random.split(K(34), 2)
    pools = (b * nbs + 3, bs, kh, d)
    k_stack = jax.random.normal(ks[0], (n_layers,) + pools)
    v_stack = jax.random.normal(ks[1], (n_layers,) + pools)
    if kind == "decode":
        q = jax.random.normal(kr, (b, h, d))
        call = ops.paged_decode_attention
    else:
        q = jax.random.normal(kr, (b, kq, h, d))
        call = ops.paged_verify_attention
    outs = []
    for layer in range(n_layers):
        got = call(q, k_stack, v_stack, bt, key_pos, pos,
                   jnp.asarray(layer, jnp.int32), interpret=True)
        want = call(q, k_stack[layer], v_stack[layer], bt, key_pos, pos,
                    interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        outs.append(np.asarray(got))
    assert not np.array_equal(outs[0], outs[1])     # the layers differ


# ---- model-level: attend_decode_paged dispatch (per-slot vs shared,
# ---- write_mask scratch isolation, impl contract)

def _attn_fixture():
    from repro.configs import get_config
    from repro.models import attention as A
    from repro.models.kvcache import init_paged_block_cache
    from repro.models.layers import ParamBuilder
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    spec = [s for s in cfg.layer_specs() if s.kind == "attn"][0]
    pb = ParamBuilder(K(23), jnp.float32)
    A.init_attention(pb, "mixer", cfg)

    def make_cache(batch, num_blocks=8, max_len=32):
        cache = init_paged_block_cache(cfg, spec, batch, max_len, num_blocks,
                                       16, jnp.float32)
        cache["k_pool"] = jax.random.normal(K(24), cache["k_pool"].shape)
        cache["v_pool"] = jax.random.normal(K(25), cache["v_pool"].shape)
        return cache

    return cfg, spec, pb.params["mixer"], make_cache


def test_attend_decode_paged_per_slot_matches_shared():
    """Shared semantics (scalar pos, the pipeline tick's view) must equal
    the same slot decoded through the per-slot convention."""
    from repro.models import attention as A
    cfg, spec, params, make_cache = _attn_fixture()
    x = jax.random.normal(K(26), (1, 1, cfg.d_model))
    per = make_cache(1)
    per["bt"] = jnp.array([[0, 1]], jnp.int32)
    per["key_pos"] = per["key_pos"].at[0, :20].set(jnp.arange(20))
    per["pos"] = jnp.array([20], jnp.int32)
    shared = dict(per, bt=per["bt"][0], key_pos=per["key_pos"][0],
                  pos=per["pos"][0])
    for impl in ("xla", "pallas"):
        y_per, c_per = A.attend_decode_paged(params, cfg, spec, x,
                                             dict(per), impl)
        y_sh, c_sh = A.attend_decode_paged(params, cfg, spec, x,
                                           dict(shared), impl)
        np.testing.assert_array_equal(np.asarray(y_per), np.asarray(y_sh))
        np.testing.assert_array_equal(np.asarray(c_per["key_pos"][0]),
                                      np.asarray(c_sh["key_pos"]))
        assert c_sh["pos"].ndim == 0 and int(c_sh["pos"]) == 21


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_attend_decode_paged_write_mask_scratch_isolation(impl):
    """A write-masked row must scatter to the scratch block only: no live
    slot's pool blocks change, the masked row's key_pos/pos freeze, and the
    live rows' outputs equal an unmasked decode of the same rows."""
    from repro.models import attention as A
    cfg, spec, params, make_cache = _attn_fixture()
    b = 2
    x = jax.random.normal(K(27), (b, 1, cfg.d_model))
    cache = make_cache(b)
    cache["bt"] = jnp.array([[0, 1], [2, 3]], jnp.int32)
    cache["key_pos"] = cache["key_pos"].at[0, :20].set(jnp.arange(20))
    cache["key_pos"] = cache["key_pos"].at[1, :7].set(jnp.arange(7))
    cache["pos"] = jnp.array([20, 7], jnp.int32)
    wm = jnp.array([True, False])
    y, new = A.attend_decode_paged(params, cfg, spec, x, dict(cache), impl,
                                   write_mask=wm)
    scratch = cache["k_pool"].shape[0] - 1
    live = np.arange(scratch)                   # every non-scratch block
    row0_blocks = {0, 1}
    for k in ("k_pool", "v_pool"):
        for blk in live:
            if blk in row0_blocks:
                continue                        # row 0 wrote its own block
            np.testing.assert_array_equal(np.asarray(new[k][blk]),
                                          np.asarray(cache[k][blk]),
                                          err_msg=f"{k}[{blk}] corrupted")
    np.testing.assert_array_equal(np.asarray(new["key_pos"][1]),
                                  np.asarray(cache["key_pos"][1]))
    assert int(new["pos"][1]) == 7 and int(new["pos"][0]) == 21
    # row 0's output is independent of row 1 being masked
    y_solo, _ = A.attend_decode_paged(
        params, cfg, spec, x[:1],
        {**{k: v for k, v in cache.items() if "pool" in k},
         "bt": cache["bt"][:1], "key_pos": cache["key_pos"][:1],
         "pos": cache["pos"][:1]}, impl)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_solo[0]),
                               rtol=1e-6, atol=1e-6)


def test_attend_decode_paged_unknown_impl_raises():
    from repro.models import attention as A
    cfg, spec, params, make_cache = _attn_fixture()
    x = jax.random.normal(K(28), (1, 1, cfg.d_model))
    cache = make_cache(1)
    with pytest.raises(ValueError, match="unknown decode impl"):
        A.attend_decode_paged(params, cfg, spec, x, cache, "cuda")
    with pytest.raises(ValueError, match="unknown decode impl"):
        A.attend_decode(params, cfg, spec, x, cache, "cuda")


# ---- model-level: decode_step over a stacked pool carried through the scan

def _stacked_paged_state(cfg, n_slots, max_len, pos, seed):
    """Paged caches whose pools hold random history: slot ``s`` owns
    blocks ``s*nbs .. s*nbs+nbs-1`` and has seen positions ``0..pos[s]-1``
    (ring-wrapped in windowed layers), and the contiguous caches that hold
    the same keys and values."""
    from repro.models import transformer as T
    from repro.models.kvcache import max_ctx_blocks
    nbs = max_ctx_blocks(cfg, max_len, 16)
    paged = T.init_paged_caches(cfg, n_slots, max_len, n_slots * nbs, 16,
                                jnp.float32)
    dense = T.init_caches(cfg, n_slots, max_len, jnp.float32)
    keys = iter(jax.random.split(K(seed), 16))
    bt = np.arange(n_slots * nbs, dtype=np.int32).reshape(n_slots, nbs)

    def fill(pe, de):
        c_pad, c = pe["key_pos"].shape[-1], de["key_pos"].shape[-1]
        key_pos = np.full((n_slots, c_pad), -1, np.int32)
        for s, p in enumerate(pos):
            for t in range(p):
                key_pos[s, t % c] = t
        lead = pe["k_pool"].shape[:-4]
        pe, de = dict(pe), dict(de)
        for name in ("k", "v"):
            pool = jax.random.normal(next(keys), pe[f"{name}_pool"].shape)
            pe[f"{name}_pool"] = pool
            rows = pool[..., bt[:, :c_pad // 16], :, :, :]
            de[name] = rows.reshape(lead + (n_slots, c_pad) + rows.shape[-2:]
                                    )[..., :c, :, :]
        pe["bt"] = jnp.broadcast_to(jnp.asarray(bt), pe["bt"].shape)
        pe["key_pos"] = jnp.broadcast_to(jnp.asarray(key_pos),
                                         pe["key_pos"].shape)
        de["key_pos"] = jnp.broadcast_to(jnp.asarray(key_pos[:, :c]),
                                         de["key_pos"].shape)
        for e in (pe, de):
            e["pos"] = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                        e["pos"].shape)
        return pe, de

    for group in ("stack", "tail"):
        for name in paged[group]:
            paged[group][name], dense[group][name] = fill(
                paged[group][name], dense[group][name])
    return paged, dense


def _layer_entries(caches):
    """(stack entry, layer) or (tail entry, None) for every attention layer."""
    for entry in caches["stack"].values():
        for layer in range(entry["pos"].shape[0]):
            yield entry, layer
    for entry in caches["tail"].values():
        yield entry, None


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_stacked_paged_decode_step_writes_each_token_into_its_own_layer(impl):
    """Two full periods of a (local, global) pattern plus a tail layer,
    three slots, four steps, slot 1 write-masked.  Each step writes each
    live slot's token at ``(layer, block, offset)`` of every layer's pool
    with that layer's own key and value (the contiguous layout's, decoded
    alongside), changes nothing else in any pool but the scratch block,
    freezes the masked slot, and gives the contiguous layout's logits."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("gemma2-2b").reduced(n_layers=5)
    assert cfg.n_full_periods == 2 and len(cfg.pattern) == 2 and cfg.tail
    n_slots, max_len, pos = 3, 64, [20, 9, 33]
    params, _ = T.init_params(cfg, K(40))
    paged, dense = _stacked_paged_state(cfg, n_slots, max_len, pos, seed=41)
    wm = jnp.array([True, False, True])
    live = [0, 2]
    step_paged = jax.jit(lambda t, c: T.decode_step(
        cfg, params, t, c, impl=impl, write_mask=wm))
    step_dense = jax.jit(lambda t, c: T.decode_step(cfg, params, t, c,
                                                    impl=impl))
    tokens = jnp.array([3, 5, 7], jnp.int32)
    for _ in range(4):
        logits, new = step_paged(tokens, paged)
        dlogits, dnew = step_dense(tokens, dense)
        np.testing.assert_allclose(np.asarray(logits)[live],
                                   np.asarray(dlogits)[live],
                                   rtol=1e-5, atol=1e-5)
        for (old_e, layer), (new_e, _), (dense_e, _) in zip(
                _layer_entries(paged), _layer_entries(new),
                _layer_entries(dnew)):
            sel = (lambda a: a[layer]) if layer is not None else (lambda a: a)
            c_pad, c = sel(old_e["key_pos"]).shape[-1], \
                sel(dense_e["key_pos"]).shape[-1]
            p_old = np.asarray(sel(old_e["pos"]))
            bt = np.asarray(sel(old_e["bt"]))
            for name in ("k", "v"):
                before = np.array(sel(old_e[f"{name}_pool"]))
                after = np.asarray(sel(new_e[f"{name}_pool"]))
                for s in live:
                    ring = p_old[s] % c_pad
                    blk, off = bt[s, ring // 16], ring % 16
                    np.testing.assert_allclose(
                        after[blk, off],
                        np.asarray(sel(dense_e[name]))[s, p_old[s] % c],
                        rtol=1e-5, atol=1e-5)
                    before[blk, off] = after[blk, off]
                # nothing else moved: not the masked slot's blocks, not
                # another layer's slots (the scratch block may)
                np.testing.assert_array_equal(after[:-1], before[:-1])
            np.testing.assert_array_equal(
                np.asarray(sel(new_e["pos"])), p_old + np.array([1, 0, 1]))
            np.testing.assert_array_equal(
                np.asarray(sel(new_e["key_pos"]))[1],
                np.asarray(sel(old_e["key_pos"]))[1])
        # the contiguous layout has no write mask: its slot 1 runs on
        # unread, and the rows do not interact
        paged, dense = new, dnew
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)


def test_stacked_paged_decode_greedy_tokens_match_xla():
    """Greedy decoding through the carried pool: the Pallas kernel reading
    each layer through its index map gives the tokens of the ``xla``
    gather path, step for step, with a write-masked slot."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("gemma2-2b").reduced(n_layers=5)
    params, _ = T.init_params(cfg, K(42))
    wm = jnp.array([True, True, False])
    streams = {}
    for impl in ("xla", "pallas"):
        caches, _ = _stacked_paged_state(cfg, 3, 64, [20, 9, 33], seed=43)
        step = jax.jit(lambda t, c, impl=impl: T.decode_step(
            cfg, params, t, c, impl=impl, write_mask=wm))
        tokens, out = jnp.array([3, 5, 7], jnp.int32), []
        for _ in range(6):
            logits, caches = step(tokens, caches)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(np.asarray(tokens)[:2])
        streams[impl] = np.stack(out)
    np.testing.assert_array_equal(streams["pallas"], streams["xla"])


# --------------------------------------------------------------------------- #
# RG-LRU scan
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("b,s,r", [(1, 16, 128), (2, 33, 200), (4, 7, 64),
                                   (1, 128, 384)])
def test_rglru_scan_matches_ref(b, s, r):
    ks = jax.random.split(K(5), 3)
    log_a = -jnp.abs(jax.random.normal(ks[0], (b, s, r)))
    bb = jax.random.normal(ks[1], (b, s, r))
    h0 = jax.random.normal(ks[2], (b, r))
    out = ops.rglru_scan(log_a, bb, h0, interpret=True)
    want = ref.rglru_scan_ref(log_a, bb, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rglru_scan_zero_init_equals_none():
    ks = jax.random.split(K(6), 2)
    log_a = -jnp.abs(jax.random.normal(ks[0], (2, 9, 128)))
    bb = jax.random.normal(ks[1], (2, 9, 128))
    a = ops.rglru_scan(log_a, bb, None, interpret=True)
    b2 = ops.rglru_scan(log_a, bb, jnp.zeros((2, 128)), interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b2), rtol=0, atol=0)


if HAS_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 10_000), st.integers(1, 3), st.integers(1, 40),
           st.integers(1, 260))
    def test_rglru_scan_property(seed, b, s, r):
        ks = jax.random.split(K(seed), 3)
        log_a = -jnp.abs(jax.random.normal(ks[0], (b, s, r)))
        bb = jax.random.normal(ks[1], (b, s, r))
        h0 = jax.random.normal(ks[2], (b, r))
        out = ops.rglru_scan(log_a, bb, h0, interpret=True)
        want = ref.rglru_scan_ref(log_a, bb, h0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
else:       # keep the gap visible in test reports instead of not collecting
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_rglru_scan_property():
        pass


# --------------------------------------------------------------------------- #
# int8 matmul
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(128, 512, 128), (70, 300, 130),
                                   (1, 1024, 256), (256, 64, 64)])
def test_int8_matmul_matches_ref(m, k, n, dtype):
    ks = jax.random.split(K(7), 2)
    x = jax.random.normal(ks[0], (m, k), dtype)
    w = jax.random.normal(ks[1], (k, n), jnp.float32)
    wq, sc = quantize_int8(w)
    out = ops.int8_matmul(x, wq, sc, interpret=True)
    want = ref.int8_matmul_ref(x, wq, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_int8_quantization_error_bounded():
    w = jax.random.normal(K(8), (256, 128))
    wq, sc = quantize_int8(w)
    w_deq = wq.astype(jnp.float32) * sc
    # max per-element error is half a quantization step
    step = np.asarray(sc)[0]
    err = np.abs(np.asarray(w) - np.asarray(w_deq))
    assert (err <= step / 2 + 1e-6).all()


def test_int8_matmul_leading_dims():
    x = jax.random.normal(K(9), (2, 3, 64))
    w = jax.random.normal(K(10), (64, 32))
    wq, sc = quantize_int8(w)
    out = ops.int8_matmul(x, wq, sc, interpret=True)
    assert out.shape == (2, 3, 32)


# --------------------------------------------------------------------------- #
# model-level: pallas impl == xla impl
# --------------------------------------------------------------------------- #

def test_model_forward_pallas_matches_xla():
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("gemma2-2b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, K(11))
    tokens = jax.random.randint(K(12), (2, 24), 0, cfg.vocab_size)
    ref_logits, _, _ = T.forward(cfg, params, tokens, mode="train", impl="xla")
    pal_logits, _, _ = T.forward(cfg, params, tokens, mode="train", impl="pallas")
    np.testing.assert_allclose(np.asarray(pal_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_rglru_block_pallas_matches_xla():
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("recurrentgemma-2b").reduced(n_layers=3)
    params, _ = T.init_params(cfg, K(13))
    tokens = jax.random.randint(K(14), (2, 16), 0, cfg.vocab_size)
    a, _, _ = T.forward(cfg, params, tokens, mode="train", impl="xla")
    b = T.forward(cfg, params, tokens, mode="train", impl="pallas")[0]
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-4)
