"""Single-token GQA decode attention over a (ring-buffer or paged) KV cache.

The decode hot spot: one query row per sequence against a cache of up to
524288 keys (``long_500k``).  Grid ``(batch, num_kv_blocks)`` with
online-softmax state in VMEM scratch; the kv axis is innermost so the cache
streams HBM->VMEM block by block.  A kv block carries *every* kv head
(``[bc, KH, D]``) and the kernel walks the heads inside the grid step, each
with its whole GQA query group (query block ``[KH, R, D]``), so each cache
block is DMA'd exactly **once** per decode step — a per-q-head grid would
re-stream the cache ``h/kh`` times and forfeit the memory-roofline win the
kernel exists for.

Block shapes follow the TPU lowering's tiling rule (the last two block
dimensions are multiples of ``(8, 128)`` or span the whole array): the kv
block's last two dims are the full ``(KH, D)``, the query block's the full
``(R, D)``, and the mask is laid out ``[B|1, n_blocks, R|1, bc]`` so its
block is a whole ``(R|1, bc)`` tile.  The mask is int32 (nonzero = attend).

Two cache layouts share the same kernel body:

- :func:`decode_attention_bhd` — contiguous ring buffers ``[B, C, KH, D]``,
- :func:`paged_decode_attention_bhd` — a shared block pool
  ``[NB+1, bs, KH, D]`` read *through the slot's block table*: the table is
  scalar-prefetched and drives the kv ``BlockSpec`` index map, so block
  ``ib`` of slot ``b`` streams pool block ``bt[b, ib]`` HBM->VMEM directly.
  A pool stacked over layers ``[L, NB+1, bs, KH, D]`` is read in place at a
  scalar-prefetched layer index, so a decode layer scan never slices it.
  This is the vLLM-style fused indirection — no ``[B, C_pad, KH, D]``
  gather temporary exists, killing the per-step full-cache materialization
  the XLA paged path pays for.

:func:`paged_verify_attention_bhd` (speculative verify) is the same kernel
with ``R = KQ * g`` query rows per kv head, so a one-token verify runs the
decode kernel's exact arithmetic.

Slot validity/window masking is precomputed by the wrapper from
``key_pos`` — ring buffers make validity position- not index-monotonic.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                 acc_ref, *, scale: float, softcap: Optional[float]):
    """Online-softmax attention of one batch row over its cache blocks.

    Block shapes: q/o ``[KH, R, d]`` (kv head ``j``'s ``R`` query rows),
    k/v ``[bc, KH, d]``, mask ``[1|R, bc]`` int32; scratch m/l
    ``[KH, R, 1]``, acc ``[KH, R, d]`` persist across the innermost
    (kv-block) grid axis.
    """
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = mask_ref[...] != 0                                   # [1|R, bc]
    for j in range(k_ref.shape[1]):                              # kv heads
        q = q_ref[j].astype(jnp.float32)                         # [R, d]
        k = k_ref[:, j, :].astype(jnp.float32)                   # [bc, d]
        v = v_ref[:, j, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [R, bc]
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[j]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[j] = acc_ref[j] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[j] = m_new

    @pl.when(ic == nc - 1)
    def _finish():
        # a fully-masked row (idle paged slot: every key_pos == -1) keeps
        # l at 0; the clamp yields exact zeros instead of NaN
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_kernel(bt_ref, layer_ref, *refs, scale: float,
                  softcap: Optional[float]):
    """``bt_ref`` and ``layer_ref`` (the scalar-prefetched block table and
    pool layer) are consumed by the kv BlockSpec index map, not the body —
    which is exactly the dense one."""
    del bt_ref, layer_ref
    _attn_kernel(*refs, scale=scale, softcap=softcap)


def _scratch(kh: int, r: int, d: int):
    return [pltpu.VMEM((kh, r, 1), jnp.float32),       # m
            pltpu.VMEM((kh, r, 1), jnp.float32),       # l
            pltpu.VMEM((kh, r, d), jnp.float32)]       # acc


def _blocked_mask(mask: jax.Array, bc: int) -> jax.Array:
    """[M, R, C] (any truthy dtype) -> int32 [M, C // bc, R, bc]."""
    m, r, c = mask.shape
    return mask.astype(jnp.int32).reshape(m, r, c // bc, bc).swapaxes(1, 2)


def decode_attention_bhd(q: jax.Array, k: jax.Array, v: jax.Array,
                         mask: jax.Array, *, softcap: Optional[float] = None,
                         block_c: int = 512, interpret: bool = False,
                         ) -> jax.Array:
    """q [B,H,D]; k/v [B,C,KH,D]; mask [1,C] or [B,C] (nonzero = attend;
    a [B,C] mask carries per-row validity/window, e.g. per-row decode
    positions after a masked length-bucketed prefill).

    Returns [B,H,D].  C must be a multiple of ``block_c`` (wrapper pads with
    masked slots).
    """
    b, h, d = q.shape
    c, kh = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    g = h // kh                  # GQA group: q heads sharing one kv head
    assert c % block_c == 0, (c, block_c)
    assert mask.shape[0] in (1, b), mask.shape
    shared_mask = mask.shape[0] == 1

    # q heads j*g..(j+1)*g-1 attend kv head j (the _sdpa grouping)
    q4 = q.reshape(b, kh, g, d)
    mask4 = _blocked_mask(mask[:, None, :], block_c)        # [1|B, nc, 1, bc]
    q_spec = pl.BlockSpec((None, kh, g, d), lambda b_, ic: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, block_c, kh, d),
                           lambda b_, ic: (b_, ic, 0, 0))
    mask_spec = pl.BlockSpec(
        (None, None, 1, block_c),
        (lambda b_, ic: (0, ic, 0, 0)) if shared_mask
        else (lambda b_, ic: (b_, ic, 0, 0)))

    kernel = functools.partial(_attn_kernel, scale=1.0 / math.sqrt(d),
                               softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid=(b, c // block_c),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, q.dtype),
        scratch_shapes=_scratch(kh, g, d),
        interpret=interpret,
        name="decode_attention",
    )(q4, k, v, mask4)
    return out.reshape(b, h, d)


def _layered(k_pool: jax.Array, v_pool: jax.Array,
             layer: Optional[jax.Array]):
    """Pools with a leading layer axis ``[L, NB+1, bs, KH, D]`` and the
    layer to read as int32 ``[1]``.  An unstacked ``[NB+1, bs, KH, D]``
    pool (``layer`` None) becomes ``pool[None]`` at layer 0 — a bitcast,
    not a copy."""
    if layer is None:
        assert k_pool.ndim == 4, k_pool.shape
        return k_pool[None], v_pool[None], jnp.zeros((1,), jnp.int32)
    assert k_pool.ndim == 5, k_pool.shape
    return k_pool, v_pool, jnp.reshape(layer, (1,)).astype(jnp.int32)


def _paged_call(q4: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                bt: jax.Array, layer: jax.Array, mask4: jax.Array, *,
                softcap: Optional[float], interpret: bool,
                name: str) -> jax.Array:
    """q4 [B, KH, R, D]; pools [L, NB+1, bs, KH, D]; bt [B, nbs]; layer
    int32 [1]; mask4 int32 [B, nbs, 1|R, bs] -> [B, KH, R, D].  The pools
    are read in place at ``layer`` through the kv index map, so a stacked
    pool is never sliced.  ``name`` is the kernel's custom call in the
    device trace."""
    b, kh, r, d = q4.shape
    bs = k_pool.shape[2]
    nbs = bt.shape[1]
    assert k_pool.shape[3] == kh, (k_pool.shape, q4.shape)
    assert bt.shape == (b, nbs), bt.shape
    assert layer.shape == (1,), layer.shape
    rm = mask4.shape[2]
    assert mask4.shape == (b, nbs, rm, bs) and rm in (1, r), mask4.shape

    q_spec = pl.BlockSpec((None, kh, r, d),
                          lambda b_, ib, bt_, l_: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (None, None, bs, kh, d),
        lambda b_, ib, bt_, l_: (l_[0], bt_[b_, ib], 0, 0, 0))
    mask_spec = pl.BlockSpec((None, None, rm, bs),
                             lambda b_, ib, bt_, l_: (b_, ib, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nbs),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
        out_specs=q_spec,
        scratch_shapes=_scratch(kh, r, d))
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(d),
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        interpret=interpret,
        name=name,
    )(bt, layer, q4, k_pool, v_pool, mask4)


def paged_decode_attention_bhd(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, bt: jax.Array,
                               mask: jax.Array, *,
                               layer: Optional[jax.Array] = None,
                               softcap: Optional[float] = None,
                               interpret: bool = False) -> jax.Array:
    """Paged GQA decode: q [B,H,D]; pools [NB+1, bs, KH, D] (last block =
    scratch), or stacked ``[L, NB+1, bs, KH, D]`` and read in place at
    ``layer`` (scalar int32); bt [B, nbs] int32 *physical* block ids (must
    be pre-clipped in-bounds — the wrapper maps unallocated ``-1`` entries
    to the scratch block, whose keys the mask hides); mask [B, nbs*bs]
    (nonzero = attend, carrying ring validity + causality + window per
    slot).

    Returns [B, H, D].  Grid ``(batch, blocks_per_slot)``: the block table
    is scalar-prefetched and indexes the kv BlockSpec directly, and every kv
    head's GQA query group shares the grid step — so each pool block is
    DMA'd exactly once and the slot's cache streams HBM->VMEM once per
    decode step, with no gathered ``[B, C_pad, KH, D]`` intermediate ever
    materialized.
    """
    b, h, d = q.shape
    k_pool, v_pool, layer = _layered(k_pool, v_pool, layer)
    bs, kh = k_pool.shape[2], k_pool.shape[3]
    assert h % kh == 0, (h, kh)
    assert mask.shape == (b, bt.shape[1] * bs), (mask.shape, bt.shape, bs)
    out = _paged_call(q.reshape(b, kh, h // kh, d), k_pool, v_pool, bt,
                      layer, _blocked_mask(mask[:, None, :], bs),
                      softcap=softcap, interpret=interpret,
                      name="paged_decode_attention")
    return out.reshape(b, h, d)


def paged_verify_attention_bhd(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, bt: jax.Array,
                               mask: jax.Array, *,
                               layer: Optional[jax.Array] = None,
                               softcap: Optional[float] = None,
                               interpret: bool = False) -> jax.Array:
    """Paged GQA *verify*: ``kq`` draft query tokens per slot in one pass.

    q [B, KQ, H, D]; pools and ``layer`` as
    :func:`paged_decode_attention_bhd`; bt [B, nbs] pre-clipped
    physical block ids; mask [B, KQ, nbs*bs] — row ``i`` carries the
    causality set of position ``pos + i`` (plus ring validity/window), so
    draft token ``i`` attends every accepted key *and* the keys scattered
    for drafts ``0..i`` but not later ones.

    Returns [B, KQ, H, D].  The ``KQ`` positions × the GQA group form the
    ``R = KQ*g`` query rows of each kv head, so the streaming structure —
    each pool block DMA'd exactly once per verify step, amortized over all
    ``kq`` tokens, which is the whole speculative-decoding bandwidth win —
    is the decode kernel's, and with ``KQ == 1`` the arithmetic is too.
    """
    b, kq, h, d = q.shape
    k_pool, v_pool, layer = _layered(k_pool, v_pool, layer)
    bs, kh = k_pool.shape[2], k_pool.shape[3]
    assert h % kh == 0, (h, kh)
    g = h // kh
    c = bt.shape[1] * bs
    assert mask.shape == (b, kq, c), (mask.shape, b, kq, bt.shape, bs)
    # rows ordered (draft position, group member): row i*g + m
    q4 = q.reshape(b, kq, kh, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, kq * g, d)
    rows = jnp.broadcast_to(mask[:, :, None, :], (b, kq, g, c)).reshape(
        b, kq * g, c)
    out = _paged_call(q4, k_pool, v_pool, bt, layer, _blocked_mask(rows, bs),
                      softcap=softcap, interpret=interpret,
                      name="paged_verify_attention")
    return out.reshape(b, kh, kq, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kq, h, d)
