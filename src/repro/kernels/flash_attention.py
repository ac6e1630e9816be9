"""Blocked flash attention (prefill hot spot) as a Pallas TPU kernel.

Grid ``(batch, q_heads, num_q_blocks, num_kv_blocks)``; the kv dimension is
the innermost ("arbitrary") axis so the (m, l, acc) online-softmax state
lives in VMEM scratch across kv iterations.  Block shapes are MXU-aligned
(multiples of 128 on the seq axes, head_dim padded to 128).

Supports causal masking, sliding windows (gemma2 / recurrentgemma local
attention and the documented `swa` long-context variant), GQA via the kv-head
index map, and attention-logit soft-capping.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int,
                  window: Optional[int], softcap: Optional[float],
                  seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # causal block skip: no key in this block can be visible to any query
    should_run = k_start <= q_start + block_q - 1
    if window is not None:
        # window block skip: every key is older than q_start - window
        should_run &= k_start + block_k - 1 > q_start - window

    @pl.when(should_run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                      # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                      # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos <= q_pos
        mask &= k_pos < seq_len                                   # key padding
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)                # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         softcap: Optional[float] = None, block_q: int = 128,
                         block_k: int = 128, kv_len: Optional[int] = None,
                         interpret: bool = False) -> jax.Array:
    """q [B,H,S,D], k/v [B,KH,S,D] (S, D already padded to block multiples).

    ``kv_len``: real (unpadded) sequence length — keys at positions >= kv_len
    are masked out.  ``causal`` must be True (decoder-only framework).
    """
    assert causal, "only causal attention is supported"
    b, h, s, d = q.shape
    kh = k.shape[1]
    assert h % kh == 0
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    if kv_len is None:
        kv_len = s
    scale = 1.0 / math.sqrt(d)
    grid = (b, h, s // block_q, s // block_k)

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, h_, iq, ik: (b_, h_ * kh // h, ik, 0))
    out_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0))

    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, window=window,
                               softcap=softcap, seq_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # m
            pltpu.VMEM((block_q, 1), jnp.float32),     # l
            pltpu.VMEM((block_q, d), jnp.float32),     # acc
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
