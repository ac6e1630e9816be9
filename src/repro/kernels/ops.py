"""jit'd public wrappers around the Pallas kernels.

Handle layout ([B,S,H,D] model layout <-> [B,H,S,D] kernel layout), padding
to block multiples, interpret-mode selection (CPU validates the kernel body
in Python; TPU compiles it), and mask precomputation for the decode kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention_bhd,
                                            paged_decode_attention_bhd,
                                            paged_verify_attention_bhd)
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.int8_matmul import int8_matmul_pallas, quantize_int8
from repro.kernels.rglru_scan import rglru_scan_pallas


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Model layout: q [B,S,H,D], k/v [B,S,KH,D] -> [B,S,H,D]."""
    if interpret is None:
        interpret = _on_cpu()
    b, s, h, d = q.shape
    qt = _pad_to(jnp.swapaxes(q, 1, 2), 2, max(block_q, block_k))
    kt = _pad_to(jnp.swapaxes(k, 1, 2), 2, max(block_q, block_k))
    vt = _pad_to(jnp.swapaxes(v, 1, 2), 2, max(block_q, block_k))
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k, kv_len=s, interpret=interpret)
    return jnp.swapaxes(out[:, :, :s], 1, 2)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "block_c",
                                             "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     key_pos: jax.Array, pos: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None, block_c: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q [B,1,H,D] or [B,H,D]; caches [B,C,KH,D]; key_pos [C] or [B,C];
    pos scalar or [B] (per-row decode positions after a masked, length-
    bucketed prefill)."""
    if interpret is None:
        interpret = _on_cpu()
    if q.ndim == 4:
        q3 = q[:, 0]
    else:
        q3 = q
    c = k_cache.shape[1]
    bc = min(block_c, c) if c % block_c else block_c
    if c % bc:
        bc = c            # tiny caches: single block
    pos_b = pos[..., None] if pos.ndim else pos     # [B,1] | scalar
    mask = (key_pos >= 0) & (key_pos <= pos_b)
    if window is not None:
        mask &= key_pos > pos_b - window
    kp = _pad_to(k_cache, 1, bc)
    vp = _pad_to(v_cache, 1, bc)
    maskp = _pad_to(mask if mask.ndim == 2 else mask[None, :], 1, bc)
    out = decode_attention_bhd(q3, kp, vp, maskp, softcap=softcap,
                               block_c=bc, interpret=interpret)
    if q.ndim == 4:
        return out[:, None]
    return out


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           bt: jax.Array, key_pos: jax.Array, pos: jax.Array,
                           layer: Optional[jax.Array] = None,
                           *, window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Paged decode through the block table — no gathered cache temporary.

    q [B,1,H,D] or [B,H,D]; k_pool/v_pool [NB+1, bs, KH, D] (last block =
    scratch), or stacked over layers ``[L, NB+1, bs, KH, D]`` and read in
    place at ``layer`` (scalar int32); bt [B, nbs] int32 block table (-1 =
    unmapped, redirected to the scratch block whose keys the validity mask
    hides); key_pos [B, C] per-ring-slot absolute positions (-1 = empty,
    C == nbs*bs); pos [B] per-slot decode positions.
    """
    if interpret is None:
        interpret = _on_cpu()
    q3 = q[:, 0] if q.ndim == 4 else q
    b = q3.shape[0]
    nbs = bt.shape[1]
    scratch = k_pool.shape[-4] - 1
    assert key_pos.shape == (b, nbs * k_pool.shape[-3]), \
        (key_pos.shape, bt.shape, k_pool.shape)
    # validity is position-driven, exactly like the contiguous decode mask
    mask = (key_pos >= 0) & (key_pos <= pos[:, None])
    if window is not None:
        mask &= key_pos > pos[:, None] - window
    bt_read = jnp.where(bt >= 0, bt, scratch).astype(jnp.int32)
    out = paged_decode_attention_bhd(q3, k_pool, v_pool, bt_read, mask,
                                     layer=layer, softcap=softcap,
                                     interpret=interpret)
    if q.ndim == 4:
        return out[:, None]
    return out


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_verify_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           bt: jax.Array, key_pos: jax.Array, pos: jax.Array,
                           layer: Optional[jax.Array] = None,
                           *, window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Speculative-verify attention: ``KQ`` draft tokens per slot, one pass.

    q [B, KQ, H, D]; pools/bt/key_pos/layer as
    :func:`paged_decode_attention`; pos [B] is the position of the *first*
    fed token, so q row ``i`` decodes at position ``pos + i`` and its mask
    admits keys with ``key_pos <= pos + i`` — the per-row causality that
    lets the drafts' freshly-scattered keys be attended by later drafts
    only.  Rows past a slot's true draft count are fully masked by
    construction when their keys were never scattered; callers discard
    their outputs regardless.
    """
    if interpret is None:
        interpret = _on_cpu()
    b, kq = q.shape[0], q.shape[1]
    nbs = bt.shape[1]
    scratch = k_pool.shape[-4] - 1
    assert key_pos.shape == (b, nbs * k_pool.shape[-3]), \
        (key_pos.shape, bt.shape, k_pool.shape)
    pos_i = pos[:, None, None] + jnp.arange(kq)[None, :, None]   # [B,KQ,1]
    mask = (key_pos[:, None, :] >= 0) & (key_pos[:, None, :] <= pos_i)
    if window is not None:
        mask &= key_pos[:, None, :] > pos_i - window
    bt_read = jnp.where(bt >= 0, bt, scratch).astype(jnp.int32)
    return paged_verify_attention_bhd(q, k_pool, v_pool, bt_read, mask,
                                      layer=layer, softcap=softcap,
                                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def rglru_scan(log_a: jax.Array, b: jax.Array,
               h0: Optional[jax.Array] = None, *, block_r: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """log_a/b [B,S,R] f32, h0 [B,R] f32 or None -> h [B,S,R] f32."""
    if interpret is None:
        interpret = _on_cpu()
    bb, s, r = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((bb, r), jnp.float32)
    br = block_r if r % block_r == 0 else r
    la = _pad_to(log_a, 2, br)
    bv = _pad_to(b, 2, br)
    h0p = _pad_to(h0, 1, br)
    out = rglru_scan_pallas(la, bv, h0p, block_r=br, interpret=interpret)
    return out[:, :, :r]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def int8_matmul(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
                block_m: int = 128, block_n: int = 128, block_k: int = 512,
                interpret: Optional[bool] = None) -> jax.Array:
    """x [..., K] @ int8 w_q [K, N] * scale [1, N] -> [..., N]."""
    if interpret is None:
        interpret = _on_cpu()
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w_q.shape[1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm = min(block_m, m) if m < block_m else block_m
    bk = min(block_k, k) if k < block_k else block_k
    bn = min(block_n, n) if n < block_n else block_n
    xp = _pad_to(_pad_to(x2, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_q, 0, bk), 1, bn)
    sp = _pad_to(scale, 1, bn)
    y = int8_matmul_pallas(xp, wp, sp, block_m=bm, block_n=bn, block_k=bk,
                           interpret=interpret)
    return y[:m, :n].reshape(*lead, n)


__all__ = ["flash_attention", "decode_attention", "paged_decode_attention",
           "paged_verify_attention", "rglru_scan", "int8_matmul",
           "quantize_int8"]
