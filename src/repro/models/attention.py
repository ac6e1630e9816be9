"""GQA attention: full / sliding-window, qk-norm, bias, logit soft-capping.

Three entry points sharing one set of parameters:

- :func:`attend_full`     — train / prefill over a whole sequence,
- :func:`attend_decode`   — one token against a (ring-buffer) KV cache,
- :func:`prefill_cache`   — populate the cache while running prefill.

Prefill supports *masked* left-padded batches: pass per-row positions
[B, S] where pad slots hold negative values — pad keys are masked out of
the softmax and written with ``key_pos == -1``, so the output for real
tokens (and every later decode step) is independent of the padded width.

``impl="xla"`` is the pure-jnp reference; ``impl="pallas"`` dispatches the
Pallas kernels — flash attention for the full-sequence path (prefill hot
spot), the streaming decode kernel for :func:`attend_decode`, and the
block-table-fused paged kernel for :func:`attend_decode_paged`.  Decode
paths raise on unknown ``impl`` values (``DECODE_IMPLS``) instead of
silently running the XLA math.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import BlockSpec, ModelConfig
from repro.models.kvcache import attn_cache_len
from repro.models.layers import (ParamBuilder, apply_rope, rms_norm_headwise,
                                 softcap)
from repro.sharding.rules import logical_constraint

NEG_INF = -2.0 ** 30

#: decode-path implementations: "xla" (masked-sdpa reference), "chunked"
#: (alias — chunking is a prefill lever; one-token decode runs the same
#: sdpa math), "pallas" (streaming online-softmax kernel).
DECODE_IMPLS = ("xla", "chunked", "pallas")


def _check_decode_impl(impl: str) -> None:
    if impl not in DECODE_IMPLS:
        raise ValueError(
            f"unknown decode impl {impl!r}: expected one of {DECODE_IMPLS}")


def effective_decode_impl(impl: str, cfg: ModelConfig) -> str:
    """The impl the paged decode/verify paths will actually execute.

    ``impl="pallas"`` with ``kv_dtype="int8"`` runs the XLA gather+dequant
    reference (per-block in-kernel dequant is future work) — backends
    surface this in ``BackendInfo.attn_impl`` so benchmarks can assert the
    kernel they think they're measuring is the one running.
    """
    _check_decode_impl(impl)
    if impl == "pallas" and cfg.kv_dtype == "int8":
        return "xla"
    return impl


_INT8_PALLAS_NOTED = False


def _note_int8_pallas_fallback(cfg: ModelConfig) -> None:
    """The pallas->xla downgrade for int8 KV used to be silent; now it warns
    once per process, or raises when ``REPRO_STRICT_IMPL`` is set (CI /
    benchmarks that must fail rather than quietly measure the wrong path).
    """
    global _INT8_PALLAS_NOTED
    import os
    import warnings
    msg = ("impl='pallas' with kv_dtype='int8' falls back to the XLA "
           "gather+dequant decode path (in-kernel dequant not implemented); "
           "set impl='xla' to silence, or unset kv_dtype int8 to get the "
           "fused kernel")
    if os.environ.get("REPRO_STRICT_IMPL"):
        raise ValueError(msg + " (strict: REPRO_STRICT_IMPL is set)")
    if not _INT8_PALLAS_NOTED:
        warnings.warn(msg, RuntimeWarning, stacklevel=3)
        _INT8_PALLAS_NOTED = True


def init_attention(pb: ParamBuilder, name: str, cfg: ModelConfig):
    d, q, kv, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.resolved_head_dim
    sub = pb.scope(name)
    sub.add("wq", (d, q), ("embed", "qkv"))
    sub.add("wk", (d, kv), ("embed", "qkv"))
    sub.add("wv", (d, kv), ("embed", "qkv"))
    sub.add("wo", (q, d), ("qkv", "embed"))
    if cfg.qkv_bias:
        sub.add("bq", (q,), ("qkv",), init="zeros")
        sub.add("bk", (kv,), ("qkv",), init="zeros")
        sub.add("bv", (kv,), ("qkv",), init="zeros")
    if cfg.qk_norm:
        sub.add("q_norm", (hd,), (None,), init="ones")
        sub.add("k_norm", (hd,), (None,), init="ones")


def _project_qkv(params: Dict, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [B,S,d] -> q [B,S,h,hd], k/v [B,S,n_kv,hd]; RoPE applied."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm_headwise(params["q_norm"], q)
        k = rms_norm_headwise(params["k_norm"], k)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = logical_constraint(q, "batch", None, "heads", None)
    k = logical_constraint(k, "batch", None, "kv_heads", None)
    v = logical_constraint(v, "batch", None, "kv_heads", None)
    return q, k, v


def _sdpa(cfg: ModelConfig, spec: BlockSpec, q: jax.Array, k: jax.Array,
          v: jax.Array, q_pos: jax.Array, k_pos: jax.Array,
          k_valid: Optional[jax.Array] = None) -> jax.Array:
    """Grouped scaled-dot-product attention with position-based masking.

    q [B,Sq,h,hd], k/v [B,Sk,n_kv,hd]; q_pos [Sq], k_pos [Sk] absolute
    positions; mask = causal (k_pos <= q_pos) & window & validity.

    Per-sequence positions (the paged-decode path, where every slot sits at
    its own position) pass q_pos [B,Sq] / k_pos [B,Sk] (k_valid [B,Sk]); the
    mask then varies along the batch axis but the math is unchanged.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    g = h // cfg.n_kv_heads
    qg = q.reshape(b, sq, cfg.n_kv_heads, g, hd)
    logits = jnp.einsum("bsngd,btnd->bngst", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (hd ** -0.5)
    logits = softcap(logits, cfg.attn_logit_softcap)
    # shared positions promote to a broadcastable batch axis, so one mask
    # expression serves both calling conventions
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    if k_pos.ndim == 1:
        k_pos = k_pos[None]
    if k_valid is not None and k_valid.ndim == 1:
        k_valid = k_valid[None]
    mask = k_pos[:, None, :] <= q_pos[:, :, None]                 # causal
    if spec.window is not None:
        mask &= k_pos[:, None, :] > (q_pos[:, :, None] - spec.window)
    if k_valid is not None:
        mask &= k_valid[:, None, :]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngst,btnd->bsngd", probs.astype(v.dtype), v)
    # a cache wider than the activations (float32 KV under bf16 params)
    # must not widen the residual stream: return the queries' dtype, as the
    # Pallas kernels do
    return out.reshape(b, sq, h * hd).astype(q.dtype)


def _sdpa_chunked(cfg: ModelConfig, spec: BlockSpec, q: jax.Array,
                  k: jax.Array, v: jax.Array, q_pos: jax.Array,
                  k_pos: jax.Array, k_valid: Optional[jax.Array] = None,
                  block: int = 1024) -> jax.Array:
    """Online-softmax attention over key blocks (flash-style, pure XLA).

    Never materializes the [.., Sq, Sk] logits — the SPerf lever for the
    memory-term-dominated prefill rows: working set drops from O(Sq*Sk) to
    O(Sq*block).  Semantics identical to :func:`_sdpa` (causal + window +
    softcap + validity masking), including the per-row calling convention
    (``q_pos``/``k_pos`` [B, S], ``k_valid`` [B, Sk]) used by masked
    prefill.  Sk must be divisible by ``block`` (pad upstream or pick a
    divisor).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    block = min(block, sk)
    assert sk % block == 0, (sk, block)
    g = h // cfg.n_kv_heads
    qg = q.reshape(b, sq, cfg.n_kv_heads, g, hd)
    kb = k.reshape(b, sk // block, block, cfg.n_kv_heads, hd)
    vb = v.reshape(b, sk // block, block, cfg.n_kv_heads, hd)
    nb = sk // block
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None], (b, sk))
    pb = k_pos.reshape(b, nb, block).swapaxes(0, 1)          # [nb, B, block]
    if k_valid is not None:
        if k_valid.ndim == 1:
            k_valid = jnp.broadcast_to(k_valid[None], (b, sk))
        vld = k_valid.reshape(b, nb, block).swapaxes(0, 1)
    else:
        vld = jnp.ones((nb, b, block), bool)
    scale = hd ** -0.5

    def step(carry, inp):
        m, l, acc = carry                     # [b,n,g,sq], same, [b,n,g,sq,hd]
        k_c, v_c, kp, kv = inp                # [b,block,n,hd] x2, [b,block] x2
        logits = jnp.einsum("bsngd,btnd->bngst", qg, k_c,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, cfg.attn_logit_softcap)
        msk = kp[:, None, :] <= q_pos[:, :, None]             # [b, sq, block]
        if spec.window is not None:
            msk &= kp[:, None, :] > (q_pos[:, :, None] - spec.window)
        msk &= kv[:, None, :]
        logits = jnp.where(msk[:, None, None, :, :], logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # explicit zero under the mask: a fully-masked block (all-pad keys
        # under masked prefill) keeps m at NEG_INF, where exp(logit - m)
        # would be 1, not 0
        p = jnp.where(msk[:, None, None, :, :],
                      jnp.exp(logits - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bngst,btnd->bngsd", p.astype(jnp.float32), v_c.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, cfg.n_kv_heads, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, cfg.n_kv_heads, g, sq), jnp.float32)
    a0 = jnp.zeros((b, cfg.n_kv_heads, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), pb, vld))
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # [b,n,g,sq,hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h * hd)
    return out.astype(q.dtype)


def attend_full(params: Dict, cfg: ModelConfig, spec: BlockSpec, x: jax.Array,
                positions: jax.Array, impl: str = "xla") -> jax.Array:
    """Full-sequence causal attention (train / prefill)."""
    _check_decode_impl(impl)
    q, k, v = _project_qkv(params, cfg, x, positions)
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(
            q, k, v, causal=True, window=spec.window,
            softcap=cfg.attn_logit_softcap)
        out = out.reshape(*x.shape[:2], cfg.q_dim)
    elif impl == "chunked":
        out = _sdpa_chunked(cfg, spec, q, k, v, positions, positions)
    else:
        out = _sdpa(cfg, spec, q, k, v, positions, positions)
    y = out @ params["wo"]
    return logical_constraint(y, "batch", None, "embed")


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, head) absmax int8 quantization. x [B,S,n_kv,hd] ->
    (q8 [B,S,n_kv,hd] int8, scale [B,S,n_kv] f32)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q8 = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q8, -127, 127).astype(jnp.int8), scale


def _dequantize_kv(q8: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q8.astype(jnp.float32) * scale[..., None]).astype(dtype)


def prefill_cache(params: Dict, cfg: ModelConfig, spec: BlockSpec,
                  x: jax.Array, positions: jax.Array, cache: Dict,
                  impl: str = "xla") -> Tuple[jax.Array, Dict]:
    """Run prefill AND write k/v into the (possibly ring) cache.

    ``positions`` is [S] (batch-shared) or [B, S] (per-row, the masked
    left-padded prefill path).  Per-row positions may be *negative* at pad
    slots; those keys are masked out of the attention (``k_valid``) and
    written with ``key_pos == -1``, so pads never become valid cache keys
    and the computed prefix is bit-for-bit the unpadded continuation.

    The returned cache carries per-row ``key_pos [B, C]`` and ``pos [B]``
    (rows in one wave may hold different true lengths).
    """
    _check_decode_impl(impl)   # "pallas" prefills via _sdpa (flash kernel
    b, s = x.shape[:2]         # is not wired to the cache-writing path)
    q, k, v = _project_qkv(params, cfg, x, positions)
    pos_b = positions if positions.ndim == 2 \
        else jnp.broadcast_to(positions[None], (b, s))
    valid = pos_b >= 0                                           # [B, S]
    if impl == "chunked":
        out = _sdpa_chunked(cfg, spec, q, k, v, pos_b, pos_b, k_valid=valid)
    else:
        out = _sdpa(cfg, spec, q, k, v, pos_b, pos_b, k_valid=valid)
    y = out @ params["wo"]
    y = logical_constraint(y, "batch", None, "embed")
    c = cache["k"].shape[1]
    k_tail, v_tail, pos_tail, valid_tail = k, v, pos_b, valid
    if k.shape[1] > c:          # sliding window: only the last c tokens survive
        k_tail, v_tail = k[:, -c:], v[:, -c:]
        pos_tail, valid_tail = pos_b[:, -c:], valid[:, -c:]
    # each row's tail positions are S' contiguous integers, so `% c` maps
    # them to distinct ring slots — pad writes land on slots no valid token
    # occupies and are neutralized by key_pos == -1
    slots = pos_tail % c                                         # [B, S']
    rows = jnp.arange(b)[:, None]
    key_pos = cache["key_pos"].at[rows, slots].set(
        jnp.where(valid_tail, pos_tail, -1).astype(jnp.int32))
    new_pos = pos_b[:, -1].astype(jnp.int32) + 1                 # [B]
    if cfg.kv_dtype == "int8":
        k8, ks = _quantize_kv(k_tail)
        v8, vs = _quantize_kv(v_tail)
        new_cache = {"k": cache["k"].at[rows, slots].set(k8),
                     "v": cache["v"].at[rows, slots].set(v8),
                     "k_scale": cache["k_scale"].at[rows, slots].set(ks),
                     "v_scale": cache["v_scale"].at[rows, slots].set(vs),
                     "key_pos": key_pos,
                     "pos": new_pos}
        return y, new_cache
    ck = cache["k"].at[rows, slots].set(k_tail.astype(cache["k"].dtype))
    cv = cache["v"].at[rows, slots].set(v_tail.astype(cache["v"].dtype))
    new_cache = {"k": ck, "v": cv, "key_pos": key_pos, "pos": new_pos}
    return y, new_cache


def extend_cache(params: Dict, cfg: ModelConfig, spec: BlockSpec,
                 x: jax.Array, positions: jax.Array, seq_valid: jax.Array,
                 cache: Dict, impl: str = "xla") -> Tuple[jax.Array, Dict]:
    """Prefill a *continuation*: run ``x``'s tokens at absolute positions
    ``positions`` against a **paged** cache that already holds keys for
    positions below them (an adopted shared prefix and/or earlier chunks),
    writing the new k/v into the slot's blocks.

    x [B, S, d]; positions [B, S] absolute, right-aligned payload (pads on
    the left, ``seq_valid`` False there).  Only valid for specs where
    ``attn_cache_len == max_len`` (no effective sliding window — see
    ``kvcache.prefix_sharing_supported``): positions never wrap the ring,
    so ``ring slot == position`` and a shared block is never rewritten
    (the copy-on-write rule).  Pad rows' writes are redirected to the
    scratch block and their ``key_pos`` entries are left untouched, so a
    padded chunk is bit-for-bit the unpadded continuation.

    The chunk's k/v are scattered into the pool first, then attended
    through the block table with the chunk's own causal mask, so token i
    of the chunk sees: the adopted prefix, all earlier chunks, and chunk
    tokens 0..i.  ``impl="pallas"`` reads via the same gather as the XLA
    reference (extend is not the decode hot loop; the paged kernel is
    decode-shaped).
    """
    _check_decode_impl(impl)
    b, s = x.shape[:2]
    q, k, v = _project_qkv(params, cfg, x, positions)
    bt, key_pos = cache["bt"], cache["key_pos"]
    c_pad = key_pos.shape[-1]
    bsz = cache["k_pool"].shape[1]
    nbs = c_pad // bsz
    scratch = cache["k_pool"].shape[0] - 1

    # scatter the chunk into the slot's blocks (scratch for pads/unmapped)
    blk = jnp.clip(positions // bsz, 0, nbs - 1)                  # [B, S]
    off = positions % bsz
    phys = jnp.take_along_axis(bt, blk, axis=1)                   # [B, S]
    tgt = jnp.where(seq_valid & (phys >= 0), phys, scratch)
    quant = cfg.kv_dtype == "int8"
    if quant:
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        kp = cache["k_pool"].at[tgt, off].set(k8)
        vp = cache["v_pool"].at[tgt, off].set(v8)
        ksp = cache["k_scale_pool"].at[tgt, off].set(ks)
        vsp = cache["v_scale_pool"].at[tgt, off].set(vs)
    else:
        kp = cache["k_pool"].at[tgt, off].set(
            k.astype(cache["k_pool"].dtype))
        vp = cache["v_pool"].at[tgt, off].set(
            v.astype(cache["v_pool"].dtype))

    # ring slot == position (no wrap), so key_pos updates need no scatter:
    # mark exactly this chunk's position range valid, leave the rest alone
    end = positions[:, -1]                                        # [B]
    n_valid = jnp.sum(seq_valid, axis=-1)
    lo = end + 1 - n_valid                                        # chunk start
    iota = jnp.arange(c_pad, dtype=jnp.int32)[None, :]
    in_chunk = (iota >= lo[:, None]) & (iota <= end[:, None])
    new_key_pos = jnp.where(in_chunk, iota, key_pos)
    new_pos = (end + 1).astype(jnp.int32)

    # attend through the table over the dense gather (prefix + chunk)
    read = jnp.clip(bt[:, :nbs], 0, None)
    if quant:
        ck = _dequantize_kv(kp[read].reshape(b, c_pad, cfg.n_kv_heads, -1),
                            ksp[read].reshape(b, c_pad, cfg.n_kv_heads),
                            k.dtype)
        cv = _dequantize_kv(vp[read].reshape(b, c_pad, cfg.n_kv_heads, -1),
                            vsp[read].reshape(b, c_pad, cfg.n_kv_heads),
                            v.dtype)
    else:
        ck = kp[read].reshape(b, c_pad, cfg.n_kv_heads, -1)
        cv = vp[read].reshape(b, c_pad, cfg.n_kv_heads, -1)
    sdpa = _sdpa_chunked if impl == "chunked" else _sdpa
    out = sdpa(cfg, spec, q, ck, cv, positions, new_key_pos,
               k_valid=new_key_pos >= 0)
    y = out @ params["wo"]
    y = logical_constraint(y, "batch", None, "embed")
    new_cache = {"k_pool": kp, "v_pool": vp, "bt": bt,
                 "key_pos": new_key_pos, "pos": new_pos}
    if quant:
        new_cache["k_scale_pool"] = ksp
        new_cache["v_scale_pool"] = vsp
    return y, new_cache


def attend_decode(params: Dict, cfg: ModelConfig, spec: BlockSpec,
                  x: jax.Array, cache: Dict, impl: str = "xla",
                  ) -> Tuple[jax.Array, Dict]:
    """One-token decode against the cache. x: [B, 1, d].

    ``pos`` is per-row [B] and ``key_pos`` per-row [B, C] — after a masked
    (length-bucketed) prefill each row sits at its own true position, so
    every row writes and attends its own ring independently.
    """
    _check_decode_impl(impl)
    b = x.shape[0]
    pos = cache["pos"]                                           # [B]
    positions = pos[:, None]                                     # [B, 1]
    q, k, v = _project_qkv(params, cfg, x, positions)
    c = cache["k"].shape[1]
    slot = pos % c                                               # [B]
    rows = jnp.arange(b)
    quant = cfg.kv_dtype == "int8"
    if quant:
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        c8k = cache["k"].at[rows, slot].set(k8[:, 0])
        c8v = cache["v"].at[rows, slot].set(v8[:, 0])
        csk = cache["k_scale"].at[rows, slot].set(ks[:, 0])
        csv = cache["v_scale"].at[rows, slot].set(vs[:, 0])
        ck = _dequantize_kv(c8k, csk, k.dtype)
        cv = _dequantize_kv(c8v, csv, v.dtype)
    else:
        ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
    key_pos = cache["key_pos"].at[rows, slot].set(pos.astype(jnp.int32))
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.decode_attention(
            q, ck, cv, key_pos, pos, window=spec.window,
            softcap=cfg.attn_logit_softcap)
        out = out.reshape(x.shape[0], 1, cfg.q_dim)
    else:
        out = _sdpa(cfg, spec, q, ck, cv, positions, key_pos,
                    k_valid=key_pos >= 0)
    y = out @ params["wo"]
    y = logical_constraint(y, "batch", None, "embed")
    if quant:
        new_cache = {"k": c8k, "v": c8v, "k_scale": csk, "v_scale": csv,
                     "key_pos": key_pos, "pos": pos + 1}
    else:
        new_cache = {"k": ck, "v": cv, "key_pos": key_pos, "pos": pos + 1}
    return y, new_cache


def attend_decode_paged(params: Dict, cfg: ModelConfig, spec: BlockSpec,
                        x: jax.Array, cache: Dict, impl: str = "xla",
                        write_mask: Optional[jax.Array] = None,
                        layer: Optional[jax.Array] = None,
                        ) -> Tuple[jax.Array, Dict]:
    """One-token decode against a *paged* KV cache. x: [B, 1, d].

    ``cache`` holds the layer's shared block pool plus this batch's view of
    it (see :func:`repro.models.kvcache.init_paged_block_cache`): ``k_pool``
    / ``v_pool`` ``[NB+1, bs, n_kv, hd]`` (last block = scratch), ``bt``
    block table, ``key_pos`` ring positions, ``pos`` decode position.  Two
    batch semantics, chosen by ``pos``'s rank:

    - **per-slot** (``pos [B]``, ``bt [B, nbs]``, ``key_pos [B, C]``) — each
      batch row is an independent slot at its own position (TensorBackend's
      batched decode),
    - **shared** (``pos`` scalar, ``bt [nbs]``, ``key_pos [C]``) — the batch
      shares one position stream (the pipeline tick's micro-batch; B == 1).

    The new k/v are **scattered into the pool first**, then attended through
    the slot's block table, so the attended key set is element-for-element
    identical to the contiguous ring buffer (extra never-written tail slots
    stay masked via ``key_pos == -1``) — greedy decode parity between
    layouts is exact, not approximate.  ``write_mask`` (bool, [B] or scalar)
    redirects masked rows' writes to the scratch block and freezes their
    ``key_pos``/``pos``, so idle slots and dead pipeline ticks can never
    touch another slot's blocks.

    ``layer`` (scalar int32) says the pool leaves are stacked over layers
    (``[L, NB+1, bs, n_kv, hd]``, the decode layer scan's carry): this
    token is scattered into the stacked pool at ``(layer, block, offset)``
    and the pool is read at ``layer`` in place, so no layer's pool is ever
    sliced out or written back.  The returned pools stay stacked.

    ``impl`` selects how the pool is *read* (unknown values raise):

    - ``"pallas"`` — :func:`repro.kernels.ops.paged_decode_attention`: the
      block table is scalar-prefetched into the kernel and drives the kv
      BlockSpec index map, so the slot's blocks stream HBM->VMEM once with
      online-softmax state in scratch.  No ``[B, C_pad, n_kv, hd]`` gather
      temporary is ever materialized — the decode cache-read term halves.
    - ``"xla"`` / ``"chunked"`` — the reference path: gather the slot's
      blocks back in ring order, then run the masked sdpa over the dense
      copy.  ``kv_dtype="int8"`` always takes this path (per-block in-kernel
      dequant is future work) — the pool is dequantized during the gather.
    """
    _check_decode_impl(impl)
    b = x.shape[0]
    shared = cache["pos"].ndim == 0
    if shared:
        assert b == 1, "shared-position paged decode supports a single lane"
        pos = cache["pos"][None]
        bt = cache["bt"][None]
        key_pos = cache["key_pos"][None]
    else:
        pos, bt, key_pos = cache["pos"], cache["bt"], cache["key_pos"]
    c_pad = key_pos.shape[-1]
    bsz = cache["k_pool"].shape[-3]                   # tokens per block
    nbs = c_pad // bsz                                # this spec's table span
    scratch = cache["k_pool"].shape[-4] - 1
    at = () if layer is None else (layer,)            # the pools' layer
    positions = pos[:, None]                                      # [B, 1]
    q, k, v = _project_qkv(params, cfg, x, positions)

    # scatter this token's k/v into its slot's current block (or scratch)
    ring = pos % c_pad                                            # [B]
    blk, off = ring // bsz, ring % bsz
    phys = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]    # [B]
    tgt = jnp.where(phys >= 0, phys, scratch)
    wmask = None
    if write_mask is not None:
        wmask = jnp.broadcast_to(jnp.asarray(write_mask, bool), (b,))
        tgt = jnp.where(wmask, tgt, scratch)
    quant = cfg.kv_dtype == "int8"
    if quant:
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        kp = cache["k_pool"].at[at + (tgt, off)].set(k8[:, 0])
        vp = cache["v_pool"].at[at + (tgt, off)].set(v8[:, 0])
        ksp = cache["k_scale_pool"].at[at + (tgt, off)].set(ks[:, 0])
        vsp = cache["v_scale_pool"].at[at + (tgt, off)].set(vs[:, 0])
    else:
        kp = cache["k_pool"].at[at + (tgt, off)].set(
            k[:, 0].astype(cache["k_pool"].dtype))
        vp = cache["v_pool"].at[at + (tgt, off)].set(
            v[:, 0].astype(cache["v_pool"].dtype))

    new_key_pos = key_pos.at[jnp.arange(b), ring].set(pos.astype(jnp.int32))
    new_pos = pos + 1
    if wmask is not None:
        new_key_pos = jnp.where(wmask[:, None], new_key_pos, key_pos)
        new_pos = jnp.where(wmask, new_pos, pos)

    if impl == "pallas" and not quant:
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(
            q, kp, vp, bt[:, :nbs], new_key_pos, pos, layer,
            window=spec.window, softcap=cfg.attn_logit_softcap)
        out = out.reshape(b, 1, cfg.q_dim)
    else:
        if impl == "pallas":
            _note_int8_pallas_fallback(cfg)
        # reference / int8 fallback: gather the slot's blocks back in ring
        # order ([B, C_pad, n_kv, hd]); unmapped entries read block 0
        # garbage, masked via key_pos == -1
        read = at + (jnp.clip(bt[:, :nbs], 0, None),)
        if quant:
            ck = _dequantize_kv(
                kp[read].reshape(b, c_pad, cfg.n_kv_heads, -1),
                ksp[read].reshape(b, c_pad, cfg.n_kv_heads), k.dtype)
            cv = _dequantize_kv(
                vp[read].reshape(b, c_pad, cfg.n_kv_heads, -1),
                vsp[read].reshape(b, c_pad, cfg.n_kv_heads), v.dtype)
        else:
            ck = kp[read].reshape(b, c_pad, cfg.n_kv_heads, -1)
            cv = vp[read].reshape(b, c_pad, cfg.n_kv_heads, -1)
        out = _sdpa(cfg, spec, q, ck, cv, positions, new_key_pos,
                    k_valid=new_key_pos >= 0)
    y = out @ params["wo"]
    y = logical_constraint(y, "batch", None, "embed")
    new_cache = {"k_pool": kp, "v_pool": vp, "bt": cache["bt"],
                 "key_pos": new_key_pos if not shared else new_key_pos[0],
                 "pos": new_pos if not shared else new_pos[0]}
    if quant:
        new_cache["k_scale_pool"] = ksp
        new_cache["v_scale_pool"] = vsp
    return y, new_cache


def attend_verify_paged(params: Dict, cfg: ModelConfig, spec: BlockSpec,
                        x: jax.Array, lens: jax.Array, cache: Dict,
                        impl: str = "xla") -> Tuple[jax.Array, Dict]:
    """Multi-token speculative *verify* against a paged KV cache.

    x [B, K, d] — row ``b``'s first ``lens[b]`` tokens are the last
    accepted token plus the draft continuation, left-aligned, occupying
    absolute positions ``pos[b] .. pos[b] + lens[b] - 1``.  ``lens == 0``
    rows are idle: their writes are redirected to the scratch block and
    their ``key_pos``/``pos`` stay frozen, exactly like a masked decode
    row.  Only valid for specs where ``prefix_sharing_supported`` holds
    (ring slot == position, no wrap), which is what makes rejection exact:
    the caller rolls back by invalidating ``key_pos >= pos + accepted`` —
    no surviving key is ever overwritten by a rejected draft.

    All ``K`` tokens are scattered into the pool first, then attended in
    one pass.  ``impl="pallas"`` runs the multi-q streaming kernel
    (:func:`repro.kernels.ops.paged_verify_attention`): each cache block is
    DMA'd once per *verify step* instead of once per token, which is the
    speculative-decoding bandwidth win.  With ``K == 1`` the kernel math
    degenerates to the decode kernel's exactly, so greedy spec decode is
    bit-identical to plain decode.  int8 KV takes the gather+dequant
    reference (same fallback — and the same one-time warning — as
    :func:`attend_decode_paged`).
    """
    _check_decode_impl(impl)
    b, kq = x.shape[:2]
    pos, bt, key_pos = cache["pos"], cache["bt"], cache["key_pos"]
    c_pad = key_pos.shape[-1]
    bsz = cache["k_pool"].shape[1]
    nbs = c_pad // bsz
    scratch = cache["k_pool"].shape[0] - 1
    positions = pos[:, None] + jnp.arange(kq, dtype=pos.dtype)[None]  # [B,K]
    valid = jnp.arange(kq)[None, :] < lens[:, None]                   # [B,K]
    q, k, v = _project_qkv(params, cfg, x, positions)

    # scatter all K tokens into their slots' blocks (scratch for idle/pad
    # rows and unmapped blocks); no wrap => ring slot == position
    ring = positions % c_pad
    blk = jnp.clip(ring // bsz, 0, nbs - 1)
    off = ring % bsz
    phys = jnp.take_along_axis(bt, blk, axis=1)                       # [B,K]
    tgt = jnp.where(valid & (phys >= 0), phys, scratch)
    quant = cfg.kv_dtype == "int8"
    if quant:
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        kp = cache["k_pool"].at[tgt, off].set(k8)
        vp = cache["v_pool"].at[tgt, off].set(v8)
        ksp = cache["k_scale_pool"].at[tgt, off].set(ks)
        vsp = cache["v_scale_pool"].at[tgt, off].set(vs)
    else:
        kp = cache["k_pool"].at[tgt, off].set(
            k.astype(cache["k_pool"].dtype))
        vp = cache["v_pool"].at[tgt, off].set(
            v.astype(cache["v_pool"].dtype))

    rows = jnp.arange(b)[:, None]
    prev = key_pos[rows, ring]
    new_key_pos = key_pos.at[rows, ring].set(
        jnp.where(valid, positions.astype(jnp.int32), prev))
    new_pos = (pos + lens).astype(pos.dtype)

    if impl == "pallas" and not quant:
        from repro.kernels import ops as kops
        out = kops.paged_verify_attention(
            q, kp, vp, bt[:, :nbs], new_key_pos, pos,
            window=spec.window, softcap=cfg.attn_logit_softcap)
        out = out.reshape(b, kq, cfg.q_dim)
    else:
        if impl == "pallas":
            _note_int8_pallas_fallback(cfg)
        read = jnp.clip(bt[:, :nbs], 0, None)
        if quant:
            ck = _dequantize_kv(
                kp[read].reshape(b, c_pad, cfg.n_kv_heads, -1),
                ksp[read].reshape(b, c_pad, cfg.n_kv_heads), k.dtype)
            cv = _dequantize_kv(
                vp[read].reshape(b, c_pad, cfg.n_kv_heads, -1),
                vsp[read].reshape(b, c_pad, cfg.n_kv_heads), v.dtype)
        else:
            ck = kp[read].reshape(b, c_pad, cfg.n_kv_heads, -1)
            cv = vp[read].reshape(b, c_pad, cfg.n_kv_heads, -1)
        out = _sdpa(cfg, spec, q, ck, cv, positions, new_key_pos,
                    k_valid=new_key_pos >= 0)
    y = out @ params["wo"]
    y = logical_constraint(y, "batch", None, "embed")
    new_cache = {"k_pool": kp, "v_pool": vp, "bt": bt,
                 "key_pos": new_key_pos, "pos": new_pos}
    if quant:
        new_cache["k_scale_pool"] = ksp
        new_cache["v_scale_pool"] = vsp
    return y, new_cache
