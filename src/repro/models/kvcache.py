"""Per-layer decode caches: ring-buffer KV, RG-LRU state, xLSTM states.

Caches are plain dict pytrees so they stack cleanly under ``lax.scan`` and
shard with the same logical-axis rules as activations:

- attention:  k/v ``[B, C, n_kv, head_dim]`` (C = min(max_len, window)),
  ``key_pos [B, C]`` absolute position per ring slot (-1 = empty),
  ``pos [B]`` decode position — both *per-row*, so one wave of
  length-bucketed (masked, left-padded) prefills can hold a different true
  length per sequence.
- rglru:      hidden ``[B, rnn]``, conv tail ``[B, conv_width-1, rnn]``.
- mlstm:      C ``[B, heads, dk, dv]``, n ``[B, heads, dk]``, m ``[B, heads]``.
- slstm:      c/n/h ``[B, d]``, m ``[B, d]`` (stabilizer).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.config import BlockSpec, ModelConfig


#: vLLM-style paging granularity: tokens per KV block.
DEFAULT_BLOCK_SIZE = 16


def attn_cache_len(spec: BlockSpec, max_len: int) -> int:
    """Ring-buffer length for one attention spec.

    Windowed specs clamp to ``max_len`` — a window larger than the serving
    length degenerates to full attention and must be *accounted* at the
    clamped length too (paged pools and ``cache_bytes_per_slot`` both size
    from this value, so they always agree).
    """
    return min(max_len, spec.window) if spec.window else max_len


def paged_cache_len(spec: BlockSpec, max_len: int,
                    block_size: int = DEFAULT_BLOCK_SIZE) -> int:
    """`attn_cache_len` rounded up to whole blocks (the gathered width).

    Positions ``attn_cache_len .. paged_cache_len-1`` are never written and
    stay masked via ``key_pos == -1``.
    """
    c = attn_cache_len(spec, max_len)
    return -(-c // block_size) * block_size


def max_ctx_blocks(cfg: ModelConfig, max_len: int,
                   block_size: int = DEFAULT_BLOCK_SIZE) -> int:
    """Most blocks one slot can hold = blocks of the largest (clamped)
    attention cache across the pattern + tail.  0 for attention-free models."""
    specs = [s for s in cfg.layer_specs() if s.kind == "attn"]
    if not specs:
        return 0
    return max(-(-attn_cache_len(s, max_len) // block_size) for s in specs)


def prefix_sharing_supported(cfg: ModelConfig, max_len: int) -> bool:
    """True when every layer's cache is position-addressed with no eviction
    — the precondition for shared-prefix KV reuse and chunked prefill.

    Requires all-attention layers (recurrent kinds carry state that cannot
    be restored from pool blocks) with no *effective* sliding window at
    this serving length (a windowed ring wraps, so a shared block would be
    overwritten in place — a copy-on-write violation).  Backends silently
    disable prefix caching / extend when this returns False.
    """
    specs = list(cfg.layer_specs())
    return bool(specs) and all(
        s.kind == "attn" and attn_cache_len(s, max_len) == max_len
        for s in specs)


def block_pool_bytes_per_block(cfg: ModelConfig, dtype=jnp.bfloat16) -> int:
    """Bytes one logical block occupies summed over every attention layer
    (each layer materializes the block id space in its own pool)."""
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    if cfg.kv_dtype == "int8":
        per_tok = 2 * nkv * hd * 1 + 2 * nkv * 4        # k/v int8 + scales
    else:
        per_tok = 2 * nkv * hd * jnp.dtype(dtype).itemsize
    n_attn = sum(1 for s in cfg.layer_specs() if s.kind == "attn")
    return per_tok * n_attn


def init_paged_block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int,
                           max_len: int, num_blocks: int,
                           block_size: int = DEFAULT_BLOCK_SIZE,
                           dtype=jnp.bfloat16) -> Dict:
    """Paged twin of :func:`init_block_cache` for ``spec.kind == "attn"``.

    Layout per layer (non-attn kinds keep their dense cache):

    - ``k_pool``/``v_pool`` ``[num_blocks+1, block_size, n_kv, head_dim]`` —
      the shared pool; the **last block is scratch**: writes whose block-table
      entry is unallocated (or whose slot is masked) are redirected there so
      they can never corrupt another slot's blocks,
    - ``bt`` ``[B, max_ctx_blocks]`` int32 physical block ids (-1 = unmapped),
    - ``key_pos`` ``[B, paged_cache_len]`` absolute position per ring slot
      (-1 = empty), per-slot like the contiguous layout,
    - ``pos`` ``[B]`` per-slot decode position.
    """
    assert spec.kind == "attn", spec.kind
    c = paged_cache_len(spec, max_len, block_size)
    nbs = max_ctx_blocks(cfg, max_len, block_size)
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    out = {
        "bt": jnp.full((batch, max(nbs, 1)), -1, jnp.int32),
        "key_pos": jnp.full((batch, c), -1, jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.kv_dtype == "int8":
        out["k_pool"] = jnp.zeros((num_blocks + 1, block_size, nkv, hd),
                                  jnp.int8)
        out["v_pool"] = jnp.zeros((num_blocks + 1, block_size, nkv, hd),
                                  jnp.int8)
        out["k_scale_pool"] = jnp.zeros((num_blocks + 1, block_size, nkv),
                                        jnp.float32)
        out["v_scale_pool"] = jnp.zeros((num_blocks + 1, block_size, nkv),
                                        jnp.float32)
    else:
        out["k_pool"] = jnp.zeros((num_blocks + 1, block_size, nkv, hd), dtype)
        out["v_pool"] = jnp.zeros((num_blocks + 1, block_size, nkv, hd), dtype)
    return out


#: the block-pool leaves of a paged attention cache (the scale pools only
#: with ``kv_dtype="int8"``); every other leaf is per-slot
POOL_KEYS = ("k_pool", "v_pool", "k_scale_pool", "v_scale_pool")


def is_paged_attn_cache(cache: Dict) -> bool:
    return isinstance(cache, dict) and "k_pool" in cache


def init_block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int,
                     max_len: int, dtype=jnp.bfloat16) -> Dict:
    if spec.kind == "attn":
        c = attn_cache_len(spec, max_len)
        if cfg.kv_dtype == "int8":      # quantized cache + per-(token, head)
            return {                    # absmax scales (EXPERIMENTS.md SPerf-A)
                "k": jnp.zeros((batch, c, cfg.n_kv_heads,
                                cfg.resolved_head_dim), jnp.int8),
                "v": jnp.zeros((batch, c, cfg.n_kv_heads,
                                cfg.resolved_head_dim), jnp.int8),
                "k_scale": jnp.zeros((batch, c, cfg.n_kv_heads), jnp.float32),
                "v_scale": jnp.zeros((batch, c, cfg.n_kv_heads), jnp.float32),
                "key_pos": jnp.full((batch, c), -1, jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32),
            }
        return {
            "k": jnp.zeros((batch, c, cfg.n_kv_heads, cfg.resolved_head_dim), dtype),
            "v": jnp.zeros((batch, c, cfg.n_kv_heads, cfg.resolved_head_dim), dtype),
            "key_pos": jnp.full((batch, c), -1, jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if spec.kind == "rglru":
        r = cfg.rnn_dim
        return {
            "h": jnp.zeros((batch, r), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, r), dtype),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if spec.kind == "mlstm":
        dp = int(cfg.d_model * cfg.mlstm_proj_factor)
        hd = dp // cfg.n_heads
        return {
            "C": jnp.zeros((batch, cfg.n_heads, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, cfg.n_heads, hd), jnp.float32),
            "m": jnp.zeros((batch, cfg.n_heads), jnp.float32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if spec.kind == "slstm":
        d = cfg.d_model
        return {
            "c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.zeros((batch, d), jnp.float32),
            "h": jnp.zeros((batch, d), jnp.float32),
            "m": jnp.zeros((batch, d), jnp.float32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    raise ValueError(spec.kind)


def cache_logical_axes(cfg: ModelConfig, spec: BlockSpec) -> Dict:
    """Logical sharding axes matching :func:`init_block_cache`."""
    if spec.kind == "attn":
        # "seq_kv" maps to None by default; long-context decode (batch too
        # small to fill the data axis) remaps it to ("data",) instead.
        out = {"k": ("batch", "seq_kv", "kv_heads", None),
               "v": ("batch", "seq_kv", "kv_heads", None),
               "key_pos": ("batch", "seq_kv"), "pos": ("batch",)}
        if cfg.kv_dtype == "int8":
            out["k_scale"] = ("batch", "seq_kv", "kv_heads")
            out["v_scale"] = ("batch", "seq_kv", "kv_heads")
        return out
    if spec.kind == "rglru":
        return {"h": ("batch", "rnn"), "conv": ("batch", None, "rnn"),
                "pos": ("batch",)}
    if spec.kind == "mlstm":
        return {"C": ("batch", "heads", None, None), "n": ("batch", "heads", None),
                "m": ("batch", "heads"), "pos": ("batch",)}
    if spec.kind == "slstm":
        return {"c": ("batch", "embed"), "n": ("batch", "embed"),
                "h": ("batch", "embed"), "m": ("batch", "embed"),
                "pos": ("batch",)}
    raise ValueError(spec.kind)
