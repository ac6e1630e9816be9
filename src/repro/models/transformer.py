"""TransformerLM: init + forward for every assigned architecture.

The model is a repeating *pattern* of blocks (see ``ModelConfig``).  Full
periods are executed with ``jax.lax.scan`` over stacked parameters — HLO size
stays O(pattern) instead of O(layers), which keeps 61-layer Kimi compilable
on a 512-device host mesh.  Remainder ("tail") blocks run unrolled.

Three entry points:

- :func:`forward`       — mode="train": logits over the full sequence
- :func:`forward`       — mode="prefill": logits + populated decode caches
- :func:`decode_step`   — one token in, one logits row + updated caches
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.config import BlockSpec, ModelConfig
from repro.models.kvcache import (DEFAULT_BLOCK_SIZE, POOL_KEYS,
                                  cache_logical_axes, init_block_cache,
                                  init_paged_block_cache, is_paged_attn_cache)
from repro.models.layers import (ParamBuilder, apply_mlp, apply_norm,
                                 embed_tokens, init_embedding, init_mlp,
                                 init_norm, lm_logits, sinusoidal_embedding)
from repro.sharding.rules import (default_rules, logical_constraint,
                                  shape_aware_sharding_tree)

PyTree = Any


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #

def _init_block(cfg: ModelConfig, spec: BlockSpec, key: jax.Array,
                dtype) -> Tuple[Dict, Dict]:
    pb = ParamBuilder(key, dtype)
    init_norm(pb, "norm1", cfg.d_model, cfg.norm)
    if spec.kind == "attn":
        attn.init_attention(pb, "mixer", cfg)
    elif spec.kind == "rglru":
        rglru_mod.init_rglru_block(pb, "mixer", cfg)
    elif spec.kind == "mlstm":
        xlstm_mod.init_mlstm_block(pb, "mixer", cfg)
    elif spec.kind == "slstm":
        xlstm_mod.init_slstm_block(pb, "mixer", cfg)
    if cfg.post_norm:
        init_norm(pb, "post_norm1", cfg.d_model, cfg.norm)
    has_ffn = spec.moe is not None or spec.mlp != "none"
    if has_ffn:
        init_norm(pb, "norm2", cfg.d_model, cfg.norm)
        if spec.moe is not None:
            moe_mod.init_moe(pb, "ffn", cfg, spec.moe)
        else:
            init_mlp(pb, "ffn", cfg, spec.mlp)
        if cfg.post_norm:
            init_norm(pb, "post_norm2", cfg.d_model, cfg.norm)
    return pb.params, pb.axes


def init_params(cfg: ModelConfig, key: jax.Array) -> Tuple[PyTree, PyTree]:
    """Returns (params, logical_axes) with matching tree structure."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 2)
    pb = ParamBuilder(keys[0], dtype)
    init_embedding(pb, cfg)
    params, axes = pb.params, pb.axes
    init_norm(pb, "final_norm", cfg.d_model, cfg.norm)

    # stacked full periods: one vmapped init per pattern entry (the same
    # values as a per-layer loop, in one small program instead of n_layers)
    if cfg.n_full_periods > 0:
        stack_p: Dict[str, Any] = {}
        stack_a: Dict[str, Any] = {}
        for p, spec in enumerate(cfg.pattern):
            layer_keys = keys[1 + p + cfg.period * jnp.arange(
                cfg.n_full_periods)]
            block_axes = {}

            def init_one(k, spec=spec):
                bp, block_axes["tree"] = _init_block(cfg, spec, k, dtype)
                return bp

            stack_p[f"p{p}"] = jax.vmap(init_one)(layer_keys)
            stack_a[f"p{p}"] = jax.tree.map(
                lambda t: ("layers",) + t, block_axes["tree"],
                is_leaf=lambda t: isinstance(t, tuple))
        params["stack"] = stack_p
        axes["stack"] = stack_a

    # tail blocks (n_layers % period)
    if cfg.tail:
        tail_p, tail_a = {}, {}
        base = cfg.n_full_periods * cfg.period
        for t, spec in enumerate(cfg.tail):
            bp, ba = _init_block(cfg, spec, keys[1 + base + t], dtype)
            tail_p[f"t{t}"] = bp
            tail_a[f"t{t}"] = ba
        params["tail"] = tail_p
        axes["tail"] = tail_a
    return params, axes


def init_params_on_mesh(cfg: ModelConfig, key: jax.Array, mesh) -> PyTree:
    """:func:`init_params`, with every leaf created in place in its
    tensor-parallel sharding over ``mesh`` (default logical-axis rules; a
    dimension a mesh axis does not divide stays whole).  Each device only
    ever holds its own share, so a model too large for one chip can be
    built.  The values do not depend on the mesh (JAX's random bits are
    partitionable): they are those of a jitted :func:`init_params`."""
    axes = {}

    def init(k):
        params, axes["tree"] = init_params(cfg, k)
        return params

    shapes = jax.eval_shape(init, key)
    shardings = shape_aware_sharding_tree(
        shapes, axes["tree"], mesh, default_rules("pod" in mesh.axis_names))
    return jax.jit(init, out_shardings=shardings)(key)


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> PyTree:
    """Decode caches matching the stacked/tail layout of the params."""
    caches: Dict[str, Any] = {}
    if cfg.n_full_periods > 0:
        stack = {}
        for p, spec in enumerate(cfg.pattern):
            one = init_block_cache(cfg, spec, batch, max_len, dtype)
            stack[f"p{p}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (cfg.n_full_periods,) + x.shape).copy(), one)
        caches["stack"] = stack
    if cfg.tail:
        caches["tail"] = {
            f"t{t}": init_block_cache(cfg, spec, batch, max_len, dtype)
            for t, spec in enumerate(cfg.tail)}
    return caches


def init_paged_caches(cfg: ModelConfig, batch: int, max_len: int,
                      num_blocks: int,
                      block_size: int = DEFAULT_BLOCK_SIZE,
                      dtype=jnp.bfloat16) -> PyTree:
    """Paged twin of :func:`init_caches`: attention entries hold shared
    block pools + per-slot block tables (``batch`` = slots); non-attention
    entries keep their dense per-slot state (``pos`` is per-slot [B] in
    every layout, so each slot owns its position in the batched, vmap-free
    decode)."""
    def one_entry(spec: BlockSpec, stack_layers: int = 0):
        if spec.kind == "attn":
            one = init_paged_block_cache(cfg, spec, batch, max_len,
                                         num_blocks, block_size, dtype)
        else:
            one = init_block_cache(cfg, spec, batch, max_len, dtype)
        if stack_layers:
            one = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (stack_layers,) + x.shape).copy(), one)
        return one

    caches: Dict[str, Any] = {}
    if cfg.n_full_periods > 0:
        caches["stack"] = {f"p{p}": one_entry(spec, cfg.n_full_periods)
                           for p, spec in enumerate(cfg.pattern)}
    if cfg.tail:
        caches["tail"] = {f"t{t}": one_entry(spec)
                          for t, spec in enumerate(cfg.tail)}
    return caches


def caches_are_paged(caches: PyTree) -> bool:
    """True when the cache pytree came from :func:`init_paged_caches` (i.e.
    holds at least one attention block pool)."""
    for group in ("stack", "tail"):
        for entry in (caches.get(group) or {}).values():
            if is_paged_attn_cache(entry):
                return True
    return False


def cache_axes(cfg: ModelConfig) -> PyTree:
    axes: Dict[str, Any] = {}
    if cfg.n_full_periods > 0:
        axes["stack"] = {
            f"p{p}": jax.tree.map(lambda t: ("layers",) + tuple(t),
                                  cache_logical_axes(cfg, spec),
                                  is_leaf=lambda t: isinstance(t, tuple))
            for p, spec in enumerate(cfg.pattern)}
    if cfg.tail:
        axes["tail"] = {f"t{t}": cache_logical_axes(cfg, spec)
                        for t, spec in enumerate(cfg.tail)}
    return axes


# --------------------------------------------------------------------------- #
# block apply
# --------------------------------------------------------------------------- #

def _apply_block(cfg: ModelConfig, spec: BlockSpec, params: Dict,
                 x: jax.Array, positions: jax.Array, mode: str,
                 cache: Optional[Dict], impl: str,
                 write_mask: Optional[jax.Array] = None,
                 seq_valid: Optional[jax.Array] = None,
                 verify_lens: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None,
                 ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (x_out, new_cache, aux_loss).  ``write_mask`` gates paged
    KV-pool writes (idle slots / dead pipeline ticks scatter to scratch);
    ``layer`` marks paged pools stacked over layers, updated at that layer
    in place (:func:`repro.models.attention.attend_decode_paged`).

    ``seq_valid`` ([B, S], masked prefill) marks pad positions invalid:
    attention masks them via the negative per-row ``positions``, recurrent
    mixers treat them as state-preserving no-ops, and the block re-zeroes
    pad activations on exit so they cannot leak into later layers (e.g.
    through a causal conv window)."""
    if mode in ("extend", "verify") and spec.kind != "attn":
        raise ValueError(
            f"{mode} (chunked/offset prefill or speculative verify) requires "
            f"attention caches; got {spec.kind!r} — gate via "
            f"kvcache.prefix_sharing_supported")
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(params["norm1"], x, cfg.norm)
    new_cache = cache
    if spec.kind == "attn":
        if mode == "train":
            mix = attn.attend_full(params["mixer"], cfg, spec, h, positions, impl)
        elif mode == "prefill":
            mix, new_cache = attn.prefill_cache(params["mixer"], cfg, spec, h,
                                                positions, cache, impl)
        elif mode == "extend":
            mix, new_cache = attn.extend_cache(params["mixer"], cfg, spec, h,
                                               positions, seq_valid, cache,
                                               impl)
        elif mode == "verify":
            mix, new_cache = attn.attend_verify_paged(
                params["mixer"], cfg, spec, h, verify_lens, cache, impl)
        elif is_paged_attn_cache(cache):
            mix, new_cache = attn.attend_decode_paged(
                params["mixer"], cfg, spec, h, cache, impl,
                write_mask=write_mask, layer=layer)
        else:
            mix, new_cache = attn.attend_decode(params["mixer"], cfg, spec, h,
                                                cache, impl)
    elif spec.kind == "rglru":
        if mode == "decode":
            mix, new_cache = rglru_mod.apply_rglru_decode(params["mixer"], cfg,
                                                          h, cache)
        else:
            mix, new_cache = rglru_mod.apply_rglru_seq(
                params["mixer"], cfg, h, cache if mode == "prefill" else None,
                impl, seq_valid=seq_valid)
    elif spec.kind == "mlstm":
        if mode == "decode":
            mix, new_cache = xlstm_mod.apply_mlstm_decode(params["mixer"], cfg,
                                                          h, cache)
        else:
            mix, new_cache = xlstm_mod.apply_mlstm_seq(
                params["mixer"], cfg, h, cache if mode == "prefill" else None,
                seq_valid=seq_valid)
    elif spec.kind == "slstm":
        if mode == "decode":
            mix, new_cache = xlstm_mod.apply_slstm_decode(params["mixer"], cfg,
                                                          h, cache)
        else:
            mix, new_cache = xlstm_mod.apply_slstm_seq(
                params["mixer"], cfg, h, cache if mode == "prefill" else None,
                seq_valid=seq_valid)
    else:
        raise ValueError(spec.kind)
    if cfg.post_norm:
        mix = apply_norm(params["post_norm1"], mix, cfg.norm)
    x = x + mix
    if spec.moe is not None or spec.mlp != "none":
        h2 = apply_norm(params["norm2"], x, cfg.norm)
        if spec.moe is not None:
            ffn, aux = moe_mod.apply_moe(params["ffn"], cfg, spec.moe, h2)
        else:
            ffn = apply_mlp(params["ffn"], h2, spec.mlp)
        if cfg.post_norm:
            ffn = apply_norm(params["post_norm2"], ffn, cfg.norm)
        x = x + ffn
    if seq_valid is not None:
        x = jnp.where(seq_valid[..., None], x, 0)
    if mode == "train":
        new_cache = None
    return x, new_cache, aux


# --------------------------------------------------------------------------- #
# forward / decode
# --------------------------------------------------------------------------- #

def _embed_inputs(cfg: ModelConfig, params: PyTree, inputs: jax.Array,
                  positions: jax.Array) -> jax.Array:
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        x = embed_tokens(params, cfg, inputs)
    else:
        x = inputs.astype(jnp.dtype(cfg.dtype))     # stub frontend embeddings
    if cfg.pos_emb == "sinusoidal":
        emb = sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)
        # positions [S] (shared) -> emb [S,d] broadcast over batch;
        # positions [B,S] (per-slot paged decode) -> emb [B,S,d] as-is
        x = x + (emb if emb.ndim == x.ndim else emb[None])
    return logical_constraint(x, "batch", None, "embed")


def forward(cfg: ModelConfig, params: PyTree, inputs: jax.Array,
            mode: str = "train", caches: Optional[PyTree] = None,
            pos_offset: int = 0, impl: str = "xla",
            prompt_lens: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    """Full-sequence forward. inputs: [B, S] int tokens or [B, S, d] embeds.

    Returns (logits [B, S, vocab], caches or None, aux_loss scalar).

    ``prompt_lens`` ([B] int, prefill only) marks inputs as *left-padded*
    to S with true lengths ``prompt_lens[b]``: positions become per-row
    (``s - (S - plen)``; negative at pads), pad keys are masked out of
    attention and written with ``key_pos == -1``, recurrent state skips pad
    steps, and pad activations are zeroed between blocks — so logits at
    real positions and the resulting caches are independent of the padded
    width (pad tokens are semantically invisible).
    """
    assert mode in ("train", "prefill")
    b, s = inputs.shape[:2]
    if prompt_lens is None:
        positions = jnp.arange(s, dtype=jnp.int32) + pos_offset
        seq_valid = None
    else:
        assert mode == "prefill" and pos_offset == 0, \
            "prompt_lens implies a left-padded prefill from position 0"
        plen = jnp.asarray(prompt_lens, jnp.int32)
        positions = jnp.arange(s, dtype=jnp.int32)[None] \
            - (s - plen)[:, None]                                # [B, S]
        seq_valid = positions >= 0
    x = _embed_inputs(cfg, params, inputs, positions)
    if seq_valid is not None:
        x = jnp.where(seq_valid[..., None], x, 0)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}

    if cfg.n_full_periods > 0:
        stack_params = params["stack"]
        stack_caches = (caches or {}).get("stack")

        def body(carry, per_period):
            x_c, aux_c = carry
            p_params, p_caches = per_period
            new_p_caches = {}
            for p, spec in enumerate(cfg.pattern):
                cache_p = p_caches[f"p{p}"] if p_caches is not None else None
                x_c, nc, aux = _apply_block(cfg, spec, p_params[f"p{p}"], x_c,
                                            positions, mode, cache_p, impl,
                                            seq_valid=seq_valid)
                new_p_caches[f"p{p}"] = nc
                aux_c = aux_c + aux
            ys = new_p_caches if mode == "prefill" else None
            return (x_c, aux_c), ys

        (x, aux_total), scanned_caches = jax.lax.scan(
            body, (x, aux_total), (stack_params, stack_caches))
        if mode == "prefill":
            new_caches["stack"] = scanned_caches

    if cfg.tail:
        base = cfg.n_full_periods * cfg.period
        new_tail = {}
        for t, spec in enumerate(cfg.tail):
            cache_t = (caches or {}).get("tail", {}).get(f"t{t}")
            x, nc, aux = _apply_block(cfg, spec, params["tail"][f"t{t}"], x,
                                      positions, mode, cache_t, impl,
                                      seq_valid=seq_valid)
            new_tail[f"t{t}"] = nc
            aux_total = aux_total + aux
        if mode == "prefill":
            new_caches["tail"] = new_tail

    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x)
    return logits, (new_caches if mode == "prefill" else None), aux_total


def decode_step(cfg: ModelConfig, params: PyTree, inputs: jax.Array,
                caches: PyTree, impl: str = "xla",
                write_mask: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, PyTree]:
    """One decode step. inputs: [B] int tokens or [B, d] embeddings.

    Returns (logits [B, vocab], updated caches).

    Every cache kind carries per-row ``pos [B]`` (attention additionally
    per-row ``key_pos``), so every sequence decodes at its own true
    position — the masked length-bucketed prefill leaves rows at different
    lengths.  Paged caches (:func:`init_paged_caches`) additionally route
    KV through per-slot block tables; ``write_mask [B]`` freezes masked
    slots' pool writes.  ``impl="pallas"`` dispatches the Pallas decode
    kernels on both layouts (the paged kernel reads pool blocks through
    the table — no per-step gather); unknown impls raise.

    The stacked block pools of paged attention entries ride in the layer
    scan's carry, not its xs/ys: each layer scatters its token into the
    stacked pool at ``(layer, block, offset)`` and reads the pool at
    ``layer`` in place, so a step moves one token per slot per layer of
    pool memory, not the whole pool (with the caches donated, the pool's
    output buffer is its input's).  Per-slot leaves and every other cache
    kind stay in xs/ys.
    """
    if inputs.ndim == 1 and jnp.issubdtype(inputs.dtype, jnp.integer):
        inputs2 = inputs[:, None]
    else:
        inputs2 = inputs[:, None, :]
    pos = _first_pos(caches)
    positions = pos[..., None] if pos.ndim else pos[None]   # [B,1] | [1]
    x = _embed_inputs(cfg, params, inputs2, positions)
    new_caches: Dict[str, Any] = {}

    if cfg.n_full_periods > 0:
        pools, per_layer = {}, {}
        for name, entry in caches["stack"].items():
            if is_paged_attn_cache(entry):
                pools[name] = {k: v for k, v in entry.items()
                               if k in POOL_KEYS}
            per_layer[name] = {k: v for k, v in entry.items()
                               if k not in pools.get(name, ())}

        def body(carry, per_period):
            x_c, pools_c = carry
            p_params, p_caches, layer = per_period
            new_p = {}
            for p, spec in enumerate(cfg.pattern):
                name = f"p{p}"
                own = pools_c.get(name)
                cache = p_caches[name] if own is None \
                    else {**p_caches[name], **own}
                x_c, nc, _ = _apply_block(
                    cfg, spec, p_params[name], x_c, positions, "decode",
                    cache, impl, write_mask=write_mask,
                    layer=None if own is None else layer)
                if own is not None:
                    pools_c = {**pools_c, name: {k: nc[k] for k in own}}
                    nc = {k: v for k, v in nc.items() if k not in own}
                new_p[name] = nc
            return (x_c, pools_c), new_p

        (x, pools), per_layer = jax.lax.scan(
            body, (x, pools),
            (params["stack"], per_layer,
             jnp.arange(cfg.n_full_periods, dtype=jnp.int32)))
        new_caches["stack"] = {name: {**entry, **pools.get(name, {})}
                               for name, entry in per_layer.items()}

    if cfg.tail:
        new_tail = {}
        for t, spec in enumerate(cfg.tail):
            x, nc, _ = _apply_block(cfg, spec, params["tail"][f"t{t}"], x,
                                    positions, "decode",
                                    caches["tail"][f"t{t}"], impl,
                                    write_mask=write_mask)
            new_tail[f"t{t}"] = nc
        new_caches["tail"] = new_tail

    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x)
    return logits[:, 0], new_caches


def extend_step(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
                caches: PyTree, starts: jax.Array, lens: jax.Array,
                impl: str = "xla") -> Tuple[jax.Array, PyTree]:
    """Chunked/offset prefill over paged caches: run ``tokens`` [B, S]
    (right-aligned payload, left-padded to S, true lengths ``lens`` [B]) at
    absolute positions ``starts[b] .. starts[b]+lens[b]-1`` with every
    earlier cache key visible — the continuation twin of
    ``forward(mode="prefill")`` for prompts whose head is already cached
    (an adopted shared prefix and/or previous chunks).

    Returns (logits [B, S, vocab], updated caches).  Row ``b``'s last-token
    logits sit at ``logits[b, -1]``.  Only valid for paged all-attention
    deployments with no effective sliding window
    (``kvcache.prefix_sharing_supported``); recurrent kinds raise.
    """
    b, s = tokens.shape[:2]
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    cols = jnp.arange(s, dtype=jnp.int32)[None, :]
    positions = starts[:, None] + cols - (s - lens)[:, None]      # [B, S]
    seq_valid = cols >= (s - lens)[:, None]
    x = _embed_inputs(cfg, params, tokens, positions)
    x = jnp.where(seq_valid[..., None], x, 0)
    new_caches: Dict[str, Any] = {}

    if cfg.n_full_periods > 0:
        def body(x_c, per_period):
            p_params, p_caches = per_period
            new_p = {}
            for p, spec in enumerate(cfg.pattern):
                x_c, nc, _ = _apply_block(cfg, spec, p_params[f"p{p}"], x_c,
                                          positions, "extend",
                                          p_caches[f"p{p}"], impl,
                                          seq_valid=seq_valid)
                new_p[f"p{p}"] = nc
            return x_c, new_p

        x, new_caches["stack"] = jax.lax.scan(
            body, x, (params["stack"], caches["stack"]))

    if cfg.tail:
        new_tail = {}
        for t, spec in enumerate(cfg.tail):
            x, nc, _ = _apply_block(cfg, spec, params["tail"][f"t{t}"], x,
                                    positions, "extend",
                                    caches["tail"][f"t{t}"], impl,
                                    seq_valid=seq_valid)
            new_tail[f"t{t}"] = nc
        new_caches["tail"] = new_tail

    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x)
    return logits, new_caches


def verify_step(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
                caches: PyTree, lens: jax.Array,
                impl: str = "xla") -> Tuple[jax.Array, PyTree]:
    """Speculative verify: score ``tokens`` [B, K] — row ``b``'s first
    ``lens[b]`` entries are the last accepted token followed by draft
    continuations, left-aligned — in ONE forward pass at absolute positions
    ``pos[b] .. pos[b]+lens[b]-1`` (``pos`` read from the caches).

    Returns (logits [B, K, vocab], updated caches): ``logits[b, i]`` is the
    target model's next-token distribution *after* fed token ``i``, so
    greedy acceptance compares ``argmax(logits[b, i-1])`` against fed token
    ``i``.  ``lens[b] == 0`` rows are idle (writes to scratch, state
    frozen); ``lens[b] == 1`` is exactly a decode step (and with K == 1 the
    pallas path is bit-identical to :func:`decode_step`'s).  The caches
    come back advanced by ``lens`` with all K candidate keys written —
    callers must roll back rejected positions (invalidate
    ``key_pos >= pos + accepted``, reset ``pos``).  Only valid for paged
    all-attention deployments (``kvcache.prefix_sharing_supported``);
    recurrent kinds raise.
    """
    b, kq = tokens.shape[:2]
    lens = jnp.asarray(lens, jnp.int32)
    pos = _first_pos(caches).astype(jnp.int32)                    # [B]
    cols = jnp.arange(kq, dtype=jnp.int32)[None, :]
    positions = pos[:, None] + cols                               # [B, K]
    seq_valid = cols < lens[:, None]
    x = _embed_inputs(cfg, params, tokens, positions)
    x = jnp.where(seq_valid[..., None], x, 0)
    new_caches: Dict[str, Any] = {}

    if cfg.n_full_periods > 0:
        def body(x_c, per_period):
            p_params, p_caches = per_period
            new_p = {}
            for p, spec in enumerate(cfg.pattern):
                x_c, nc, _ = _apply_block(cfg, spec, p_params[f"p{p}"], x_c,
                                          positions, "verify",
                                          p_caches[f"p{p}"], impl,
                                          seq_valid=seq_valid,
                                          verify_lens=lens)
                new_p[f"p{p}"] = nc
            return x_c, new_p

        x, new_caches["stack"] = jax.lax.scan(
            body, x, (params["stack"], caches["stack"]))

    if cfg.tail:
        new_tail = {}
        for t, spec in enumerate(cfg.tail):
            x, nc, _ = _apply_block(cfg, spec, params["tail"][f"t{t}"], x,
                                    positions, "verify",
                                    caches["tail"][f"t{t}"], impl,
                                    seq_valid=seq_valid, verify_lens=lens)
            new_tail[f"t{t}"] = nc
        new_caches["tail"] = new_tail

    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x)
    return logits, new_caches


def _first_pos(caches: PyTree) -> jax.Array:
    """Current decode position(s), [B] per-slot in every cache kind.
    Prefer an attention entry — its ``pos`` is authoritative per slot and
    may differ per row after a masked (length-bucketed) prefill."""
    entries = []
    if "stack" in caches:
        entries += [(e, True) for e in caches["stack"].values()]
    if "tail" in caches:
        entries += [(e, False) for e in caches["tail"].values()]
    for e, stacked in entries:
        if is_paged_attn_cache(e) or (isinstance(e, dict) and "key_pos" in e):
            return e["pos"][0] if stacked else e["pos"]
    e, stacked = entries[0]
    return e["pos"][0] if stacked else e["pos"]


# --------------------------------------------------------------------------- #
# loss
# --------------------------------------------------------------------------- #

def cross_entropy_loss(cfg: ModelConfig, logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None,
                       z_loss: float = 1e-4) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(logz)
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def forward_hidden(cfg: ModelConfig, params: PyTree, inputs: jax.Array,
                   impl: str = "xla") -> Tuple[jax.Array, jax.Array]:
    """Like forward(mode="train") but stops at the final normalized hidden
    state (no logits) — the chunked-loss path computes logits blockwise."""
    b, s = inputs.shape[:2]
    positions = jnp.arange(s, dtype=jnp.int32)
    x = _embed_inputs(cfg, params, inputs, positions)
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.n_full_periods > 0:
        def body(carry, per_period):
            x_c, aux_c = carry
            p_params = per_period
            for p, spec in enumerate(cfg.pattern):
                x_c, _, aux = _apply_block(cfg, spec, p_params[f"p{p}"], x_c,
                                           positions, "train", None, impl)
                aux_c = aux_c + aux
            return (x_c, aux_c), None
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                         params["stack"])
    if cfg.tail:
        for t, spec in enumerate(cfg.tail):
            x, _, aux = _apply_block(cfg, spec, params["tail"][f"t{t}"], x,
                                     positions, "train", None, impl)
            aux_total = aux_total + aux
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, aux_total


def chunked_xent(cfg: ModelConfig, params: PyTree, hidden: jax.Array,
                 labels: jax.Array, chunk: int,
                 z_loss: float = 1e-4) -> jax.Array:
    """Cross entropy over seq chunks — never materializes [B, S, V] logits.

    Memory-roofline optimization (EXPERIMENTS.md §Perf): for 256k-vocab
    models the full logits tensor dominates HBM traffic of the train step.
    """
    b, s, d = hidden.shape
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    h = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    y = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def one(carry, hy):
        hc, yc = hy
        logits = lm_logits(params, cfg, hc)
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        nll = logz - gold + z_loss * jnp.square(logz)
        return carry + jnp.sum(nll), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (h, y))
    return total / (b * s)


def train_loss(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
               labels: jax.Array, mask: Optional[jax.Array] = None,
               impl: str = "xla", xent_chunk: Optional[int] = None,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    if xent_chunk:
        hidden, aux = forward_hidden(cfg, params, tokens, impl=impl)
        ce = chunked_xent(cfg, params, hidden, labels, xent_chunk)
    else:
        logits, _, aux = forward(cfg, params, tokens, mode="train", impl=impl)
        ce = cross_entropy_loss(cfg, logits, labels, mask)
    lb_weight = 0.0
    for spec in cfg.pattern:
        if spec.moe is not None:
            lb_weight = spec.moe.load_balance_weight
    total = ce + lb_weight * aux
    return total, {"ce": ce, "aux": aux}
