"""EdgeShard pipeline runtime: the paper's layer-sharded collaborative
inference mapped onto a TPU mesh axis.

The DP planner (``core/partition.py``) decides *which contiguous slab of
layers lives on which stage* — stages may be **uneven** (the point of the
paper's heterogeneity-aware partition).  This module executes that plan as a
single SPMD program:

- stages = positions along the ``model`` mesh axis (``shard_map``),
- activation hand-off = ``jax.lax.ppermute`` to the next stage (the paper's
  device-to-device activation send, on ICI instead of Ethernet),
- the sampled-token ring closure back to stage 0 = the paper's privacy-
  constrained "return to the source node" hop (Eq. 6, last-layer term),
- uneven stage sizes are realized by padding every stage to ``l_max``
  periods and masking dead layers inside a ``lax.scan``,
- **EdgeShard-No-bubbles** decode = the tick protocol of
  :func:`pipeline_decode_tick`: each tick, every stage processes a
  *different* micro-batch and passes it on; with >= n_stages micro-batches
  in flight no stage idles — Fig. 5(b) in SPMD lockstep form.  Warm-up
  validity flags ride the ring so cold stages never corrupt KV caches.

Pipeline mode partitions at *period* ("superlayer") granularity and supports
configs with ``n_layers % period == 0``; recurrentgemma's 2-block tail is the
one exception (DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.partition import Plan
from repro.models import transformer as tmod
from repro.models.config import ModelConfig
from repro.models.kvcache import (DEFAULT_BLOCK_SIZE, POOL_KEYS,
                                  cache_logical_axes, init_block_cache,
                                  init_paged_block_cache)
from repro.models.layers import apply_norm, embed_tokens, lm_logits

PyTree = Any


@dataclass(frozen=True)
class PipelineSpec:
    """Stage layout: ``periods_per_stage[s]`` periods on stage s (uneven OK)."""

    n_stages: int
    periods_per_stage: Tuple[int, ...]

    def __post_init__(self):
        assert len(self.periods_per_stage) == self.n_stages
        assert all(p >= 0 for p in self.periods_per_stage)

    @property
    def n_periods(self) -> int:
        return sum(self.periods_per_stage)

    @property
    def l_max(self) -> int:
        return max(self.periods_per_stage)

    @property
    def starts(self) -> Tuple[int, ...]:
        out, acc = [], 0
        for p in self.periods_per_stage:
            out.append(acc)
            acc += p
        return tuple(out)


def even_pipeline_spec(cfg: ModelConfig, n_stages: int) -> PipelineSpec:
    n = cfg.n_full_periods
    base, extra = divmod(n, n_stages)
    return PipelineSpec(n_stages, tuple(base + (1 if s < extra else 0)
                                        for s in range(n_stages)))


def spec_from_plan(cfg: ModelConfig, plan: Plan, n_stages: int) -> PipelineSpec:
    """Map a DP plan over units (embed + blocks + head) to period counts."""
    assert cfg.n_layers % cfg.period == 0, "pipeline needs whole periods"
    blocks_per_stage: List[int] = []
    for st in plan.stages:
        lo = max(st.start, 1)            # drop the embed unit
        hi = min(st.end, cfg.n_layers)   # drop the head unit
        blocks_per_stage.append(max(0, hi - lo + 1))
    while len(blocks_per_stage) > n_stages:
        # merge the smallest stage into its right neighbour (or left, if
        # last); pop FIRST so the target index is computed on the shrunk
        # list — the augmented-assign form loses blocks when j > i.
        i = int(np.argmin(blocks_per_stage))
        v = blocks_per_stage.pop(i)
        j = min(i, len(blocks_per_stage) - 1)
        blocks_per_stage[j] += v
    while len(blocks_per_stage) < n_stages:
        i = int(np.argmax(blocks_per_stage))
        half = blocks_per_stage[i] // 2
        blocks_per_stage[i] -= half
        blocks_per_stage.insert(i + 1, half)
    total_p = cfg.n_full_periods
    raw = np.array(blocks_per_stage, float) / cfg.period
    base = np.floor(raw).astype(int)
    rem = total_p - int(base.sum())
    order = np.argsort(-(raw - base))
    for idx in order[:rem]:
        base[idx] += 1
    assert base.sum() == total_p
    return PipelineSpec(n_stages, tuple(int(x) for x in base))


# --------------------------------------------------------------------------- #
# parameter / cache restacking
# --------------------------------------------------------------------------- #

def stack_stage_params(cfg: ModelConfig, params: PyTree, spec: PipelineSpec,
                       mesh: Optional[Mesh] = None, stage_axis: str = "model",
                       ) -> Tuple[PyTree, jax.Array]:
    """[n_periods, ...] block params -> per-stage slabs [n_stages, l_max, ...].

    Returns (stage_params, valid mask [n_stages, l_max]).  Embedding / final
    norm / head stay replicated (gated by stage id at run time).

    With a ``mesh``, each stage's slab is sliced out of ``params`` and put
    straight onto the devices that own that stage along ``stage_axis``, and
    the replicated leaves are replicated over the mesh: no device ever holds
    more than its own layers, whatever devices ``params`` live on.  Without
    one, the slabs are built with traceable jnp ops (``jax.eval_shape``,
    single-device tests).
    """
    assert cfg.n_full_periods == spec.n_periods
    assert not cfg.tail, "pipeline mode requires n_layers % period == 0"
    l_max, starts, sizes = spec.l_max, spec.starts, spec.periods_per_stage

    def restack(leaf):
        out = jnp.zeros((spec.n_stages, l_max) + leaf.shape[1:], leaf.dtype)
        for s in range(spec.n_stages):
            if sizes[s]:
                out = out.at[s, :sizes[s]].set(jax.lax.dynamic_slice_in_dim(
                    leaf, starts[s], sizes[s], axis=0))
        return out

    def restack_on_devices(leaf):
        shape = (spec.n_stages, l_max) + leaf.shape[1:]
        sharding = NamedSharding(mesh, P(stage_axis))
        shards = []
        for dev, idx in sharding.addressable_devices_indices_map(
                shape).items():
            s = idx[0].start or 0           # one stage per device slice
            part = jax.device_put(leaf[starts[s]:starts[s] + sizes[s]],
                                  SingleDeviceSharding(dev))
            if sizes[s] < l_max:
                part = jnp.concatenate([part, jnp.zeros(
                    (l_max - sizes[s],) + leaf.shape[1:], leaf.dtype,
                    device=dev)])
            shards.append(part[None])
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        shards)

    stage_params = dict(params)
    if mesh is None:
        stage_params["stack"] = jax.tree.map(restack, params["stack"])
    else:
        assert mesh.shape[stage_axis] == spec.n_stages, \
            (dict(mesh.shape), spec.n_stages)
        replicated = NamedSharding(mesh, P())
        stage_params = {k: jax.device_put(v, replicated)
                        for k, v in params.items() if k != "stack"}
        stage_params["stack"] = jax.tree.map(restack_on_devices,
                                             params["stack"])
    mask = jnp.array([[l < sizes[s] for l in range(l_max)]
                      for s in range(spec.n_stages)], bool)
    return stage_params, mask


def stack_stage_caches(cfg: ModelConfig, spec: PipelineSpec,
                       n_microbatches: int, mb: int, max_len: int,
                       dtype=jnp.bfloat16) -> PyTree:
    """Fresh decode caches in stage layout: [n_stages, l_max, M, ...]."""
    per = {}
    for p, bspec in enumerate(cfg.pattern):
        one = init_block_cache(cfg, bspec, mb, max_len, dtype)
        per[f"p{p}"] = jax.tree.map(
            lambda x: jnp.zeros(
                (spec.n_stages, spec.l_max, n_microbatches) + x.shape,
                x.dtype) + x, one)
    return per


def stack_stage_caches_paged(cfg: ModelConfig, spec: PipelineSpec,
                             n_microbatches: int, mb: int, max_len: int,
                             num_blocks: int,
                             block_size: int = DEFAULT_BLOCK_SIZE,
                             dtype=jnp.bfloat16) -> PyTree:
    """Paged stage caches: every stage owns a block pool *over its own layer
    range* — attention pool leaves are [n_stages, l_max, NB+1, bs, ...]
    (no micro-batch axis: slots map blocks via the shared table), while
    ``key_pos``/``pos`` stay per-micro-batch [n_stages, l_max, M, ...].  One
    logical block id addresses the same stripe in every stage/layer pool,
    so a single host-side allocator governs all stages.  Requires mb == 1
    (request-granular slots, the scheduler's configuration)."""
    assert mb == 1, "paged pipeline caches require lanes == 1"
    per = {}
    for p, bspec in enumerate(cfg.pattern):
        if bspec.kind == "attn":
            one = init_paged_block_cache(cfg, bspec, 1, max_len, num_blocks,
                                         block_size, dtype)
            entry = {}
            for k in ("k_pool", "v_pool", "k_scale_pool", "v_scale_pool"):
                if k in one:
                    entry[k] = jnp.zeros(
                        (spec.n_stages, spec.l_max) + one[k].shape,
                        one[k].dtype)
            entry["key_pos"] = jnp.full(
                (spec.n_stages, spec.l_max, n_microbatches,
                 one["key_pos"].shape[-1]), -1, jnp.int32)
            entry["pos"] = jnp.zeros(
                (spec.n_stages, spec.l_max, n_microbatches), jnp.int32)
            per[f"p{p}"] = entry
        else:
            one = init_block_cache(cfg, bspec, mb, max_len, dtype)
            per[f"p{p}"] = jax.tree.map(
                lambda x: jnp.zeros(
                    (spec.n_stages, spec.l_max, n_microbatches) + x.shape,
                    x.dtype) + x, one)
    return per


# --------------------------------------------------------------------------- #
# microbatched forward (prefill / scoring)
# --------------------------------------------------------------------------- #

def pipeline_forward(cfg: ModelConfig, stage_params: PyTree, mask: jax.Array,
                     tokens: jax.Array, spec: PipelineSpec, mesh: Mesh,
                     n_microbatches: int, stage_axis: str = "model",
                     batch_axes: Tuple[str, ...] = ("data",),
                     impl: str = "xla") -> jax.Array:
    """GPipe-style microbatched forward. tokens [B, S] -> logits [B, S, V]."""
    b, s = tokens.shape[:2]
    m = n_microbatches
    assert b % m == 0
    mb = b // m
    ns = spec.n_stages
    positions = jnp.arange(s, dtype=jnp.int32)
    tokens_mb = tokens.reshape(m, mb, *tokens.shape[1:])

    stack_specs = jax.tree.map(lambda _: P(stage_axis), stage_params["stack"])
    other = {k: v for k, v in stage_params.items() if k != "stack"}
    other_specs = jax.tree.map(lambda _: P(), other)
    tok_spec = P(None, batch_axes, *([None] * (tokens_mb.ndim - 2)))

    def body(tok_mb, stack_local, mask_local, embed_etc):
        sid = jax.lax.axis_index(stage_axis)
        params_l = dict(embed_etc)
        params_l["stack"] = jax.tree.map(lambda x: x[0], stack_local)
        msk = mask_local[0]                                      # [l_max]

        def stage_apply(x):
            def scan_body(x_c, inp):
                layer_params, valid = inp
                y = x_c
                for p, bspec in enumerate(cfg.pattern):
                    y, _, _ = tmod._apply_block(cfg, bspec,
                                                layer_params[f"p{p}"], y,
                                                positions, "train", None, impl)
                return jnp.where(valid, y, x_c), None
            x, _ = jax.lax.scan(scan_body, x, (params_l["stack"], msk))
            return x

        steps = m + ns - 1
        d = cfg.d_model
        mb_l = tok_mb.shape[1]
        buf = jnp.zeros((mb_l, s, d), jnp.dtype(cfg.dtype))
        acc = jnp.zeros((m, mb_l, s, d), jnp.dtype(cfg.dtype))

        def step(carry, t):
            buf, acc = carry
            mb_idx = jnp.clip(t, 0, m - 1)
            inp_tok = jax.lax.dynamic_index_in_dim(tok_mb, mb_idx, 0,
                                                   keepdims=False)
            x0 = tmod._embed_inputs(cfg, params_l, inp_tok, positions)
            x_in = jnp.where(sid == 0, x0.astype(buf.dtype), buf)
            y = stage_apply(x_in)
            out_idx = jnp.clip(t - (ns - 1), 0, m - 1)
            emit = (sid == ns - 1) & (t >= ns - 1)
            prev = jax.lax.dynamic_index_in_dim(acc, out_idx, 0,
                                                keepdims=False)
            acc = jax.lax.dynamic_update_index_in_dim(
                acc, jnp.where(emit, y, prev), out_idx, 0)
            nxt = jax.lax.ppermute(y, stage_axis,
                                   [(i, (i + 1) % ns) for i in range(ns)])
            return (nxt, acc), None

        (buf, acc), _ = jax.lax.scan(step, (buf, acc), jnp.arange(steps))
        return acc                                               # valid on last stage

    acc = shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, stack_specs, P(stage_axis, None), other_specs),
        out_specs=P(stage_axis, batch_axes, None, None),
        check_vma=False,
    )(tokens_mb, stage_params["stack"], mask, other)
    # global acc: [ns*m, mb*|data|, s, d]; the last stage's block is valid
    acc = acc[(ns - 1) * m:]
    x = acc.reshape(b, s, cfg.d_model)
    x = apply_norm(stage_params["final_norm"], x, cfg.norm)
    return lm_logits(stage_params, cfg, x)


# --------------------------------------------------------------------------- #
# no-bubbles decode: tick protocol
# --------------------------------------------------------------------------- #

def _cache_pspecs(cfg: ModelConfig, stage_axis: str,
                  batch_axes: Tuple[str, ...]):
    """PartitionSpecs for stage-layout caches [n_stages, l_max, M, <leaf>].

    The per-sequence batch dim (logical axis "batch") shards over the data
    axes; nothing else shards — the model axis is consumed by the stages.
    """
    out = {}
    for p, bspec in enumerate(cfg.pattern):
        ax = cache_logical_axes(cfg, bspec)

        def to_spec(axes_tuple):
            dims = [stage_axis, None, None]
            for a in axes_tuple:
                dims.append(batch_axes if a == "batch" else None)
            return P(*dims)

        out[f"p{p}"] = jax.tree.map(to_spec, ax,
                                    is_leaf=lambda t: isinstance(t, tuple))
    return out

@jax.tree_util.register_dataclass
@dataclass
class PipelineDecodeState:
    caches: PyTree          # [n_stages, l_max, M, mb, ...]
    buf: jax.Array          # [n_stages, mb, d] activation entering each stage
    buf_mb: jax.Array       # [n_stages] int32: micro-batch id riding in buf
    buf_valid: jax.Array    # [n_stages] bool: warm-up validity flag
    logits_out: jax.Array   # [M, mb, V] f32: latest last-stage logits per mb
    token_ready: jax.Array  # [M] bool: logits_out[m] was produced by the ring
    tick: jax.Array         # scalar int32


def decode_state_shardings(cfg: ModelConfig, state: PipelineDecodeState,
                           mesh: Mesh, paged: bool,
                           stage_axis: str = "model",
                           batch_axes: Tuple[str, ...] = ("data",),
                           ) -> PipelineDecodeState:
    """Where :func:`pipeline_decode_tick` keeps each leaf of ``state`` (a
    state or its ``jax.eval_shape``): creating the state with these
    shardings puts every stage's caches on its own devices from the start,
    and the first tick compiles for the same layout as every later one."""
    def named(spec):
        return NamedSharding(mesh, spec)
    if paged:
        caches = jax.tree.map(lambda _: named(P(stage_axis)), state.caches)
    else:
        caches = jax.tree.map(named, _cache_pspecs(cfg, stage_axis,
                                                   batch_axes),
                              is_leaf=lambda x: isinstance(x, P))
    return PipelineDecodeState(
        caches=caches,
        buf=named(P(stage_axis, batch_axes, None)),
        buf_mb=named(P(stage_axis)),
        buf_valid=named(P(stage_axis)),
        logits_out=named(P(None, batch_axes, None)),
        token_ready=named(P(None)),
        tick=named(P()))


def init_pipeline_decode_state(cfg: ModelConfig, spec: PipelineSpec,
                               n_microbatches: int, mb: int, max_len: int,
                               dtype=jnp.bfloat16,
                               cache_layout: str = "contiguous",
                               num_blocks: int = 0,
                               block_size: int = DEFAULT_BLOCK_SIZE,
                               ) -> PipelineDecodeState:
    if cache_layout == "paged":
        caches = stack_stage_caches_paged(cfg, spec, n_microbatches, mb,
                                          max_len, num_blocks, block_size,
                                          dtype)
    else:
        caches = stack_stage_caches(cfg, spec, n_microbatches, mb, max_len,
                                    dtype)
    return PipelineDecodeState(
        caches=caches,
        buf=jnp.zeros((spec.n_stages, mb, cfg.d_model), jnp.dtype(cfg.dtype)),
        buf_mb=jnp.zeros((spec.n_stages,), jnp.int32),
        buf_valid=jnp.zeros((spec.n_stages,), bool),
        logits_out=jnp.zeros((n_microbatches, mb, cfg.vocab_size),
                             jnp.float32),
        token_ready=jnp.zeros((n_microbatches,), bool),
        tick=jnp.zeros((), jnp.int32),
    )


def pipeline_decode_tick(cfg: ModelConfig, stage_params: PyTree,
                         mask: jax.Array, state: PipelineDecodeState,
                         feed_tokens: jax.Array, spec: PipelineSpec,
                         mesh: Mesh, stage_axis: str = "model",
                         batch_axes: Tuple[str, ...] = ("data",),
                         impl: str = "xla",
                         vocab_sharded: bool = False,
                         feed_valid: Optional[jax.Array] = None,
                         block_tables: Optional[jax.Array] = None,
                         ) -> PipelineDecodeState:
    """One no-bubbles decode tick.

    Stage 0 ingests ``feed_tokens [mb]`` for micro-batch ``tick % M``; every
    stage advances the micro-batch riding in its buffer; the last stage
    computes the full next-token logits and they ride the ring back to stage
    0 where they are recorded in ``logits_out`` (the paper's return-to-source
    hop).  Sampling happens on the host — greedy and temperature>0 requests
    both work, and speculative verify can score draft tokens against the
    returned distribution.

    ``feed_valid`` (scalar bool, default True) marks this tick's ingested
    micro-batch as live.  The serving runtime feeds dead ticks with
    ``feed_valid=False`` when a micro-batch slot has no active request, so
    the garbage activation rides the ring without touching KV caches or
    ``logits_out`` — the same warm-up validity mechanism, driven externally.

    ``vocab_sharded`` (§Perf-C2, beyond-paper): shard the embedding table
    (rows) and LM head (columns) over the *stage* axis so each stage reads
    1/n_stages of the vocab weights per tick instead of the full tables —
    the tables are otherwise re-read every tick by every stage although only
    stage 0 embeds and only the last stage computes logits.  Reconstruction
    costs a psum of the [mb, d] embedding partials, a broadcast of the last
    stage's hidden, and a scatter + psum that reassembles the full [mb, V]
    logits from the per-stage column slices.  Requires
    ``vocab_size % n_stages == 0``.

    ``block_tables`` ([M, max_ctx_blocks] int32, replicated) switches the
    KV path to the *paged* layout: each stage holds a block pool over its
    own layer range (see :func:`stack_stage_caches_paged`) and micro-batch
    ``m``'s attention state is reached through ``block_tables[m]`` instead
    of a dense cache slice.  Dead ticks (``feed_valid=False``) redirect
    their pool writes to the scratch block, extending the warm-up validity
    mechanism to the shared pool.
    """
    ns = spec.n_stages
    m = state.logits_out.shape[0]
    paged = block_tables is not None
    if vocab_sharded:
        assert cfg.vocab_size % ns == 0, (cfg.vocab_size, ns)
    if feed_valid is None:
        feed_valid = jnp.ones((), bool)
    if not paged:       # keep one jaxpr signature; the dummy operand is dead
        block_tables = jnp.zeros((m, 1), jnp.int32)

    stack_specs = jax.tree.map(lambda _: P(stage_axis), stage_params["stack"])
    if paged:           # pools/key_pos/pos all lead with the stage axis only
        cache_specs = jax.tree.map(lambda _: P(stage_axis), state.caches)
    else:
        cache_specs = _cache_pspecs(cfg, stage_axis, batch_axes)
    other = {k: v for k, v in stage_params.items() if k != "stack"}
    other_specs = jax.tree.map(lambda _: P(), other)
    if vocab_sharded:
        other_specs = dict(other_specs)
        other_specs["embedding"] = P(stage_axis, None)      # [V, d] rows
        if "lm_head" in other:
            other_specs["lm_head"] = P(None, stage_axis)    # [d, V] cols

    def body(stack_local, embed_etc, mask_local, caches_l, buf_l, buf_mb_l,
             buf_valid_l, feed, fvalid, tick, btab):
        sid = jax.lax.axis_index(stage_axis)
        params_l = dict(embed_etc)
        params_l["stack"] = jax.tree.map(lambda x: x[0], stack_local)
        caches_l = jax.tree.map(lambda x: x[0], caches_l)       # [l_max, M, ...]
        msk = mask_local[0]                                      # [l_max]
        buf = buf_l[0]                                           # [mb, d]
        my_mb = buf_mb_l[0]
        my_valid = buf_valid_l[0]

        fresh_mb = jnp.mod(tick, m)
        if vocab_sharded:
            # local vocab slice: rows [V/ns, d]; mask out-of-slice ids, psum
            vs = cfg.vocab_size // ns
            base = sid * vs
            ids = feed.astype(jnp.int32) - base
            in_slice = (ids >= 0) & (ids < vs)
            rows = jnp.take(params_l["embedding"],
                            jnp.clip(ids, 0, vs - 1), axis=0)
            rows = jnp.where(in_slice[:, None], rows, 0)
            x_embed = jax.lax.psum(rows, stage_axis)             # [mb, d]
            if cfg.name.startswith(("gemma", "recurrentgemma")):
                x_embed = x_embed * jnp.asarray(
                    np.sqrt(cfg.d_model), x_embed.dtype)
        else:
            x_embed = embed_tokens(params_l, cfg, feed)          # [mb, d]
        is_first = sid == 0
        x_in = jnp.where(is_first, x_embed.astype(buf.dtype), buf)[:, None, :]
        mb_idx = jnp.where(is_first, fresh_mb, my_mb)
        valid = jnp.where(is_first, fvalid, my_valid)

        bt_slot = jax.lax.dynamic_index_in_dim(btab, mb_idx, 0,
                                               keepdims=False)

        def scan_body(x_c, inp):
            layer_params, layer_caches, lvalid = inp
            ok = lvalid & valid
            y = x_c
            new_caches = {}
            for p, bspec in enumerate(cfg.pattern):
                lc = layer_caches[f"p{p}"]
                if paged and bspec.kind == "attn":
                    # pools are layer-wide (no M axis); this micro-batch's
                    # view = shared pools + its block-table row + its
                    # key_pos/pos slices.  Writes are gated inside the
                    # paged attention (scratch redirect + frozen pos), so
                    # a dead tick cannot touch another slot's blocks.
                    my = {k: lc[k] for k in POOL_KEYS if k in lc}
                    my["bt"] = bt_slot
                    my["key_pos"] = jax.lax.dynamic_index_in_dim(
                        lc["key_pos"], mb_idx, 0, keepdims=False)
                    my["pos"] = jax.lax.dynamic_index_in_dim(
                        lc["pos"], mb_idx, 0, keepdims=False)
                    y, c2, _ = tmod._apply_block(
                        cfg, bspec, layer_params[f"p{p}"], y, None,
                        "decode", my, impl, write_mask=ok)
                    nc = {k: c2[k] for k in my if k not in
                          ("bt", "key_pos", "pos")}
                    nc["key_pos"] = jax.lax.dynamic_update_index_in_dim(
                        lc["key_pos"], c2["key_pos"], mb_idx, 0)
                    nc["pos"] = jax.lax.dynamic_update_index_in_dim(
                        lc["pos"], c2["pos"], mb_idx, 0)
                else:
                    my_cache = jax.tree.map(
                        lambda c: jax.lax.dynamic_index_in_dim(
                            c, mb_idx, 0, keepdims=False), lc)
                    y, c2, _ = tmod._apply_block(
                        cfg, bspec, layer_params[f"p{p}"], y, None,
                        "decode", my_cache, impl)
                    nc = jax.tree.map(
                        lambda old, new, cur:
                        jax.lax.dynamic_update_index_in_dim(
                            old, jnp.where(ok, new, cur), mb_idx, 0),
                        lc, c2, my_cache)
                new_caches[f"p{p}"] = nc
            y = jnp.where(ok, y, x_c)
            return y, new_caches

        x_out, new_caches = jax.lax.scan(scan_body, x_in,
                                         (params_l["stack"], caches_l, msk))
        x_out2 = x_out[:, 0]                                     # [mb, d]

        # last stage: final norm + full next-token logits
        h = apply_norm(params_l["final_norm"], x_out, cfg.norm)
        if vocab_sharded:
            from repro.models.layers import softcap
            vs = cfg.vocab_size // ns
            base = sid * vs
            # broadcast the last stage's hidden to every stage (tiny [mb,d])
            h_last = jax.lax.psum(
                jnp.where(sid == ns - 1, h, jnp.zeros_like(h)), stage_axis)
            if cfg.tie_embeddings:
                logit_slice = h_last[:, 0] @ params_l["embedding"].T
            else:
                logit_slice = h_last[:, 0] @ params_l["lm_head"]
            logit_slice = softcap(logit_slice, cfg.final_logit_softcap)
            # reassemble the full [mb, V] row: scatter the local column
            # slice at its vocab offset and psum — identical on all stages.
            full = jnp.zeros((logit_slice.shape[0], cfg.vocab_size),
                             jnp.float32)
            full = jax.lax.dynamic_update_slice_in_dim(
                full, logit_slice.astype(jnp.float32), base, axis=1)
            logits = jax.lax.psum(full, stage_axis)              # [mb, V]
        else:
            logits = lm_logits(params_l, cfg, h)[:, 0]           # [mb, V]
            logits = logits.astype(jnp.float32)

        # ring shift: activations to the next stage; logits close the ring
        perm = [(i, (i + 1) % ns) for i in range(ns)]
        nxt_buf = jax.lax.ppermute(x_out2, stage_axis, perm)
        nxt_mb = jax.lax.ppermute(mb_idx, stage_axis, perm)
        nxt_valid = jax.lax.ppermute(valid, stage_axis, perm)
        logits_ring = jax.lax.ppermute(logits, stage_axis, perm)  # last->0
        done_mb = jax.lax.ppermute(mb_idx, stage_axis, perm)
        done_valid = jax.lax.ppermute(valid & (sid == ns - 1), stage_axis,
                                      perm)

        # stage 0 records the completed logits; replicate via psum
        upd = (sid == 0) & done_valid
        onehot = (jnp.arange(m) == done_mb) & upd                # [M]
        log_update = jnp.where(onehot[:, None, None],
                               logits_ring[None, :, :], 0.)
        log_update = jax.lax.psum(log_update, stage_axis)
        ready_update = jax.lax.psum(onehot.astype(jnp.int32), stage_axis) > 0

        return (jax.tree.map(lambda x: x[None], new_caches),
                nxt_buf[None], nxt_mb[None], nxt_valid[None],
                log_update, ready_update)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(stack_specs, other_specs, P(stage_axis, None), cache_specs,
                  P(stage_axis, batch_axes, None), P(stage_axis),
                  P(stage_axis), P(batch_axes), P(), P(), P()),
        out_specs=(cache_specs,
                   P(stage_axis, batch_axes, None), P(stage_axis),
                   P(stage_axis), P(None, batch_axes, None), P(None)),
        check_vma=False,
    )(stage_params["stack"], other, mask, state.caches, state.buf,
      state.buf_mb, state.buf_valid, feed_tokens,
      jnp.asarray(feed_valid, bool), state.tick, block_tables)
    new_caches, buf, buf_mb, buf_valid, log_update, ready = out

    logits_out = jnp.where(ready[:, None, None], log_update,
                           state.logits_out)
    token_ready = state.token_ready | ready
    return PipelineDecodeState(
        caches=new_caches, buf=buf, buf_mb=buf_mb, buf_valid=buf_valid,
        logits_out=logits_out, token_ready=token_ready,
        tick=state.tick + 1)
