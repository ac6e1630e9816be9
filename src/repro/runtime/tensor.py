"""TensorBackend: the pjit tensor-parallel (or single-device) execution path
behind the :class:`~repro.runtime.base.InferenceBackend` protocol.

Extracted from ``serving/engine.py`` and made *slot-granular*: the engine's
single batch-wide KV cache (one shared ``pos`` / ``key_pos`` for every
sequence) is replaced by per-slot caches, so a new request can be admitted
into a free slot mid-flight without re-prefilling — or corrupting — the
requests already decoding.

Two cache layouts, selectable via ``cache_layout``:

- ``"contiguous"`` (default) — one worst-case ``max_len`` ring buffer per
  slot; decode vmaps the single-sequence step over the slot axis, which
  gives every slot its own position counter for free.
- ``"paged"`` — slots map vLLM-style block tables into a shared pool of
  ``num_blocks`` KV blocks (``block_size`` tokens each, one pool stripe per
  attention layer; see ``models/kvcache.py``).  Decode runs the whole slot
  batch in ONE pass with per-slot positions (no vmap — a shared pool cannot
  be batched), scattering the new token's k/v into the pool and attending
  through each slot's table: ``impl="pallas"`` streams the blocks directly
  inside the paged decode kernel (block table scalar-prefetched into the
  BlockSpec index map — no gathered copy of the cache per step), while
  ``impl="xla"`` gathers the blocks into a dense ``[B, C_pad, ...]``
  temporary and runs the masked sdpa.  Host-side allocation
  (:class:`~repro.runtime.base.SlotPager`) grows tables as slots cross
  block boundaries and raises :class:`~repro.runtime.base.PoolExhausted`
  *before* mutating anything when the pool can't cover the next quantum —
  the scheduler's cue to preempt and requeue.  Prefill runs the same
  contiguous kernel over the admission wave (sized by the *bucketed prompt
  length*, not ``max_len``), then scatters the wave's ring caches into the
  pool by absolute position.

On both layouts each request of an admission wave is prefilled as its own
one-row program, so a wave costs the rows it holds and every width compiles
one prefill shape and one scatter shape.

Both layouts run *masked* prefill: ``prefill(slots, prompts, prompt_lens)``
left-pads to the bucket but masks pads out of attention and never writes
them as valid cache keys (``models/transformer.py::forward``), so a slot's
outputs are independent of the padded width — identical to an exact-length
unpadded prefill, whichever bucket admission chose.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import kvcache as KV
from repro.models import transformer as T
from repro.models.attention import effective_decode_impl
from repro.models.config import ModelConfig
from repro.runtime.base import (BackendInfo, InferenceBackend, PoolExhausted,
                                SlotEvent, SlotPager)
from repro.runtime.prefix_cache import PrefixCache
from repro.sharding.rules import use_mesh

PyTree = Any

def _flat_with_axes(caches: PyTree, axes: PyTree):
    """Zip cache leaves with their logical-axis tuples from cache_axes."""
    leaves, treedef = jax.tree.flatten(caches)
    ax_leaves, ax_treedef = jax.tree.flatten(
        axes, is_leaf=lambda t: isinstance(t, tuple))
    assert len(leaves) == len(ax_leaves), (treedef, ax_treedef)
    return leaves, ax_leaves, treedef


def reset_stream(caches: PyTree, slot: jax.Array,
                 start: jax.Array) -> PyTree:
    """Wipe one slot's paged ring view for a streamed admission: mark
    positions below ``start`` (the adopted prefix, whose blocks the
    host just wired into the table) as valid keys, everything above as
    empty — stale keys from the slot's previous occupant must never be
    attended."""
    def fix(entry, stacked):
        if not KV.is_paged_attn_cache(entry):
            return entry
        e = dict(entry)
        c_pad = entry["key_pos"].shape[-1]
        row = jnp.where(jnp.arange(c_pad, dtype=jnp.int32) < start,
                        jnp.arange(c_pad, dtype=jnp.int32), -1)
        if stacked:                                  # key_pos [L, B, C]
            e["key_pos"] = entry["key_pos"].at[:, slot].set(row[None])
            e["pos"] = entry["pos"].at[:, slot].set(start)
        else:
            e["key_pos"] = entry["key_pos"].at[slot].set(row)
            e["pos"] = entry["pos"].at[slot].set(start)
        return e

    out = dict(caches)
    if "stack" in out:
        out["stack"] = {k: fix(v, True) for k, v in out["stack"].items()}
    if "tail" in out:
        out["tail"] = {k: fix(v, False) for k, v in out["tail"].items()}
    return out


def rollback(caches: PyTree, new_pos: jax.Array,
             mask: jax.Array) -> PyTree:
    """Batched verify rollback: for every masked slot, mark positions
    below ``new_pos[s]`` valid and everything above empty, and rewind
    ``pos``.  Valid because the spec gate guarantees ring slot ==
    position (no wrap), so position identity IS slot identity — a
    rejected draft's key can be invalidated without touching any
    surviving key."""
    def fix(entry, stacked):
        if not KV.is_paged_attn_cache(entry):
            return entry
        e = dict(entry)
        c_pad = entry["key_pos"].shape[-1]
        iota = jnp.arange(c_pad, dtype=jnp.int32)[None, :]
        row = jnp.where(iota < new_pos[:, None], iota, -1)   # [B, C]
        if stacked:
            e["key_pos"] = jnp.where(mask[None, :, None], row[None],
                                     entry["key_pos"])
            e["pos"] = jnp.where(mask[None, :],
                                 new_pos[None].astype(entry["pos"].dtype),
                                 entry["pos"])
        else:
            e["key_pos"] = jnp.where(mask[:, None], row,
                                     entry["key_pos"])
            e["pos"] = jnp.where(mask, new_pos.astype(entry["pos"].dtype),
                                 entry["pos"])
        return e

    out = dict(caches)
    if "stack" in out:
        out["stack"] = {k: fix(v, True) for k, v in out["stack"].items()}
    if "tail" in out:
        out["tail"] = {k: fix(v, False) for k, v in out["tail"].items()}
    return out


class TensorBackend(InferenceBackend):
    """pjit prefill + (vmapped contiguous | batched paged) decode."""

    def __init__(self, cfg: ModelConfig, params: PyTree, n_slots: int,
                 max_len: int, mesh=None, impl: str = "xla",
                 cache_dtype=jnp.float32, cache_layout: str = "contiguous",
                 block_size: int = KV.DEFAULT_BLOCK_SIZE,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = False):
        assert cache_layout in ("contiguous", "paged"), cache_layout
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.mesh = mesh
        self.impl = impl
        self.cache_dtype = cache_dtype
        self.cache_layout = cache_layout
        self._axes = T.cache_axes(cfg)

        nbs = KV.max_ctx_blocks(cfg, max_len, block_size)
        # attention-free models have nothing to page; keep the contiguous
        # machinery and report an (empty) paged pool honestly
        self._paged_exec = cache_layout == "paged" and nbs > 0
        self.block_size = block_size
        self.num_blocks = 0
        self.pager: Optional[SlotPager] = None
        if cache_layout == "paged":
            self.num_blocks = num_blocks if num_blocks is not None \
                else n_slots * nbs
            self.pager = SlotPager(n_slots, self.num_blocks, block_size, nbs)

        # streamed admission (prefix reuse + chunked prefill) needs ring
        # slot == absolute position: paged layout, all-attention, no
        # effective window.  Unsupported deployments silently keep the
        # monolithic path (the --prefix-cache "contiguous ignore" contract).
        self._extend_ok = self._paged_exec and \
            KV.prefix_sharing_supported(cfg, max_len)
        self._prefix_on = bool(prefix_cache) and self._extend_ok
        self.prefix: Optional[PrefixCache] = None
        if self._prefix_on:
            self.prefix = PrefixCache(self.pager.allocator, block_size)
        self._prefix_hits = 0
        self._prefix_hit_tokens = 0
        self._stream_tokens: Dict[int, np.ndarray] = {}

        if self._paged_exec:
            self.caches = T.init_paged_caches(cfg, n_slots, max_len,
                                              self.num_blocks, block_size,
                                              cache_dtype)
        else:
            # per-slot cache storage: every leaf of a single-sequence cache,
            # stacked along a leading slot axis
            one = T.init_caches(cfg, 1, max_len, cache_dtype)
            self.caches = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_slots,) + x.shape).copy(),
                one)

        # each program's name is fixed (``jit_<def name>`` in the device
        # trace's XLA Modules line), so a trace reader can tell them apart
        def prefill(params, tokens, caches, prompt_lens):
            return T.forward(cfg, params, tokens, mode="prefill",
                             caches=caches, impl=impl,
                             prompt_lens=prompt_lens)
        self._prefill_fn = jax.jit(prefill)

        if self._paged_exec:
            def decode_step(params, tokens, caches, write_mask):
                return T.decode_step(cfg, params, tokens, caches, impl=impl,
                                     write_mask=write_mask)

            def prefill_scatter(storage, new, idx, bt_rows):
                return self._scatter_paged(storage, new, idx, bt_rows)
            self._decode_fn = jax.jit(decode_step, donate_argnums=(2,))
            self._scatter_fn = jax.jit(prefill_scatter, donate_argnums=(0,))
            if self._extend_ok:
                def extend(params, tokens, caches, starts, lens):
                    return T.extend_step(cfg, params, tokens, caches, starts,
                                         lens, impl=impl)

                def verify(params, tokens, caches, lens):
                    return T.verify_step(cfg, params, tokens, caches, lens,
                                         impl=impl)
                self._extend_fn = jax.jit(extend, donate_argnums=(2,))
                self._reset_stream_fn = jax.jit(reset_stream,
                                                donate_argnums=(0,))
                self._verify_fn = jax.jit(verify, donate_argnums=(2,))
                self._rollback_fn = jax.jit(rollback, donate_argnums=(0,))
        else:
            def decode_step(params, tokens, caches):
                logits, new = jax.vmap(
                    lambda tok, c: T.decode_step(cfg, params, tok[None], c,
                                                 impl=impl),
                    in_axes=(0, 0))(tokens, caches)
                return logits[:, 0], new

            def prefill_scatter(storage, new, idx):
                return self._scatter(storage, new, idx)
            self._decode_fn = jax.jit(decode_step)
            self._scatter_fn = jax.jit(prefill_scatter, donate_argnums=(0,))

        # speculative verify shares extend's preconditions: paged layout
        # with ring slot == position, so rejected drafts roll back exactly
        self._spec_ok = self._extend_ok
        self._pending: Dict[int, int] = {}     # slot -> fed len, last verify

        # host mirrors for paged allocation (decode position per slot)
        self._pos = np.zeros(n_slots, np.int64)
        self._active = np.zeros(n_slots, bool)

        cache_bytes = sum(l.nbytes for l in jax.tree.leaves(self.caches))
        self._info = BackendInfo(
            n_slots=n_slots, max_len=max_len,
            cache_bytes_per_slot=cache_bytes // n_slots,
            param_bytes=sum(l.nbytes for l in jax.tree.leaves(params)),
            samples_in_backend=False,
            cache_layout=cache_layout,
            block_size=block_size if cache_layout == "paged" else 0,
            total_blocks=self.num_blocks,
            free_blocks=self.num_blocks,
            bytes_per_block=KV.block_pool_bytes_per_block(cfg, cache_dtype)
            if cache_layout == "paged" else 0,
            max_ctx_blocks=nbs if cache_layout == "paged" else 0,
            prefix_caching=self._prefix_on,
            supports_extend=self._extend_ok,
            attn_impl=effective_decode_impl(impl, cfg)
            if self._paged_exec else impl,
            spec_decode=self._spec_ok)

    @property
    def info(self) -> BackendInfo:
        return self._live_info()

    # ------------------------------------------------------------------ #
    # contiguous scatter: wave prefill caches -> per-slot storage
    # ------------------------------------------------------------------ #
    def _scatter(self, storage: PyTree, new: PyTree, idx: jax.Array) -> PyTree:
        """Write batch-k prefill caches into per-slot storage at ``idx``.

        Every stateful leaf — ``key_pos``/``pos`` included, which are
        per-row since masked prefill — carries a batch dim where the
        logical axes say "batch" and lands at its slot's row; the rare
        batch-free leaf is replicated.  Per-slot storage keeps a size-1
        batch dim in every batched leaf so the vmapped decode sees the
        [B=1] cache shape.
        """
        k = idx.shape[0]
        s_leaves, ax_leaves, treedef = _flat_with_axes(storage, self._axes)
        n_leaves, _, _ = _flat_with_axes(new, self._axes)
        out = []
        for leaf_s, leaf_n, ax in zip(s_leaves, n_leaves, ax_leaves):
            if "batch" in ax:
                b = ax.index("batch")
                per = jnp.expand_dims(jnp.moveaxis(leaf_n, b, 0), axis=1 + b)
            else:                           # replicate batch-shared leaves
                per = jnp.broadcast_to(leaf_n, (k,) + leaf_n.shape)
            out.append(leaf_s.at[idx].set(per.astype(leaf_s.dtype)))
        return jax.tree.unflatten(treedef, out)

    # ------------------------------------------------------------------ #
    # paged scatter: dense ring prefill caches -> block pool
    # ------------------------------------------------------------------ #
    def _scatter_one_paged(self, spec, paged: Dict, dense: Dict,
                           slots: jax.Array, bt_rows: jax.Array) -> Dict:
        """Scatter one attention entry's wave prefill (ring layout, any
        cache length) into the pool by absolute position.  All leaves carry
        a leading layer axis (callers expand tail entries to L=1).

        The dense wave cache is per-row (``key_pos [L, W, C_d]``, ``pos
        [L, W]``): after a masked prefill each row holds its own true
        length, with pad slots at ``key_pos == -1`` — those scatter to the
        scratch block and stay invisible."""
        c_pad = paged["key_pos"].shape[-1]
        bs = paged["k_pool"].shape[2]
        scratch = paged["k_pool"].shape[1] - 1
        kp0 = dense["key_pos"][0]                       # [W, C_d] (layer-shared)
        valid = kp0 >= 0                                # [W, C_d]
        ring = jnp.where(valid, kp0 % c_pad, 0)
        blk, off = ring // bs, ring % bs                # [W, C_d]
        phys = jnp.take_along_axis(bt_rows, blk, axis=1)  # [W, C_d]
        tgt = jnp.where(valid & (phys >= 0), phys, scratch)

        out = dict(paged)
        pairs = [("k_pool", "k"), ("v_pool", "v")]
        if self.cfg.kv_dtype == "int8":
            pairs += [("k_scale_pool", "k_scale"), ("v_scale_pool", "v_scale")]
        for pool_key, dense_key in pairs:
            pool = paged[pool_key]                      # [L, NB+1, bs, ...]
            vals = dense[dense_key].astype(pool.dtype)  # [L, W, C_d, ...]
            out[pool_key] = pool.at[:, tgt, off].set(vals)

        # per-slot ring view: key_pos rows rebuilt at the paged ring length
        # (index c_pad is the sacrificial column for invalid entries)
        w = kp0.shape[0]
        rows = jnp.arange(w)[:, None]
        safe = jnp.where(valid, ring, c_pad)
        row = jnp.full((w, c_pad + 1), -1, jnp.int32).at[rows, safe].set(
            jnp.where(valid, kp0, -1))[:, :c_pad]       # [W, c_pad]
        out["key_pos"] = paged["key_pos"].at[:, slots].set(row[None])
        out["pos"] = paged["pos"].at[:, slots].set(dense["pos"])
        out["bt"] = paged["bt"].at[:, slots].set(bt_rows[None])
        return out

    def _scatter_paged(self, storage: PyTree, new: PyTree, idx: jax.Array,
                       bt_rows: jax.Array) -> PyTree:
        """Write a wave's dense prefill caches into the paged storage."""
        def walk(group: str, specs):
            src = new.get(group)
            dst = storage.get(group)
            if dst is None:
                return None
            out = {}
            for key, spec in specs:
                d, s = dst[key], src[key]
                if spec.kind == "attn":
                    if group == "tail":            # expand to L=1, squeeze
                        d1 = jax.tree.map(lambda x: x[None], d)
                        s1 = jax.tree.map(lambda x: x[None], s)
                        r = self._scatter_one_paged(spec, d1, s1, idx,
                                                    bt_rows)
                        out[key] = jax.tree.map(lambda x: x[0], r)
                    else:
                        out[key] = self._scatter_one_paged(spec, d, s, idx,
                                                           bt_rows)
                else:
                    # dense per-slot state: every leaf (pos included) leads
                    # with the batch axis and lands at the wave's slot rows
                    if group == "stack":
                        e = {k: d[k].at[:, idx].set(s[k].astype(d[k].dtype))
                             for k in d}
                    else:
                        e = {k: d[k].at[idx].set(s[k].astype(d[k].dtype))
                             for k in d}
                    out[key] = e
            return out

        result: Dict[str, Any] = {}
        if self.cfg.n_full_periods > 0:
            result["stack"] = walk(
                "stack", [(f"p{p}", s) for p, s in enumerate(self.cfg.pattern)])
        if self.cfg.tail:
            result["tail"] = walk(
                "tail", [(f"t{t}", s) for t, s in enumerate(self.cfg.tail)])
        return result

    # ------------------------------------------------------------------ #
    # speculative verify: K fed tokens per slot, one forward pass
    # ------------------------------------------------------------------ #
    def verify_step(self, feeds: Dict[int, np.ndarray]) -> List[SlotEvent]:
        if not feeds:
            return []
        assert self._spec_ok, "backend does not advertise spec_decode"
        assert not self._pending, "verify_step before accept() of the last"
        with obs.span("repro.backend.verify_step", rows=len(feeds)):
            fed = {s: np.asarray(f, np.int32).ravel()
                   for s, f in feeds.items()}
            kq = max(len(f) for f in fed.values())
            assert kq >= 1 and all(len(f) >= 1 for f in fed.values())
            tokens = np.zeros((self.n_slots, kq), np.int32)
            lens = np.zeros(self.n_slots, np.int32)
            live = [s for s in sorted(fed) if self._active[s]]
            for s in live:
                assert int(self._pos[s]) + len(fed[s]) <= self.max_len, \
                    (s, int(self._pos[s]), len(fed[s]), self.max_len)
                tokens[s, :len(fed[s])] = fed[s]
                lens[s] = len(fed[s])
            # atomic growth: blocks for ALL candidate positions up front (a
            # rejected tail leaves its blocks allocated — they back the very
            # next tokens anyway), raising before any state mutates
            with obs.span("repro.backend.pager"):
                need = sum(
                    max(self.pager.blocks_for_len(int(self._pos[s] + lens[s]))
                        - int(self.pager.n_alloc[s]), 0) for s in live)
                if need > self.pager.free_blocks:
                    raise PoolExhausted(needed=need,
                                        free=self.pager.free_blocks)
                if self._grow_atomic(
                        [(s, int(self._pos[s] + lens[s]) - 1) for s in live]):
                    self._push_tables()
            with obs.span("repro.backend.dispatch"), use_mesh(self.mesh):
                logits, self.caches = self._verify_fn(
                    self.params, jnp.asarray(tokens), self.caches,
                    jnp.asarray(lens))
            with obs.span("repro.backend.fetch"):
                logits = np.asarray(logits, np.float32)
            # host _pos stays at the pre-verify position until accept()
            # commits
            self._pending = {s: int(lens[s]) for s in live}
            return [SlotEvent(slot=s, logits=logits[s, :int(lens[s])])
                    for s in live]

    def accept(self, counts: Dict[int, int]) -> None:
        pend, self._pending = self._pending, {}
        assert set(counts) == set(pend), (sorted(counts), sorted(pend))
        new_pos = np.asarray(self._pos, np.int64).copy()
        mask = np.zeros(self.n_slots, bool)
        partial = False
        for s, e in counts.items():
            e = int(e)
            assert 0 <= e <= pend[s], (s, e, pend[s])
            mask[s] = True
            new_pos[s] = self._pos[s] + e
            partial |= e < pend[s]
        if partial:
            # rewind rejected draft keys; full acceptance leaves the device
            # state exactly right already (pos advanced by lens in verify)
            with use_mesh(self.mesh):
                self.caches = self._rollback_fn(
                    self.caches, jnp.asarray(new_pos, jnp.int32),
                    jnp.asarray(mask))
        for s in counts:
            self._pos[s] = int(new_pos[s])

    # ------------------------------------------------------------------ #
    # streamed admission: prefix adoption + chunked/offset prefill
    # ------------------------------------------------------------------ #
    def cached_prefix_len(self, prompt: np.ndarray) -> int:
        if not self._prefix_on:
            return 0
        p = np.asarray(prompt).ravel()
        cap = ((len(p) - 1) // self.block_size) * self.block_size
        return self.prefix.matched_tokens(p[:cap])

    def start_stream(self, slot: int, prompt: np.ndarray) -> int:
        assert self._extend_ok, "backend does not advertise supports_extend"
        prompt = np.asarray(prompt, np.int32).ravel()
        plen = len(prompt)
        assert plen >= 1
        self.pager.release(slot)
        start = 0
        if self._prefix_on:
            # cap so at least one suffix token remains to produce logits
            cap = ((plen - 1) // self.block_size) * self.block_size
            blocks = self.prefix.lookup(prompt[:cap])
            start = len(blocks) * self.block_size
            if start:
                self.pager.adopt(slot, blocks)
                self._prefix_hits += 1
                self._prefix_hit_tokens += start
        with use_mesh(self.mesh):
            self.caches = self._reset_stream_fn(
                self.caches, jnp.int32(slot), jnp.int32(start))
        self._stream_tokens[slot] = prompt
        self._pos[slot] = start
        self._active[slot] = True
        return start

    def prefill_chunk(self, slots: Sequence[int], chunks: np.ndarray,
                      chunk_lens: Sequence[int], starts: Sequence[int],
                      last: Sequence[bool]) -> List[SlotEvent]:
        chunks = np.atleast_2d(np.asarray(chunks, np.int32))
        k, w = chunks.shape
        lens = np.asarray(chunk_lens, np.int32)
        sts = np.asarray(starts, np.int64)
        assert len(slots) == k and lens.shape == (k,) and sts.shape == (k,)
        assert np.all(lens >= 1) and np.all(lens <= w)
        with obs.span("repro.backend.prefill_chunk", rows=k, width=w):
            # atomic growth check: raise before any table mutates so the
            # scheduler can preempt and retry the whole chunk wave
            with obs.span("repro.backend.pager"):
                need = sum(
                    max(self.pager.blocks_for_len(int(st + ln))
                        - int(self.pager.n_alloc[s]), 0)
                    for s, st, ln in zip(slots, sts, lens))
                if need > self.pager.free_blocks:
                    raise PoolExhausted(needed=need,
                                        free=self.pager.free_blocks)
                self._grow_atomic([(s, int(st + ln) - 1)
                                   for s, st, ln in zip(slots, sts, lens)])
                self._push_tables()
            # extend_step works in slot space [n_slots, w]: scatter the
            # wave's rows to their slots and make every other row a no-op
            # (len 0 => all writes masked to scratch, start=pos => pos
            # unchanged), so each chunk width compiles once regardless of
            # wave composition
            full_chunks = np.zeros((self.n_slots, w), np.int32)
            full_lens = np.zeros(self.n_slots, np.int32)
            full_starts = np.asarray(self._pos, np.int32).copy()
            for i, s in enumerate(slots):
                full_chunks[s] = chunks[i]
                full_lens[s] = lens[i]
                full_starts[s] = sts[i]
            with obs.span("repro.backend.dispatch"), use_mesh(self.mesh):
                logits, self.caches = self._extend_fn(
                    self.params, jnp.asarray(full_chunks), self.caches,
                    jnp.asarray(full_starts), jnp.asarray(full_lens))
            with obs.span("repro.backend.fetch"):
                last_logits = np.asarray(logits[:, -1], np.float32)
            events = []
            for i, s in enumerate(slots):
                self._pos[s] = int(sts[i] + lens[i])
                if last[i]:
                    if self._prefix_on:
                        self._register_stream(s)
                    self._stream_tokens.pop(s, None)
                    events.append(SlotEvent(slot=s, logits=last_logits[s]))
            return events

    def _register_stream(self, slot: int) -> None:
        """Index the finished stream's full token blocks for future reuse."""
        toks = self._stream_tokens.get(slot)
        if toks is None:
            return
        nfull = len(toks) // self.block_size
        nfull = min(nfull, int(self.pager.n_alloc[slot]))
        if nfull:
            blocks = self.pager.table[slot, :nfull].tolist()
            self.prefix.register(toks, blocks)

    def _push_tables(self) -> None:
        """Refresh the device block-table leaves from the host pager."""
        table = jnp.asarray(self.pager.table)

        def fix(entry, stacked):
            if not KV.is_paged_attn_cache(entry):
                return entry
            e = dict(entry)
            e["bt"] = jnp.broadcast_to(
                table, entry["bt"].shape) if stacked else table
            return e

        caches = dict(self.caches)
        if "stack" in caches:
            caches["stack"] = {k: fix(v, True)
                               for k, v in caches["stack"].items()}
        if "tail" in caches:
            caches["tail"] = {k: fix(v, False)
                              for k, v in caches["tail"].items()}
        self.caches = caches

    def _grow_atomic(self, targets: Sequence[Tuple[int, int]]) -> bool:
        """Grow several slots' tables as ONE transaction: ensure every
        ``(slot, pos)`` or roll the partial growth back and re-raise
        :class:`PoolExhausted`.  The aggregate prechecks in verify_step /
        prefill_chunk / decode_step make mid-loop exhaustion unreachable
        today, but the rollback keeps ensure-then-mutate atomic even if the
        precheck and the pager's accounting ever diverge — a failed quantum
        must leak nothing (allocator invariants are regression-tested).
        Returns True when any table changed (caller refreshes the device
        tables)."""
        grown: List[Tuple[int, int]] = []   # (slot, n_alloc before growth)
        changed = False
        try:
            for s, pos in targets:
                lo = int(self.pager.n_alloc[s])
                if self.pager.ensure(s, pos):
                    grown.append((s, lo))
                    changed = True
        except PoolExhausted:
            for s, lo in grown:
                hi = int(self.pager.n_alloc[s])
                self.pager.allocator.free(self.pager.table[s, lo:hi].tolist())
                self.pager.table[s, lo:hi] = -1
                self.pager.n_alloc[s] = lo
            raise
        return changed

    # ------------------------------------------------------------------ #
    def prefill(self, slots: Sequence[int], prompts: np.ndarray,
                prompt_lens: Optional[Sequence[int]] = None,
                ) -> List[SlotEvent]:
        prompts = np.atleast_2d(np.asarray(prompts, np.int32))
        k, width = prompts.shape
        assert len(slots) == k
        lens = np.full(k, width, np.int32) if prompt_lens is None \
            else np.asarray(prompt_lens, np.int32)
        assert lens.shape == (k,) and np.all(lens >= 1) \
            and np.all(lens <= width), (lens, prompts.shape)
        with obs.span("repro.backend.prefill", rows=k, width=width):
            if self._paged_exec:
                # atomic: on exhaustion nothing mutates and the scheduler can
                # retry the wave after preempting.  Blocks cover each slot's
                # TRUE length — pads are masked and never become cache keys.
                with obs.span("repro.backend.pager"):
                    self.pager.realloc_wave(slots, lens)
            # one program per request: the wave computes only the rows it
            # holds, and each width keeps one prefill and one scatter shape
            with obs.span("repro.backend.dispatch"):
                lasts = [self._prefill_row(s, prompts[i:i + 1], lens[i:i + 1])
                         for i, s in enumerate(slots)]
                if self._paged_exec:
                    for s, n in zip(slots, lens):
                        self._pos[s] = int(n)
                        self._active[s] = True
            with obs.span("repro.backend.fetch"):
                last = np.concatenate(jax.device_get(lasts)).astype(np.float32)
            return [SlotEvent(slot=s, logits=last[i])
                    for i, s in enumerate(slots)]

    def _prefill_row(self, slot: int, prompt: np.ndarray,
                     lens: np.ndarray) -> jax.Array:
        """Prefill one ``[1, S]`` prompt, scatter its caches into ``slot``'s
        storage, and return its last-position logits ``[1, V]``, still on
        the device (the whole ``[1, S, V]`` logits are dropped)."""
        idx = jnp.asarray([slot], jnp.int32)
        if self._paged_exec:
            # dense scratch caches sized by the bucketed prompt length (not
            # max_len): transient prefill workspace stays proportional to
            # the prompt, the pool holds the persistent state
            length = prompt.shape[1]
            tables = (jnp.asarray(self.pager.table[[slot]]),)
        else:
            length, tables = self.max_len, ()
        fresh = T.init_caches(self.cfg, 1, length, self.cache_dtype)
        with use_mesh(self.mesh):
            logits, new_caches, _ = self._prefill_fn(
                self.params, jnp.asarray(prompt), caches=fresh,
                prompt_lens=jnp.asarray(lens))
            self.caches = self._scatter_fn(self.caches, new_caches, idx,
                                           *tables)
        return logits[:, -1]

    def decode_step(self, feeds: Dict[int, int]) -> List[SlotEvent]:
        if not feeds:
            return []
        with obs.span("repro.backend.decode_step", rows=len(feeds)):
            tokens = np.zeros(self.n_slots, np.int32)
            for s, t in feeds.items():
                tokens[s] = t
            if self._paged_exec:
                live = [s for s in sorted(feeds) if self._active[s]]
                with obs.span("repro.backend.pager"):
                    need = sum(self.pager.blocks_needed(s, int(self._pos[s]))
                               for s in live)
                    if need > self.pager.free_blocks:   # raise BEFORE
                        raise PoolExhausted(            # any mutation
                            needed=need, free=self.pager.free_blocks)
                    if self._grow_atomic([(s, int(self._pos[s]))
                                          for s in live]):
                        self._push_tables()
                mask = np.zeros(self.n_slots, bool)
                mask[live] = True
                with obs.span("repro.backend.dispatch"), use_mesh(self.mesh):
                    logits, self.caches = self._decode_fn(
                        self.params, jnp.asarray(tokens), self.caches,
                        jnp.asarray(mask))
                for s in live:
                    self._pos[s] += 1
            else:
                with obs.span("repro.backend.dispatch"), use_mesh(self.mesh):
                    logits, self.caches = self._decode_fn(
                        self.params, jnp.asarray(tokens), self.caches)
            with obs.span("repro.backend.fetch"):
                logits = np.asarray(logits, np.float32)
            return [SlotEvent(slot=s, logits=logits[s]) for s in sorted(feeds)]

    def free_slot(self, slot: int) -> None:
        # contiguous storage is fully overwritten on the next prefill; the
        # paged pool returns the slot's blocks to the free list immediately
        # (prefix-indexed blocks park in the cached-free LRU instead)
        self._active[slot] = False
        self._stream_tokens.pop(slot, None)
        if self.pager is not None:
            self.pager.release(slot)
