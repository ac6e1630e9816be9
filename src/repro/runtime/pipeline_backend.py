"""PipelineBackend: the EdgeShard stage pipeline (planner-chosen, possibly
uneven stages; no-bubbles tick decode) behind the runtime backend protocol.

A *slot* is one micro-batch of the tick protocol — the natural admission
granularity, because each micro-batch owns its cache positions inside the
stage-stacked KV layout (``caches[stage, layer, M, ...]``).  With
``lanes=1`` (the scheduler's configuration) a slot serves exactly one
request stream.

Prompt processing is teacher-forced through the same tick path the paper
uses for generation: each of the slot's turns feeds the next prompt token;
outputs before the last prompt token are discarded.  Slots with no active
request tick with ``feed_valid=False`` so garbage activations ride the ring
without touching KV caches — which also makes slot *recycling* safe: a
freed slot's caches are reset on admission and nothing in flight can write
to them afterwards.

The quantum is one tick.  Each ``decode_step`` feeds micro-batch
``tick % M`` and completes (at most) the micro-batch fed ``n_stages - 1``
ticks ago, whose last-stage logits rode the ring back to stage 0 — so
events carry ``logits`` and the scheduler samples on the host (greedy *and*
temperature>0 both work; the paper's greedy last-stage sampling is the
host's default policy, not a backend constraint).

Speculative decoding (``verify_step``/``accept``) teacher-forces each
slot's draft tokens through the same tick protocol, one token per turn,
and returns the per-position logits stacked ``[n, V]``; rejected-suffix KV
is invalidated by rewriting the slot's ``key_pos`` rows across every
stage's pool (ring slot == absolute position under the paged spec gate).
Unlike the tensor backend there is no multi-token kernel win here — the
payoff is protocol compatibility: a spec-decoding scheduler can drive
tensor and pipeline deployments through one code path.

``cache_layout="paged"`` swaps each stage's dense per-micro-batch KV for a
block pool over the stage's own layer range (``models/kvcache.py``), with
one host-side allocator (:class:`~repro.runtime.base.SlotPager`) governing
the logical block id space across all stages.  Blocks are allocated
*lazily*, one table growth per tick as the teacher-forced/decode position
crosses a block boundary; when the pool cannot cover the next tick the
backend raises :class:`~repro.runtime.base.PoolExhausted` before mutating
anything, and the scheduler preempts.  Paged slots require ``lanes == 1``.

``impl="pallas"`` runs the Pallas attention kernels inside the tick's layer
scan; on the paged layout each stage's pool is read through the micro-
batch's block-table row *inside* the paged decode kernel (shared-position
semantics, one lane) instead of being gathered per tick.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import pipeline as PL
from repro.models import kvcache as KV
from repro.models.attention import effective_decode_impl
from repro.models.config import ModelConfig
from repro.runtime.base import (BackendInfo, InferenceBackend, PoolExhausted,
                                SlotEvent, SlotPager)
from repro.runtime.prefix_cache import PrefixCache

PyTree = Any


class PipelineBackend(InferenceBackend):
    """No-bubbles stage-pipeline decode with micro-batch-granular slots."""

    def __init__(self, cfg: ModelConfig, params: PyTree, spec: PL.PipelineSpec,
                 mesh, *, n_slots: Optional[int] = None, lanes: int = 1,
                 max_len: int = 256, cache_dtype=jnp.float32,
                 stage_axis: str = "model",
                 batch_axes: Tuple[str, ...] = ("data",), impl: str = "xla",
                 cache_layout: str = "contiguous",
                 block_size: int = KV.DEFAULT_BLOCK_SIZE,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = False):
        assert cache_layout in ("contiguous", "paged"), cache_layout
        m = n_slots or spec.n_stages
        assert m >= spec.n_stages, \
            f"need >= {spec.n_stages} micro-batch slots for no bubbles"
        self.cfg = cfg
        self.spec = spec
        self.mesh = mesh
        self.lanes = lanes
        self.max_len = max_len
        self.cache_layout = cache_layout
        self.block_size = block_size
        self._m = m

        nbs = KV.max_ctx_blocks(cfg, max_len, block_size)
        self._paged_exec = cache_layout == "paged" and nbs > 0
        self.num_blocks = 0
        self.pager: Optional[SlotPager] = None
        if cache_layout == "paged":
            assert lanes == 1, "paged pipeline caches require lanes == 1"
            self.num_blocks = num_blocks if num_blocks is not None \
                else m * nbs
            self.pager = SlotPager(m, self.num_blocks, block_size, nbs)
        # Prefix sharing rides the paged pool; the model gate mirrors the
        # tensor backend (all-attention, no effective window at max_len).
        self._prefix_on = bool(prefix_cache) and self._paged_exec \
            and KV.prefix_sharing_supported(cfg, max_len)
        self.prefix: Optional[PrefixCache] = None
        if self._prefix_on:
            self.prefix = PrefixCache(self.pager.allocator, block_size)
        self._prefix_hits = 0
        self._prefix_hit_tokens = 0

        # every stage's layers and caches go straight to its own devices
        self.stage_params, self.mask = PL.stack_stage_params(
            cfg, params, spec, mesh=mesh, stage_axis=stage_axis)
        init_state = functools.partial(
            PL.init_pipeline_decode_state, cfg, spec, m, lanes, max_len,
            cache_dtype,
            cache_layout="paged" if self._paged_exec else "contiguous",
            num_blocks=self.num_blocks, block_size=block_size)
        state_sh = PL.decode_state_shardings(
            cfg, jax.eval_shape(init_state), mesh, self._paged_exec,
            stage_axis, batch_axes)
        self.state = jax.jit(init_state, out_shardings=state_sh)()
        # pristine per-slot cache slices for admission-time resets.  Paged
        # attention entries hold no per-slot pool state — only key_pos/pos
        # rows are reset (their blocks return to the allocator host-side).
        if not self._paged_exec:
            self._fresh_slot = jax.tree.map(lambda x: x[:, :, 0],
                                            self.state.caches)

        # the program's name is fixed (``jit_tick`` in the device trace)
        if self._paged_exec:
            def tick(stage_params, mask, state, feed, feed_valid, btab):
                return PL.pipeline_decode_tick(
                    cfg, stage_params, mask, state, feed, spec, mesh,
                    stage_axis=stage_axis, batch_axes=batch_axes, impl=impl,
                    feed_valid=feed_valid, block_tables=btab)
        else:
            def tick(stage_params, mask, state, feed, feed_valid):
                return PL.pipeline_decode_tick(
                    cfg, stage_params, mask, state, feed, spec, mesh,
                    stage_axis=stage_axis, batch_axes=batch_axes, impl=impl,
                    feed_valid=feed_valid)

        self._tick_fn = jax.jit(tick)

        if self._paged_exec:
            def _reset(state: PL.PipelineDecodeState, slot,
                       start) -> PL.PipelineDecodeState:
                # ``start`` > 0 = streamed admission with an adopted shared
                # prefix: ring slot == absolute position here (prefix gating
                # rules out windows), so positions below ``start`` are marked
                # live and decode resumes at ``start``.
                caches = {}
                for key, entry in state.caches.items():
                    if KV.is_paged_attn_cache(entry):
                        c = entry["key_pos"].shape[-1]
                        row = jnp.arange(c, dtype=jnp.int32)
                        row = jnp.where(row < start, row, -1)
                        e = dict(entry)
                        e["key_pos"] = entry["key_pos"].at[:, :, slot].set(row)
                        e["pos"] = entry["pos"].at[:, :, slot].set(start)
                        caches[key] = e
                    else:
                        caches[key] = jax.tree.map(
                            lambda full: full.at[:, :, slot].set(
                                jnp.zeros_like(full[:, :, 0])), entry)
                return PL.PipelineDecodeState(
                    caches=caches, buf=state.buf, buf_mb=state.buf_mb,
                    buf_valid=state.buf_valid,
                    logits_out=state.logits_out.at[slot].set(0.),
                    token_ready=state.token_ready.at[slot].set(False),
                    tick=state.tick)
        else:
            def _reset(state: PL.PipelineDecodeState,
                       slot) -> PL.PipelineDecodeState:
                caches = jax.tree.map(
                    lambda full, fresh: full.at[:, :, slot].set(fresh),
                    state.caches, self._fresh_slot)
                return PL.PipelineDecodeState(
                    caches=caches, buf=state.buf, buf_mb=state.buf_mb,
                    buf_valid=state.buf_valid,
                    logits_out=state.logits_out.at[slot].set(0.),
                    token_ready=state.token_ready.at[slot].set(False),
                    tick=state.tick)

        self._reset_fn = jax.jit(_reset, donate_argnums=(0,))

        def _kill(state: PL.PipelineDecodeState,
                  slot) -> PL.PipelineDecodeState:
            # invalidate any in-flight activation of this micro-batch so a
            # preempted slot's remaining stage passes write nothing (their
            # validity flag gates cache/pool writes stage by stage)
            return PL.PipelineDecodeState(
                caches=state.caches, buf=state.buf, buf_mb=state.buf_mb,
                buf_valid=state.buf_valid & (state.buf_mb != slot),
                logits_out=state.logits_out, token_ready=state.token_ready,
                tick=state.tick)

        self._kill_fn = jax.jit(_kill, donate_argnums=(0,))

        def _rollback(state: PL.PipelineDecodeState, slot,
                      new_pos) -> PL.PipelineDecodeState:
            # spec-decode rejection: drop the slot's KV for every position
            # >= new_pos across all stages/layers.  Paged + prefix-sharing
            # gating guarantees ring slot == absolute position, so the
            # key_pos *values* are the positions themselves.
            caches = {}
            for key, entry in state.caches.items():
                if KV.is_paged_attn_cache(entry):
                    kp = entry["key_pos"][:, :, slot]       # [ns, l_max, C]
                    kp = jnp.where(kp >= new_pos, -1, kp)
                    e = dict(entry)
                    e["key_pos"] = entry["key_pos"].at[:, :, slot].set(kp)
                    e["pos"] = entry["pos"].at[:, :, slot].set(new_pos)
                    caches[key] = e
                else:
                    caches[key] = entry
            return PL.PipelineDecodeState(
                caches=caches, buf=state.buf, buf_mb=state.buf_mb,
                buf_valid=state.buf_valid, logits_out=state.logits_out,
                token_ready=state.token_ready, tick=state.tick)

        self._rollback_fn = jax.jit(_rollback, donate_argnums=(0,))

        self._tick = 0
        self._prompts: Dict[int, np.ndarray] = {}       # slot -> [plen, lanes]
        self._rounds: Dict[int, int] = {}               # feeds so far
        self._gen_ready: Dict[int, int] = {}            # generated tokens seen
        # feed tick -> (slot, round, occupancy epoch): the epoch guard drops
        # completions of a preempted occupancy that were still in the ring
        # when the slot was freed and re-admitted
        self._inflight: Dict[int, Tuple[int, int, int]] = {}
        # feed tick -> (slot, draft index, epoch) for in-flight verify feeds
        self._vflight: Dict[int, Tuple[int, int, int]] = {}
        self._epoch: Dict[int, int] = {}
        # spec decode rides the paged pool with absolute ring positions —
        # same gate as prefix sharing, plus request-granular slots
        self._spec_ok = self._paged_exec and lanes == 1 \
            and KV.prefix_sharing_supported(cfg, max_len)
        self._pending: Dict[int, Tuple[int, int, str]] = {}
        self._base: Dict[int, int] = {}        # slot -> adopted prefix length
        self._stream_done: Dict[int, bool] = {}  # all chunks fed?
        self._full_tokens: Dict[int, np.ndarray] = {}  # for registration
        self._bt_dev = jnp.asarray(self.pager.table) if self._paged_exec \
            else None
        self._bt_dirty = False

        cache_bytes = sum(l.nbytes for l in jax.tree.leaves(self.state.caches))
        self._info = BackendInfo(
            n_slots=m, max_len=max_len,
            cache_bytes_per_slot=cache_bytes // m,
            param_bytes=sum(l.nbytes
                            for l in jax.tree.leaves(self.stage_params)),
            samples_in_backend=False,
            attn_impl=effective_decode_impl(impl, cfg)
            if self._paged_exec else impl,
            spec_decode=self._spec_ok,
            cache_layout=cache_layout,
            block_size=block_size if cache_layout == "paged" else 0,
            total_blocks=self.num_blocks,
            free_blocks=self.num_blocks,
            bytes_per_block=KV.block_pool_bytes_per_block(cfg, cache_dtype)
            if cache_layout == "paged" else 0,
            max_ctx_blocks=nbs if cache_layout == "paged" else 0,
            prefix_caching=self._prefix_on,
            # teacher-forcing feeds one token per tick, so chunked admission
            # is just a staged feed queue — supported on every layout
            supports_extend=lanes == 1)

    @property
    def info(self) -> BackendInfo:
        return self._live_info()

    # ------------------------------------------------------------------ #
    def prefill(self, slots: Sequence[int], prompts: np.ndarray,
                prompt_lens: Optional[Sequence[int]] = None,
                ) -> List[SlotEvent]:
        """Admit prompts; tokens stream through subsequent ticks, so the
        first sampled token arrives from a later ``decode_step``.

        ``prompt_lens[i]`` marks ``prompts[i]`` as left-padded to a bucket
        with true length ``prompt_lens[i]``.  Teacher-forcing is inherently
        shape-free (one token per tick), so pad neutrality here is exact by
        construction: the pads are *stripped* and only the real tokens are
        fed, starting at position 0 — which also saves the pad ticks."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim == 2:                       # [k, S] -> lanes dim
            assert self.lanes == 1
            prompts = prompts[:, :, None]
        assert prompts.shape[0] == len(slots)
        assert prompts.shape[2] == self.lanes
        if prompt_lens is None:
            lens = [prompts.shape[1]] * len(slots)
        else:
            lens = [int(n) for n in prompt_lens]
            assert len(lens) == len(slots)
            assert all(1 <= n <= prompts.shape[1] for n in lens), \
                (lens, prompts.shape)
        with self.mesh:
            for i, slot in enumerate(slots):
                if self.pager is not None:
                    if self.pager.release(slot):  # blocks grow lazily per tick
                        self._bt_dirty = True
                self._reset_slot(slot, 0)
                self._prompts[slot] = prompts[i, prompts.shape[1] - lens[i]:]
                self._rounds[slot] = 0
                self._gen_ready[slot] = 0
                self._epoch[slot] = self._epoch.get(slot, 0) + 1
                self._base[slot] = 0
                self._stream_done[slot] = True
                self._full_tokens.pop(slot, None)
        return []

    def _reset_slot(self, slot: int, start: int) -> None:
        if self._paged_exec:
            self.state = self._reset_fn(self.state, jnp.asarray(slot),
                                        jnp.int32(start))
        else:
            assert start == 0
            self.state = self._reset_fn(self.state, jnp.asarray(slot))

    # --------------------------- streamed admission ------------------- #
    def cached_prefix_len(self, prompt: np.ndarray) -> int:
        if not self._prefix_on:
            return 0
        p = np.asarray(prompt, np.int32).ravel()
        cap = ((len(p) - 1) // self.block_size) * self.block_size
        return self.prefix.matched_tokens(p[:cap])

    def start_stream(self, slot: int, prompt: np.ndarray) -> int:
        assert self.lanes == 1, "streamed admission requires lanes == 1"
        p = np.asarray(prompt, np.int32).ravel()
        start = 0
        with self.mesh:
            if self.pager is not None and self.pager.release(slot):
                self._bt_dirty = True
            if self._prefix_on:
                # never adopt the whole prompt: >= 1 suffix token must run
                # so the first sampled token exists
                cap = ((len(p) - 1) // self.block_size) * self.block_size
                blocks = self.prefix.lookup(p[:cap])
                if blocks:
                    start = len(blocks) * self.block_size
                    self.pager.adopt(slot, blocks)
                    self._bt_dirty = True
                    self._prefix_hits += 1
                    self._prefix_hit_tokens += start
                self._full_tokens[slot] = p
            self._reset_slot(slot, start)
            self._prompts[slot] = np.zeros((0, self.lanes), np.int32)
            self._rounds[slot] = 0
            self._gen_ready[slot] = 0
            self._epoch[slot] = self._epoch.get(slot, 0) + 1
            self._base[slot] = start
            self._stream_done[slot] = False
        return start

    def prefill_chunk(self, slots: Sequence[int], chunks: np.ndarray,
                      chunk_lens: Sequence[int], starts: Sequence[int],
                      last: Sequence[bool]) -> List[SlotEvent]:
        """Queue suffix tokens for the tick loop's teacher-forcing; the
        chunk is 'prefilled' by subsequent ``decode_step`` ticks, one token
        per turn, so no event is emitted here (the first sampled token rides
        the ring after the final prompt token of the *last* chunk)."""
        chunks = np.asarray(chunks, np.int32)
        if chunks.ndim == 1:
            chunks = chunks[None]
        for i, slot in enumerate(slots):
            assert slot in self._prompts \
                and self._stream_done.get(slot) is False, slot
            n = int(chunk_lens[i])
            toks = chunks[i, chunks.shape[1] - n:]       # strip left pads
            fed = self._base.get(slot, 0) + len(self._prompts[slot])
            assert int(starts[i]) == fed, (starts[i], fed)
            self._prompts[slot] = np.concatenate(
                [self._prompts[slot], toks[:, None]])
            if last[i]:
                self._stream_done[slot] = True
        return []

    def _feed_for(self, slot: int, feeds: Dict[int, int],
                  ) -> Optional[np.ndarray]:
        """Next input tokens [lanes] for this slot's turn, or None to idle."""
        if slot not in self._prompts:
            return None                             # no active request
        r = self._rounds[slot]
        prompt = self._prompts[slot]
        if r < len(prompt):
            return prompt[r]                        # teacher-forced prefill
        # generation: consume the scheduler's sampled token exactly once
        if (r - len(prompt)) < self._gen_ready[slot] and slot in feeds:
            return np.full(self.lanes, feeds[slot], np.int32)
        return None                                 # stalled (no fresh token)

    def decode_step(self, feeds: Dict[int, int]) -> List[SlotEvent]:
        with obs.span("repro.backend.tick", tick=self._tick):
            slot = self._tick % self._m
            feed = self._feed_for(slot, feeds)
            valid = feed is not None
            with obs.span("repro.backend.pager"):
                if valid and self._paged_exec:
                    # this tick writes position base+rounds[slot] (base =
                    # adopted shared-prefix length); grow the slot's block
                    # table first, raising BEFORE any bookkeeping so the
                    # scheduler can preempt a victim and retry the very same
                    # tick
                    pos = self._base.get(slot, 0) + self._rounds[slot]
                    need = self.pager.blocks_needed(slot, pos)
                    if need > self.pager.free_blocks:
                        raise PoolExhausted(needed=need,
                                            free=self.pager.free_blocks)
                    if self.pager.ensure(slot, pos):
                        self._bt_dirty = True
                if self._paged_exec and self._bt_dirty:
                    self._bt_dev = jnp.asarray(self.pager.table)
                    self._bt_dirty = False
            if valid:
                self._inflight[self._tick] = (slot, self._rounds[slot],
                                              self._epoch.get(slot, 0))
                self._rounds[slot] += 1
            else:
                feed = np.zeros(self.lanes, np.int32)
            with obs.span("repro.backend.dispatch"), self.mesh:
                if self._paged_exec:
                    self.state = self._tick_fn(self.stage_params, self.mask,
                                               self.state, jnp.asarray(feed),
                                               jnp.asarray(valid),
                                               self._bt_dev)
                else:
                    self.state = self._tick_fn(self.stage_params, self.mask,
                                               self.state, jnp.asarray(feed),
                                               feed_valid=jnp.asarray(valid))
            events: List[SlotEvent] = []
            done = self._inflight.pop(self._tick - (self.spec.n_stages - 1),
                                      None)
            self._tick += 1
            if done is None:
                return events
            dslot, r, epoch = done
            if dslot in self._prompts and epoch == self._epoch.get(dslot, 0) \
                    and self._stream_done.get(dslot, True) \
                    and r >= len(self._prompts[dslot]) - 1:
                with obs.span("repro.backend.fetch"):       # [lanes, V]
                    arr = np.asarray(self.state.logits_out[dslot])
                self._gen_ready[dslot] += 1
                self._maybe_register_prefix(dslot)
                events.append(SlotEvent(
                    slot=dslot,
                    logits=arr[0] if self.lanes == 1 else arr))
            return events

    def _maybe_register_prefix(self, slot: int) -> None:
        full = self._full_tokens.pop(slot, None)
        if full is not None and self._prefix_on:
            # the whole prompt's KV is now resident: publish its full
            # blocks (generated tokens never land in them — the first
            # partial block stays private by the // floor)
            nfull = min(len(full) // self.block_size,
                        int(self.pager.n_alloc[slot]))
            if nfull:
                self.prefix.register(
                    full, self.pager.table[slot, :nfull].tolist())

    # --------------------------- speculative decode ------------------- #
    def verify_step(self, feeds: Dict[int, np.ndarray]) -> List[SlotEvent]:
        """Teacher-force each slot's ``[t_last, d_1..d_{n-1}]`` through the
        tick protocol and return per-slot logits ``[n, V]``.

        Draft tokens are fed one per turn exactly like prompt tokens, so a
        verify of n tokens costs n ring turns for that slot — pipeline spec
        decode trades no kernel time but keeps the scheduler's draft/verify
        protocol uniform across backends.  Slots still in their prompt
        phase keep teacher-forcing during these ticks; a prompt that
        completes mid-verify emits a ``[1, V]`` event (its first sampled
        token's logits), which the caller accepts with count=1.

        The caller MUST follow with :meth:`accept` before the next quantum.
        """
        assert self._spec_ok, "spec decode needs paged caches + lanes == 1"
        assert not self._pending, "accept() the previous verify first"
        feeds = {int(s): np.asarray(t, np.int32).ravel()
                 for s, t in feeds.items()}
        for s, toks in feeds.items():
            assert s in self._prompts and len(toks) >= 1, s
            assert self._rounds[s] >= len(self._prompts[s]), \
                f"slot {s} still in prompt phase"
            assert self._base.get(s, 0) + self._rounds[s] + len(toks) \
                <= self.max_len, "verify feed overruns max_len"
        # atomic block growth for every candidate position, before any
        # bookkeeping: a rejected tail's blocks stay allocated (harmless,
        # reused by subsequent decode or released with the slot)
        need = sum(self.pager.blocks_needed(
            s, self._base.get(s, 0) + self._rounds[s] + len(t) - 1)
            for s, t in feeds.items())
        if need > self.pager.free_blocks:
            raise PoolExhausted(needed=need, free=self.pager.free_blocks)
        for s, toks in feeds.items():
            if self.pager.ensure(
                    s, self._base.get(s, 0) + self._rounds[s] + len(toks) - 1):
                self._bt_dirty = True

        r0 = {s: self._rounds[s] for s in feeds}
        fed = {s: 0 for s in feeds}
        collect: Dict[int, List[np.ndarray]] = {s: [] for s in feeds}
        events: List[SlotEvent] = []
        guard = 0
        total = sum(len(t) for t in feeds.values())
        max_ticks = (total + self._m + self.spec.n_stages) * self._m + 8
        # empty feeds (all slots still prefilling) runs exactly one tick,
        # matching decode_step's quantum granularity
        while (any(len(collect[s]) < len(feeds[s]) for s in feeds)
               if feeds else guard == 0):
            guard += 1
            assert guard <= max_ticks, "verify tick loop failed to converge"
            slot = self._tick % self._m
            feed_tok: Optional[np.ndarray] = None
            if slot in feeds and fed[slot] < len(feeds[slot]):
                feed_tok = np.full(self.lanes, feeds[slot][fed[slot]],
                                   np.int32)
                self._vflight[self._tick] = (slot, fed[slot],
                                             self._epoch.get(slot, 0))
                fed[slot] += 1
                self._rounds[slot] += 1
            else:
                # prompt-phase slots keep teacher-forcing on spare turns;
                # a slot short on blocks stalls (no raise mid-verify — it
                # retries once the pool drains)
                p = self._feed_for(slot, {})
                if p is not None and self._rounds[slot] < len(
                        self._prompts.get(slot, ())):
                    pos = self._base.get(slot, 0) + self._rounds[slot]
                    if self.pager.blocks_needed(slot, pos) \
                            <= self.pager.free_blocks:
                        if self.pager.ensure(slot, pos):
                            self._bt_dirty = True
                        feed_tok = p
                        self._inflight[self._tick] = (
                            slot, self._rounds[slot],
                            self._epoch.get(slot, 0))
                        self._rounds[slot] += 1
            valid = feed_tok is not None
            if not valid:
                feed_tok = np.zeros(self.lanes, np.int32)
            if self._bt_dirty:
                self._bt_dev = jnp.asarray(self.pager.table)
                self._bt_dirty = False
            with self.mesh:
                self.state = self._tick_fn(self.stage_params, self.mask,
                                           self.state, jnp.asarray(feed_tok),
                                           jnp.asarray(valid), self._bt_dev)
            done_tick = self._tick - (self.spec.n_stages - 1)
            self._tick += 1
            vdone = self._vflight.pop(done_tick, None)
            if vdone is not None:
                dslot, idx, epoch = vdone
                # verify slots cannot be freed mid-verify (free_slot is a
                # scheduler call, never issued inside this loop)
                assert epoch == self._epoch.get(dslot, 0), dslot
                assert idx == len(collect[dslot]), (idx, dslot)
                collect[dslot].append(
                    np.asarray(self.state.logits_out[dslot][0], np.float32))
                continue
            pdone = self._inflight.pop(done_tick, None)
            if pdone is not None:
                dslot, r, epoch = pdone
                if dslot in self._prompts \
                        and epoch == self._epoch.get(dslot, 0) \
                        and self._stream_done.get(dslot, True) \
                        and r >= len(self._prompts[dslot]) - 1:
                    arr = np.asarray(self.state.logits_out[dslot],
                                     np.float32)          # [lanes, V]
                    self._gen_ready[dslot] += 1
                    self._maybe_register_prefix(dslot)
                    self._pending[dslot] = (self._rounds[dslot], 1, "first")
                    events.append(SlotEvent(slot=dslot, logits=arr[:1]))
        for s in feeds:
            self._pending[s] = (r0[s], len(feeds[s]), "verify")
            events.append(SlotEvent(slot=s,
                                    logits=np.stack(collect[s])))
        return events

    def accept(self, counts: Dict[int, int]) -> None:
        """Commit per-slot accepted counts from the last ``verify_step``:
        roll rejected draft positions out of every stage's pool and rewind
        the feed round so the next quantum resumes at the accept point."""
        counts = {int(s): int(e) for s, e in counts.items()}
        assert set(counts) == set(self._pending), \
            (sorted(counts), sorted(self._pending))
        for s, e in counts.items():
            r0, n, kind = self._pending[s]
            assert 1 <= e <= n, (s, e, n)
            if kind == "first":
                continue                     # prompt completion: nothing fed
            self._rounds[s] = r0 + e
            self._gen_ready[s] += e
            if e < n and s in self._prompts:
                new_pos = self._base.get(s, 0) + r0 + e
                with self.mesh:
                    self.state = self._rollback_fn(
                        self.state, jnp.asarray(s), jnp.int32(new_pos))
        self._pending.clear()

    def free_slot(self, slot: int) -> None:
        self._pending.pop(slot, None)
        self._prompts.pop(slot, None)
        self._rounds.pop(slot, None)
        self._gen_ready.pop(slot, None)
        self._base.pop(slot, None)
        self._stream_done.pop(slot, None)
        self._full_tokens.pop(slot, None)
        self._epoch[slot] = self._epoch.get(slot, 0) + 1
        if self._paged_exec:
            # a preempted slot may still be riding the ring: kill its
            # validity so remaining stage passes cannot scribble on (freed,
            # possibly reallocated) pool blocks.  Contiguous slots need no
            # kill — only preemption frees mid-flight, and only the paged
            # layout preempts; normal finishes have nothing in the ring.
            with self.mesh:
                self.state = self._kill_fn(self.state, jnp.asarray(slot))
            if self.pager.release(slot):
                self._bt_dirty = True
