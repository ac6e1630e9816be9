"""Mixture-of-experts FFN: top-k router + dropless grouped matmul, with an
expert-parallel ``shard_map`` path for the production mesh.

Two execution engines with identical semantics (up to capacity drops):

- ``ragged``  — single-shard dropless dispatch: sort tokens by expert and run
  one :func:`jax.lax.ragged_dot` per weight matrix.  Used on CPU/tests and
  inside each expert-parallel shard.
- ``ep``      — expert parallelism over the ``model`` mesh axis: tokens are
  bucketed per expert with a capacity factor, exchanged with ``all_to_all``,
  processed by the local expert group, and combined on the way back
  (GShard/Switch-style; the all-to-all bytes are what the EdgeShard DP sees
  as intra-stage traffic).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import ParamBuilder
from repro.sharding.rules import current_mesh, current_rules, logical_constraint


def init_moe(pb: ParamBuilder, name: str, cfg: ModelConfig, moe: MoEConfig):
    d, f, e = cfg.d_model, moe.d_expert, moe.num_experts
    sub = pb.scope(name)
    sub.add("router", (d, e), ("embed", None))
    sub.add("w_gate", (e, d, f), ("experts", "embed", None))
    sub.add("w_up", (e, d, f), ("experts", "embed", None))
    sub.add("w_down", (e, f, d), ("experts", None, "embed"))
    if moe.num_shared_experts:
        s = moe.num_shared_experts * f
        sub.add("s_gate", (d, s), ("embed", "ff"))
        sub.add("s_up", (d, s), ("embed", "ff"))
        sub.add("s_down", (s, d), ("ff", "embed"))


def router_topk(router_w: jax.Array, x: jax.Array, moe: MoEConfig,
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (probs [T,k], expert_ids [T,k], aux load-balance loss)."""
    logits = (x @ router_w).astype(jnp.float32)                  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, moe.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss
    e = moe.num_experts
    density = jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * mean_probs)
    return top_p.astype(x.dtype), top_i, aux


def _expert_ffn(w_gate, w_up, w_down, x, group_sizes):
    """Grouped SwiGLU over sorted tokens via ragged_dot. x: [T', d]."""
    g = jax.lax.ragged_dot(x, w_gate, group_sizes)
    u = jax.lax.ragged_dot(x, w_up, group_sizes)
    h = jax.nn.silu(g) * u
    return jax.lax.ragged_dot(h, w_down, group_sizes)


def moe_ragged(params: Dict, moe: MoEConfig, x: jax.Array,
               ) -> Tuple[jax.Array, jax.Array]:
    """Dropless single-shard MoE. x: [T, d] -> ([T, d], aux loss)."""
    t, d = x.shape
    k, e = moe.top_k, moe.num_experts
    probs, ids, aux = router_topk(params["router"], x, moe)
    flat_ids = ids.reshape(-1)                                   # [T*k]
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    xs = x[order // k]                                           # [T*k, d]
    group_sizes = jnp.bincount(flat_ids, length=e).astype(jnp.int32)
    out_sorted = _expert_ffn(params["w_gate"], params["w_up"],
                             params["w_down"], xs, group_sizes)
    out_flat = jnp.zeros((t * k, d), out_sorted.dtype).at[order].set(out_sorted)
    y = jnp.sum(out_flat.reshape(t, k, d) * probs[..., None], axis=1)
    return y.astype(x.dtype), aux


# --------------------------------------------------------------------------- #
# Expert-parallel path
# --------------------------------------------------------------------------- #

def _dispatch_buckets(x, flat_ids, n_experts, cap):
    """Scatter tokens into per-expert capacity buckets.

    Returns (buckets [E, cap, d], slot [T*k] int32, keep [T*k] bool).
    """
    tk = flat_ids.shape[0]
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_experts), side="left")
    pos_in_seg_sorted = jnp.arange(tk) - starts[sorted_ids]
    pos_in_seg = jnp.zeros(tk, jnp.int32).at[order].set(
        pos_in_seg_sorted.astype(jnp.int32))
    keep = pos_in_seg < cap
    slot = jnp.where(keep, pos_in_seg, cap)                      # cap = dropped
    buckets = jnp.zeros((n_experts, cap + 1, x.shape[-1]), x.dtype)
    buckets = buckets.at[flat_ids, slot].set(x, mode="drop")
    return buckets[:, :cap], slot, keep


def _moe_ep_local(x, router_w, w_gate, w_up, w_down, *, moe: MoEConfig,
                  ep: int, cap: int, ep_axis: str):
    """Per-device body under shard_map: tokens local, experts local E/ep."""
    t, d = x.shape
    k, e = moe.top_k, moe.num_experts
    e_loc = e // ep
    probs, ids, aux = router_topk(router_w, x, moe)
    flat_ids = ids.reshape(-1)
    rep_x = jnp.repeat(x, k, axis=0)                             # [T*k, d]
    buckets, slot, keep = _dispatch_buckets(rep_x, flat_ids, e, cap)
    # [E, cap, d] -> [ep, E_loc*cap, d] -> all_to_all -> [ep_src, E_loc, cap, d]
    send = buckets.reshape(ep, e_loc * cap, d)
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0,
                              tiled=False)
    recv = recv.reshape(ep, e_loc, cap, d).transpose(1, 0, 2, 3)
    recv = recv.reshape(e_loc, ep * cap, d)
    g = jnp.einsum("ecd,edf->ecf", recv, w_gate)
    u = jnp.einsum("ecd,edf->ecf", recv, w_up)
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, w_down)                  # [E_loc, ep*cap, d]
    out = out.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
    out = out.reshape(ep, e_loc * cap, d)
    back = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                              tiled=False)
    back = back.reshape(e, cap, d)
    # gather back to token order
    gathered = back[flat_ids, jnp.minimum(slot, cap - 1)]        # [T*k, d]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    y = jnp.sum(gathered.reshape(t, k, d) * probs[..., None], axis=1)
    return y.astype(x.dtype), aux[None]


def moe_ep(params: Dict, moe: MoEConfig, x: jax.Array,
           capacity_factor: Optional[float] = None) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE over the 'model' mesh axis. x: [T, d] (global,
    T divisible by the total device count — :func:`apply_moe` pads)."""
    import math as _math
    mesh = current_mesh()
    assert mesh is not None, "moe_ep requires an installed mesh"
    ep_axis = "model"
    ep = mesh.shape[ep_axis]
    token_axes = tuple(mesh.axis_names)                          # shard T by all
    t_global, d = x.shape
    n_dev = _math.prod(mesh.shape[a] for a in token_axes)
    assert t_global % n_dev == 0
    t_loc = t_global // n_dev
    cf = capacity_factor if capacity_factor is not None else moe.capacity_factor
    cap = max(1, int(-(-t_loc * moe.top_k * cf // moe.num_experts)))
    body = functools.partial(_moe_ep_local, moe=moe, ep=ep, cap=cap,
                             ep_axis=ep_axis)
    in_specs = (P(token_axes, None),                              # x
                P(None, None),                                    # router
                P(ep_axis, None, None),                           # w_gate
                P(ep_axis, None, None),                           # w_up
                P(ep_axis, None, None))                           # w_down
    out_specs = (P(token_axes, None), P(token_axes))
    y, aux = shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(
        x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return y, jnp.mean(aux)


def apply_moe(params: Dict, cfg: ModelConfig, moe: MoEConfig, x: jax.Array,
              ) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN on [B, S, d]; engine picked by mesh context."""
    import math as _math
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    mesh = current_mesh()
    use_ep = (mesh is not None
              and moe.num_experts % mesh.shape["model"] == 0)
    if use_ep:
        n_dev = _math.prod(mesh.shape[a] for a in mesh.axis_names)
        t = flat.shape[0]
        pad = (-t) % n_dev
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad, d), flat.dtype)], axis=0)
        y, aux = moe_ep(params, moe, flat)
        y = y[:t]
    else:
        y, aux = moe_ragged(params, moe, flat)
    y = y.reshape(b, s, d)
    if moe.num_shared_experts:
        g = x @ params["s_gate"]
        u = x @ params["s_up"]
        h = jax.nn.silu(g) * u
        h = logical_constraint(h, "batch", None, "ff")
        y = y + h @ params["s_down"]
    return y, aux
