"""Logical-axis sharding rules (flax-linen style, dependency-free).

Model code names tensor dimensions with *logical* axes ("batch", "heads",
"ff", "experts", ...).  A mesh-specific :class:`AxisRules` maps logical names
to mesh axes; :func:`use_mesh` installs (mesh, rules) in a context so the same
model code runs unsharded on CPU tests and fully sharded in the dry-run.

Default production mapping (single-pod (data, model) / multi-pod
(pod, data, model)):

    batch    -> (pod?, data)       activations & KV cache
    heads    -> model              attention TP (Megatron)
    kv_heads -> model
    ff       -> model              MLP TP
    experts  -> model              expert parallelism
    vocab    -> model              embedding / logits TP
    stage    -> model              EdgeShard pipeline mode
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class AxisRules:
    rules: Tuple[Tuple[str, MeshAxes], ...]

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        table = dict(self.rules)
        out = []
        for name in logical_axes:
            if name is None:
                out.append(None)
            else:
                out.append(table.get(name))
        return P(*out)


def default_rules(multi_pod: bool = False) -> AxisRules:
    batch = ("pod", "data") if multi_pod else ("data",)
    return AxisRules((
        ("batch", batch),
        ("seq", None),
        ("seq_kv", None),
        ("embed", None),
        ("heads", "model"),
        ("kv_heads", "model"),
        ("qkv", "model"),
        ("ff", "model"),
        ("experts", "model"),
        ("rnn", "model"),
        ("vocab", "model"),
        ("stage", "model"),
        ("layers", None),
    ))


def long_context_rules(multi_pod: bool = False) -> AxisRules:
    """Decode with batch << data-axis size: shard the KV cache sequence dim
    over the data axis instead of the (unfillable) batch dim."""
    base = dict(default_rules(multi_pod).rules)
    base["batch"] = None
    base["seq_kv"] = ("pod", "data") if multi_pod else ("data",)
    return AxisRules(tuple(base.items()))


def decode_seq_model_rules(multi_pod: bool = False) -> AxisRules:
    """§Perf variant: shard the decode KV cache over the *model* axis on the
    sequence dim instead of kv_heads.  Fixes the kv_heads-indivisible case
    (e.g. qwen1.5-32b kv=40 on a 16-way axis) where head sharding degenerates
    to replication + all-gathers of the whole cache."""
    base = dict(default_rules(multi_pod).rules)
    base["seq_kv"] = ("model",)
    base["kv_heads"] = None
    return AxisRules(tuple(base.items()))


def fsdp_rules(multi_pod: bool = False) -> AxisRules:
    """§Perf variant (train): additionally shard weights/optimizer over the
    data axis on their d_model ("embed") dimension — ZeRO-3-style.  Applied
    to *parameter in_shardings only*; activation constraints keep using the
    default rules, so XLA inserts the gather/reduce-scatter pattern."""
    base = dict(default_rules(multi_pod).rules)
    base["embed"] = ("data",)
    return AxisRules(tuple(base.items()))


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes.  The model code places arrays with
    sharding constraints and lets XLA propagate the rest; jax's default
    Explicit axes refuse such constraints."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


_ctx = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


def current_rules() -> Optional[AxisRules]:
    return getattr(_ctx, "rules", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[AxisRules] = None):
    """Install a (mesh, rules) pair; ``None`` mesh = unsharded (CPU tests)."""
    prev = (current_mesh(), current_rules())
    _ctx.mesh = mesh
    _ctx.rules = rules if rules is not None else (
        default_rules("pod" in mesh.axis_names) if mesh is not None else None)
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def logical_sharding(logical_axes: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    mesh, rules = current_mesh(), current_rules()
    if mesh is None or rules is None:
        return None
    return NamedSharding(mesh, rules.spec(logical_axes))


def logical_constraint(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint if a mesh is installed, identity otherwise."""
    sh = logical_sharding(logical_axes)
    if sh is None:
        return x
    return jax.lax.with_sharding_constraint(x, sh)


def _is_axes_leaf(x) -> bool:
    """A logical-axes annotation: tuple of axis names / None (not a pytree
    node like a NamedTuple of subtrees)."""
    return (isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))


def param_sharding_tree(param_axes, mesh: Optional[Mesh] = None,
                        rules: Optional[AxisRules] = None):
    """Map a tree of logical-axis tuples to NamedShardings (or None)."""
    mesh = mesh if mesh is not None else current_mesh()
    rules = rules if rules is not None else current_rules()
    if mesh is None:
        return jax.tree.map(lambda _: None, param_axes,
                            is_leaf=_is_axes_leaf)
    rules = rules or default_rules("pod" in mesh.axis_names)

    def one(axes):
        return NamedSharding(mesh, rules.spec(axes))

    return jax.tree.map(one, param_axes, is_leaf=_is_axes_leaf)


def shape_aware_sharding_tree(arg_tree, axes_tree, mesh: Mesh,
                              rules: AxisRules):
    """Like :func:`param_sharding_tree` but drops mesh axes from dimensions
    they do not divide (e.g. vocab 49155 on a 16-way model axis) — pjit
    ``in_shardings`` require exact divisibility."""
    import numpy as _np

    arg_leaves, treedef = jax.tree.flatten(arg_tree)
    axes_leaves = jax.tree.leaves(axes_tree, is_leaf=_is_axes_leaf)
    assert len(arg_leaves) == len(axes_leaves), \
        (len(arg_leaves), len(axes_leaves))

    def axis_size(a) -> int:
        names = (a,) if isinstance(a, str) else tuple(a)
        return int(_np.prod([mesh.shape[n] for n in names]))

    out = []
    for leaf, axes in zip(arg_leaves, axes_leaves):
        spec = list(rules.spec(axes))
        spec += [None] * (len(leaf.shape) - len(spec))
        fixed = []
        for dim, a in zip(leaf.shape, spec):
            if a is not None and dim % axis_size(a) != 0:
                a = None
            fixed.append(a)
        out.append(NamedSharding(mesh, P(*fixed)))
    return jax.tree.unflatten(treedef, out)
