"""Seeded random weights, made on the device in the type they are served in.

The benchmark makes the weights (the program only receives them), and the
reference makes the very same values again, one layer at a time, from the
same seed: every leaf of layer ``l`` comes from its own key
``fold_in(fold_in(run_key, leaf_id), l)``, so the stacked tree the program
is given and the layer the reference draws hold identical numbers.

The tree has the program's layout for a dense attention model with one
repeating block (embedding, final norm, and a ``stack`` of per-layer
leaves with a leading layer axis); :func:`check_layout` compares it with
the program's own parameter shapes before anything runs.  Norm scales and
biases are drawn away from their usual ones and zeros, so that the
reference comparison exercises them.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Shape = Tuple[int, ...]


def run_key(seed: int) -> jax.Array:
    """A JAX key for any whole-number seed (also those past 32 bits)."""
    s = int(seed) & (2 ** 64 - 1)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(s >> 32))


def _leaf_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def layer_shapes(c: dict) -> Dict[str, Dict[str, Tuple[Shape, str]]]:
    """Per-layer leaves: group -> name -> (shape, init)."""
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    f = c["intermediate_size"]
    layer_norm = c["norm_type"] == "layer_norm"

    def norm():
        out = {"scale": ((d,), "scale")}
        if layer_norm:
            out["bias"] = ((d,), "bias")
        return out

    mixer = {"wq": ((d, q), "matrix"), "wk": ((d, kv), "matrix"),
             "wv": ((d, kv), "matrix"), "wo": ((q, d), "matrix")}
    if c["attention_bias"]:
        mixer.update(bq=((q,), "bias"), bk=((kv,), "bias"),
                     bv=((kv,), "bias"))
    if c["qk_norm"]:
        mixer.update(q_norm=((hd,), "scale"), k_norm=((hd,), "scale"))
    ffn = {"w_up": ((d, f), "matrix"), "w_down": ((f, d), "matrix")}
    if c["mlp_gated"]:
        ffn["w_gate"] = ((d, f), "matrix")
    return {"norm1": norm(), "mixer": mixer, "norm2": norm(), "ffn": ffn}


def _draw(key: jax.Array, shape: Shape, init: str, dtype) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "matrix":
        x = z / np.sqrt(shape[0])
    elif init == "scale":
        x = 1.0 + 0.1 * z
    elif init == "bias":
        x = 0.1 * z
    elif init == "embedding":
        x = z / np.sqrt(shape[1])
    else:
        raise ValueError(init)
    return x.astype(dtype)


def layer(c: dict, key: jax.Array, index, dtype=jnp.bfloat16) -> Dict:
    """Layer ``index``'s leaves (``index`` may be traced)."""
    out: Dict = {}
    for group, leaves in layer_shapes(c).items():
        out[group] = {
            name: _draw(jax.random.fold_in(
                jax.random.fold_in(key, _leaf_id(f"{group}.{name}")), index),
                shape, init, dtype)
            for name, (shape, init) in leaves.items()}
    return out


def outer(c: dict, key: jax.Array, dtype=jnp.bfloat16) -> Dict:
    """The leaves outside the layers: embedding (tied head) and final norm."""
    d = c["hidden_size"]
    emb = _draw(jax.random.fold_in(key, _leaf_id("embedding")),
                (c["vocab_size"], d), "embedding", dtype)
    fn = {"scale": _draw(jax.random.fold_in(key, _leaf_id("final_norm.scale")),
                         (d,), "scale", dtype)}
    if c["norm_type"] == "layer_norm":
        fn["bias"] = _draw(jax.random.fold_in(key, _leaf_id("final_norm.bias")),
                           (d,), "bias", dtype)
    return {"embedding": emb, "final_norm": fn}


def model(c: dict, key: jax.Array) -> Dict:
    """The whole tree in the program's layout, in the served type."""
    dtype = jnp.dtype(c["torch_dtype"])
    tree = outer(c, key, dtype)
    tree["stack"] = {"p0": jax.vmap(lambda i: layer(c, key, i, dtype))(
        jnp.arange(c["num_hidden_layers"]))}
    return tree


def make(c: dict, seed: int, shardings=None) -> Dict:
    """Build every weight on the device in one jitted call (each device
    draws only its own shard: the random bits are partitionable)."""
    jax.config.update("jax_threefry_partitionable", True)
    fn = jax.jit(lambda k: model(c, k), out_shardings=shardings)
    return fn(run_key(seed))


def check_layout(mine, program) -> None:
    """Refuse to run when the tree does not have the program's structure,
    shapes and dtypes."""
    a = jax.tree.map(lambda x: (tuple(x.shape), jnp.dtype(x.dtype).name), mine)
    b = jax.tree.map(lambda x: (tuple(x.shape), jnp.dtype(x.dtype).name),
                     program)
    if a != b:
        raise ValueError(f"benchmark weights {a} differ from the program's "
                         f"parameter layout {b}")
