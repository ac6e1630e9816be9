"""The plain reference forward pass that decides ``correct``.

Dense decoder-only attention models as the configuration file states
them: grouped-query attention with rotary positions (the two halves of
each head rotated against each other), optional RMS norm of queries and
keys per head, RMSNorm or LayerNorm, a gated SiLU or tanh-GELU MLP, biases
on the query, key and value projections, and an output head tied to the
embedding.  It is written from the published architectures in plain
``jax.numpy`` and imports nothing of the program; its weights are drawn
again from the run's seed (``weights.py``), never taken from the program.

It runs in float32 at the highest matmul precision, one layer at a time
over all sampled sequences (each layer's weights are drawn, used and
dropped), so that it fits on one chip beside nothing else.  Sequences are
padded at the end to one common length; causal attention keeps the pad
out of every real position.

``precision="int8"`` or ``"fp8"`` is the control: every weight matmul
takes its weights rounded to int8 (or float8 e4m3) with one scale per
output channel and its input rounded likewise per row, the step below the
configuration's bfloat16 that a later change might be tempted to take.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W

PRECISIONS = ("float32", "int8", "fp8")


def _eps(c: dict) -> float:
    return float(c.get("rms_norm_eps", c.get("norm_epsilon")))


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Round to int8 with one absmax scale along ``axis`` (dequantized)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / 127
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _f8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / 448
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


LOWER = {"int8": _q8, "fp8": _f8}


def _mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    if precision in LOWER:
        x, w = LOWER[precision](x, -1), LOWER[precision](w, 0)
    return x @ w


def _norm(c: dict, p: dict, x: jax.Array) -> jax.Array:
    eps = _eps(c)
    if c["norm_type"] == "layer_norm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [T, heads, hd] at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None]      # [T, hd/2]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attend(c: dict, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal grouped attention of one sequence: q [T, H, hd], k/v
    [T, KH, hd] -> [T, H*hd]; query head i reads key head i // (H/KH)."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h * hd)


def _act(c: dict, x: jax.Array) -> jax.Array:
    if c["hidden_act"] == "silu":
        return jax.nn.silu(x)
    if c["hidden_act"] == "gelu_pytorch_tanh":
        return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi)
                                       * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation {c['hidden_act']!r}")


def _block(c: dict, precision: str, w: dict, x: jax.Array) -> jax.Array:
    """One layer over x [R, T, d]."""
    hd = c["head_dim"]
    h_, kh = c["num_attention_heads"], c["num_key_value_heads"]
    mix = w["mixer"]

    def attn_one(xr):                                   # [T, d]
        h = _norm(c, w["norm1"], xr)
        q = _mm(h, mix["wq"], precision)
        k = _mm(h, mix["wk"], precision)
        v = _mm(h, mix["wv"], precision)
        if c["attention_bias"]:
            q, k, v = q + mix["bq"], k + mix["bk"], v + mix["bv"]
        t = xr.shape[0]
        q, k, v = q.reshape(t, h_, hd), k.reshape(t, kh, hd), v.reshape(t, kh, hd)
        if c["qk_norm"]:
            q = q / jnp.sqrt((q * q).mean(-1, keepdims=True) + _eps(c)) * mix["q_norm"]
            k = k / jnp.sqrt((k * k).mean(-1, keepdims=True) + _eps(c)) * mix["k_norm"]
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        return xr + _mm(_attend(c, q, k, v), mix["wo"], precision)

    x = jax.lax.map(attn_one, x)
    h2 = _norm(c, w["norm2"], x)
    ffn = w["ffn"]
    up = _mm(h2, ffn["w_up"], precision)
    if c["mlp_gated"]:
        up = _act(c, _mm(h2, ffn["w_gate"], precision)) * up
    else:
        up = _act(c, up)
    return x + _mm(up, ffn["w_down"], precision)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, precision: str):
    import json
    c = json.loads(cfg_key)
    served = jnp.dtype(c["torch_dtype"])
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    draw_layer = jax.jit(lambda key, i: f32(W.layer(c, key, i, served)))
    draw_outer = jax.jit(lambda key: f32(W.outer(c, key, served)))
    block = jax.jit(functools.partial(_block, c, precision))

    @jax.jit
    def embed(outer, tokens):
        e = outer["embedding"]
        if precision in LOWER:
            e = LOWER[precision](e, -1)
        return e[tokens]

    @jax.jit
    def head(outer, hidden):                            # [K, d] -> [K, V]
        h = _norm(c, outer["final_norm"], hidden)
        return _mm(h, outer["embedding"].T, precision)

    return draw_layer, draw_outer, block, embed, head


def logits_at(c: dict, seed: int, seqs: Sequence[np.ndarray],
              reads: Sequence[np.ndarray], precision: str = "float32",
              pad_to: int = 256, chunk: int = 256) -> List[np.ndarray]:
    """Logits [len(reads[i]), V] (float32) of sequence ``seqs[i]`` at the
    positions ``reads[i]`` (each predicting the token after it)."""
    import json
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    progs = _programs(json.dumps(_model_keys(c), sort_keys=True), precision)
    draw_layer, draw_outer, block, embed, head = progs
    longest = max(len(s) for s in seqs)
    t = -(-longest // pad_to) * pad_to
    tokens = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    key = W.run_key(seed)
    with jax.default_matmul_precision("highest"):
        outer = draw_outer(key)
        x = embed(outer, jnp.asarray(tokens))
        for i in range(c["num_hidden_layers"]):
            x = block(draw_layer(key, jnp.int32(i)), x)
        rows = np.concatenate([np.full(len(r), i) for i, r in enumerate(reads)])
        cols = np.concatenate([np.asarray(r) for r in reads])
        hid = x[rows, cols]
        del x
        out = [np.asarray(head(outer, hid[j:j + chunk]))
               for j in range(0, len(rows), chunk)]
    flat = np.concatenate(out)
    split = np.cumsum([len(r) for r in reads])[:-1]
    return np.split(flat, split)


def _model_keys(c: dict) -> dict:
    """The keys of the configuration that the forward pass reads."""
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps", "norm_epsilon",
            "norm_type", "qk_norm", "attention_bias", "mlp_gated",
            "hidden_act", "torch_dtype", "tie_word_embeddings")
    return {k: c[k] for k in keys if k in c}
