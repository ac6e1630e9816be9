"""Weight-only int8 dequantizing matmul (beyond-paper feature).

The paper motivates edge deployment with quantization (§II, Table I) but
does not contribute a method; we provide int8 weight-only inference as a
first-class config option — it halves every ``Req_i`` the partitioner sees,
changing the DP's device selection (fewer devices needed per model).

y = x @ (w_q * scale): per-output-channel scales can be applied after the
K-reduction, so the kernel accumulates x @ w_q in f32 VMEM scratch over the
K grid axis and multiplies by ``scale`` once at the end — the MXU sees a
plain matmul, dequantization is free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                           # [bm, bk]
    w = w_ref[...].astype(jnp.float32)                           # [bk, bn]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * scale_ref[0]).astype(o_ref.dtype)


def int8_matmul_pallas(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
                       block_m: int = 128, block_n: int = 128,
                       block_k: int = 512, interpret: bool = False,
                       ) -> jax.Array:
    """x [M,K] float; w_q [K,N] int8; scale [1,N] f32 -> y [M,N] (x dtype)."""
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (m // block_m, n // block_n, k // block_k)

    x_spec = pl.BlockSpec((block_m, block_k), lambda im, in_, ik: (im, ik))
    w_spec = pl.BlockSpec((block_k, block_n), lambda im, in_, ik: (ik, in_))
    s_spec = pl.BlockSpec((1, block_n), lambda im, in_, ik: (0, in_))
    o_spec = pl.BlockSpec((block_m, block_n), lambda im, in_, ik: (im, in_))

    return pl.pallas_call(
        _int8_kernel,
        grid=grid,
        in_specs=[x_spec, w_spec, s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="int8_matmul",
    )(x, w_q, scale)


def quantize_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-output-channel symmetric int8 quantization. w: [K, N]."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return w_q, scale.astype(jnp.float32)
