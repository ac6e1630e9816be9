"""RG-LRU sequence scan as a Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, elementwise over the recurrent width R.

Grid ``(batch, R / block_r)`` — each program owns a [S, block_r] slab in VMEM
and walks the sequence with a ``fori_loop``, carrying h in VMEM scratch.
This is the TPU adaptation of the GPU "linear scan" kernels: instead of a
warp-level scan we keep the whole per-channel time series VMEM-resident and
let the VPU stream it; channels (lanes) are the 128-wide vector axis, so
``block_r`` is a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, seq_len: int):
    h_ref[...] = h0_ref[...]                                    # [1, br]

    def step(t, _):
        a_t = a_ref[0, t]                                       # [br]
        b_t = b_ref[0, t]
        h = a_t * h_ref[0, :] + b_t
        h_ref[0, :] = h
        o_ref[0, t] = h
        return ()

    jax.lax.fori_loop(0, seq_len, step, ())


def rglru_scan_pallas(log_a: jax.Array, b: jax.Array, h0: jax.Array, *,
                      block_r: int = 128, interpret: bool = False,
                      ) -> jax.Array:
    """log_a/b: [B, S, R] float32; h0: [B, R] float32 -> h: [B, S, R]."""
    bb, s, r = log_a.shape
    assert r % block_r == 0, (r, block_r)
    a = jnp.exp(log_a)
    grid = (bb, r // block_r)

    seq_spec = pl.BlockSpec((1, s, block_r), lambda i, j: (i, 0, j))
    h0_spec = pl.BlockSpec((1, block_r), lambda i, j: (i, j))

    kernel = functools.partial(_rglru_kernel, seq_len=s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, h0_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((bb, s, r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, b, h0)
