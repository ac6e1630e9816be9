"""Production mesh definitions (single-pod 16x16 / multi-pod 2x16x16).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run entry point must set XLA_FLAGS before anything calls
:func:`make_production_mesh`.
"""
from __future__ import annotations

import math
from typing import Optional

import jax

from repro.sharding.rules import make_mesh

# TPU v5e target constants — used by the roofline analysis (benchmarks/).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return make_mesh(shape, axes, devices=devices[:n])


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU tests (needs host-device-count >= data*model)."""
    n = data * model
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:n])


def n_chips(mesh) -> int:
    return math.prod(mesh.shape.values())
