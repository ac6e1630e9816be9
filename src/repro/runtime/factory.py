"""planner -> backend: turn a DP :class:`~repro.core.planner.Deployment`
into a running :class:`InferenceBackend` in one call.

This is the seam between the paper's Fig. 3 planning stage and the serving
stack: the same ``Deployment`` object can be materialized as

- ``kind="pipeline"`` — the real no-bubbles stage pipeline on a jax mesh
  (stage layout via :func:`repro.core.pipeline.spec_from_plan`, so uneven
  planner stages are preserved),
- ``kind="tensor"``   — the single-engine pjit path (capacity taken from
  the plan's feasible batch),
- ``kind="sim"``      — the discrete-event cost model, for planner sweeps
  and benchmarks that need the serving interface without a model.
"""
from __future__ import annotations

from typing import Any, Optional

from repro.core.devices import ClusterSpec
from repro.core.planner import Deployment
from repro.core.profile import ModelProfile, Workload
from repro.core.simulator import build_stage_costs
from repro.models.config import ModelConfig
from repro.runtime.base import InferenceBackend
from repro.runtime.sim import SimBackend

PyTree = Any


def plan_pipeline_spec(cfg: ModelConfig, cluster: ClusterSpec,
                       n_stages: int, workload: Optional[Workload] = None):
    """DP-derived (possibly uneven) stage layout from the throughput planner
    run over ``cluster``.  Raises if the plan is memory-infeasible."""
    from repro.core.partition import solve_throughput
    from repro.core.pipeline import spec_from_plan
    from repro.core.planner import build_problem

    prob = build_problem(cfg, cluster, workload or Workload(dtype_bytes=2))
    plan = solve_throughput(prob)
    if not len(plan.assignment):
        raise ValueError(
            f"{cfg.name}: infeasible on {cluster.n} devices (memory) — "
            f"DP found no plan; use more stages/chips or quantize")
    return spec_from_plan(cfg, plan, n_stages)


def from_deployment(deployment: Deployment, cluster: ClusterSpec,
                    cfg: ModelConfig, *, kind: str = "pipeline",
                    params: Optional[PyTree] = None,
                    workload: Optional[Workload] = None,
                    mesh=None, n_slots: Optional[int] = None, lanes: int = 1,
                    max_len: int = 256, cache_dtype=None,
                    schedule: str = "nobubbles", impl: str = "xla",
                    cache_layout: str = "contiguous", block_size: int = 16,
                    num_blocks: Optional[int] = None,
                    prefix_cache: bool = False,
                    ) -> InferenceBackend:
    """Materialize a planned deployment as a serving backend.

    ``cache_layout="paged"`` provisions a shared KV block pool (``num_blocks``
    blocks of ``block_size`` tokens; default = no overcommit) instead of
    worst-case per-slot caches — all three kinds honour it (``sim`` keeps
    accounting only).

    ``impl`` selects the attention math on both real kinds: ``"pallas"``
    dispatches the Pallas kernels end to end, including the paged decode
    kernel that reads pool blocks through the slot's block table (no
    per-step gather); ``"xla"``/``"chunked"`` run the jnp reference.
    Unknown values raise at the first decode step.
    """
    assert deployment.ok, f"deployment {deployment.method} is OOM-infeasible"
    plan = deployment.plan
    n_stages = len(plan.stages)

    if kind == "sim":
        profile = ModelProfile.from_config(cfg, workload or Workload())
        mb = lanes if lanes > 1 else max(deployment.batch, 1)
        costs = build_stage_costs(profile, cluster, plan, mb_batch=mb)
        return SimBackend(costs, n_slots=n_slots or 2 * n_stages,
                          mb_batch=mb, schedule=schedule,
                          vocab_size=cfg.vocab_size, max_len=max_len,
                          cache_layout=cache_layout, block_size=block_size,
                          num_blocks=num_blocks, prefix_cache=prefix_cache)

    assert params is not None, f"kind={kind!r} needs model params"
    import jax.numpy as jnp
    cache_dtype = cache_dtype or jnp.float32

    if kind == "tensor":
        from repro.runtime.tensor import TensorBackend
        return TensorBackend(cfg, params,
                             n_slots=n_slots or max(deployment.batch, 1),
                             max_len=max_len, mesh=mesh, impl=impl,
                             cache_dtype=cache_dtype,
                             cache_layout=cache_layout,
                             block_size=block_size, num_blocks=num_blocks,
                             prefix_cache=prefix_cache)

    if kind == "pipeline":
        from repro.core.pipeline import spec_from_plan
        from repro.runtime.pipeline_backend import PipelineBackend
        from repro.sharding import make_mesh
        spec = spec_from_plan(cfg, plan, n_stages)
        if mesh is None:
            mesh = make_mesh((1, n_stages), ("data", "model"))
        return PipelineBackend(cfg, params, spec, mesh,
                               n_slots=n_slots, lanes=lanes, max_len=max_len,
                               cache_dtype=cache_dtype, impl=impl,
                               cache_layout=cache_layout,
                               block_size=block_size, num_blocks=num_blocks,
                               prefix_cache=prefix_cache)

    raise ValueError(f"unknown backend kind {kind!r}")
