"""Pipeline-mode dry-run: lower + compile the EdgeShard pipeline runtime
(``core/pipeline.py`` — the paper's technique mapped onto the mesh) on the
production mesh, producing the same cost/collective record as the TP
baseline dry-run so the two distribution modes are directly comparable in
EXPERIMENTS.md §Perf.

The ``--layout dp`` stage layout routes through the same
``runtime.plan_pipeline_spec`` planner→spec path the serving facade
(``serving.LLM.from_plan``) builds on, so dry-run numbers describe the
layouts production serving actually runs.

The ``model`` axis carries the pipeline *stages* (16 stages single-pod);
``data`` (x ``pod``) carries the batch.  Decode shapes lower
``pipeline_decode_tick`` (one no-bubbles tick: every stage advances a
different micro-batch); prefill shapes lower ``pipeline_forward``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun_pipeline \
        --arch starcoder2-7b --shape decode_32k [--microbatches 16] \
        [--layout even|dp] [--tag-suffix +pipeline]

A CPU-only compile tool (512 fake host devices); it never runs on a chip.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import functools
import json
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.core import pipeline as pl
from repro.launch.dryrun import collective_bytes
from repro.launch.mesh import make_production_mesh, n_chips
from repro.models import transformer as T
from repro.models.config import InputShape, ModelConfig

PyTree = Any


def dp_pipeline_spec(cfg: ModelConfig, n_stages: int) -> pl.PipelineSpec:
    """DP-derived (possibly uneven) stage layout from the throughput planner
    run over a homogeneous n_stages-device TPU cluster profile (delegates to
    the runtime factory so dryrun and serving share one planner->spec path)."""
    from repro.core.devices import tpu_pod_cluster
    from repro.runtime import plan_pipeline_spec

    return plan_pipeline_spec(cfg, tpu_pod_cluster(n_stages), n_stages)


def run_pipeline_one(arch: str, shape_name: str, multi_pod: bool = False,
                     n_microbatches: Optional[int] = None,
                     layout: str = "even", out_dir: Optional[str] = None,
                     tag_suffix: str = "+pipeline",
                     mesh=None, stage_axis: str = "model",
                     vocab_sharded: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    other = "data" if stage_axis == "model" else "model"
    batch_axes = ("pod", other) if multi_pod else (other,)
    ns_stages = mesh.shape[stage_axis]
    n_batch = int(np.prod([mesh.shape[a] for a in batch_axes]))

    if layout == "dp":
        spec = dp_pipeline_spec(cfg, ns_stages)
    else:
        spec = pl.even_pipeline_spec(cfg, ns_stages)
    m = n_microbatches or ns_stages                 # >= n_stages: no bubbles
    assert shape.global_batch % m == 0, (shape.global_batch, m)
    mb = shape.global_batch // m

    # ---- shapes (eval_shape only, no allocation) --------------------------
    def init_stage(key):
        params, _ = T.init_params(cfg, key)
        return pl.stack_stage_params(cfg, params, spec)

    (stage_params_s, mask_s) = jax.eval_shape(init_stage, jax.random.PRNGKey(0))

    rec: Dict[str, Any] = {
        "arch": cfg.name, "shape": shape_name, "mode": f"pipeline-{layout}",
        "stage_axis": stage_axis, "vocab_sharded": vocab_sharded,
        "utilization": min(1.0, m / ns_stages),
        "mesh": dict(mesh.shape), "chips": n_chips(mesh),
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "phase": shape.phase, "n_stages": ns_stages, "n_microbatches": m,
        "mb": mb, "periods_per_stage": list(spec.periods_per_stage),
    }

    stack_sh = jax.tree.map(
        lambda _: NamedSharding(mesh, P(stage_axis)), stage_params_s["stack"])
    other_sh = jax.tree.map(
        lambda _: NamedSharding(mesh, P()),
        {k: v for k, v in stage_params_s.items() if k != "stack"})
    if vocab_sharded:
        other_sh["embedding"] = NamedSharding(mesh, P(stage_axis, None))
        if "lm_head" in other_sh:
            other_sh["lm_head"] = NamedSharding(mesh, P(None, stage_axis))
    params_sh = dict(other_sh, stack=stack_sh)
    mask_sh = NamedSharding(mesh, P(stage_axis, None))

    if shape.phase == "decode":
        state_s = jax.eval_shape(functools.partial(
            pl.init_pipeline_decode_state, cfg, spec, m, mb, shape.seq_len))
        state_sh = pl.decode_state_shardings(cfg, state_s, mesh, paged=False,
                                             stage_axis=stage_axis,
                                             batch_axes=batch_axes)
        feed_s = jax.ShapeDtypeStruct((mb,), jnp.int32)
        feed_sh = NamedSharding(mesh, P(batch_axes))

        def step(stage_params, mask, state, feed):
            return pl.pipeline_decode_tick(cfg, stage_params, mask, state,
                                           feed, spec, mesh,
                                           stage_axis=stage_axis,
                                           batch_axes=batch_axes,
                                           vocab_sharded=vocab_sharded)

        args = (stage_params_s, mask_s, state_s, feed_s)
        shardings = (params_sh, mask_sh, state_sh, feed_sh)
    else:                                           # prefill / forward
        tok_s = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                     jnp.int32)
        tok_sh = NamedSharding(mesh, P(batch_axes, None))

        def step(stage_params, mask, tokens):
            return pl.pipeline_forward(cfg, stage_params, mask, tokens, spec,
                                       mesh, n_microbatches=m,
                                       stage_axis=stage_axis,
                                       batch_axes=batch_axes)

        args = (stage_params_s, mask_s, tok_s)
        shardings = (params_sh, mask_sh, tok_sh)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(step, in_shardings=shardings).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                            if isinstance(v, (int, float))
                            and k in ("flops", "bytes accessed")}
    ma = compiled.memory_analysis()
    if ma is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                rec[attr] = int(v)
    rec["collective_bytes"] = collective_bytes(compiled.as_text())
    rec["ok"] = True
    if out_dir:
        Path(out_dir).mkdir(parents=True, exist_ok=True)
        tag = f"{cfg.name}{tag_suffix}_{shape_name}_" \
              f"{'multipod' if multi_pod else 'pod'}"
        Path(out_dir, tag.replace("/", "-") + ".json").write_text(
            json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--layout", default="even", choices=["even", "dp"])
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--tag-suffix", default="+pipeline")
    ap.add_argument("--stage-axis", default="model",
                    choices=["model", "data"],
                    help="mesh axis carrying pipeline stages (batch uses "
                         "the other axis)")
    ap.add_argument("--vocab-sharded", action="store_true",
                    help="shard embed/head tables over the stage axis "
                         "(EXPERIMENTS.md Perf-C2)")
    args = ap.parse_args()
    rec = run_pipeline_one(args.arch, args.shape, args.multi_pod,
                           args.microbatches, args.layout, args.out_dir,
                           args.tag_suffix, stage_axis=args.stage_axis,
                           vocab_sharded=args.vocab_sharded)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
