import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, record memory/cost analyses and collective bytes.

A CPU-only compile tool: the production mesh is 512 fake host devices, so
it never runs on (or asks for) a chip.  The lines above MUST stay the first
statements in this file — jax locks the platform and device count on first
init, and smoke tests / benches must keep seeing one device, so the flag
lives here and only here.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out-dir ...]
"""
import argparse
import functools
import json
import re
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch.mesh import make_production_mesh, n_chips
from repro.models import transformer as T
from repro.models.config import InputShape, ModelConfig
from repro.models.frontends import input_spec_for
from repro.sharding.rules import (decode_seq_model_rules, default_rules,
                                  fsdp_rules, long_context_rules,
                                  shape_aware_sharding_tree, use_mesh)
from repro.training.adamw import AdamWConfig, AdamWState, adamw_init, adamw_update

PyTree = Any

#: archs whose full-attention layers make 524k-token decode unreasonable
#: without the documented sliding-window variant (DESIGN.md).
LONG_CONTEXT_NATIVE = {"recurrentgemma-2b", "xlstm-1.3b", "gemma2-2b"}


def input_specs(cfg: ModelConfig, shape: InputShape, mesh=None,
                ) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this workload."""
    b, s = shape.global_batch, shape.seq_len
    if shape.phase == "train":
        specs = {
            "tokens": input_spec_for(cfg, b, s, decode=False),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
    elif shape.phase == "prefill":
        caches = jax.eval_shape(
            functools.partial(T.init_caches, cfg, b, s, jnp.bfloat16))
        specs = {
            "tokens": input_spec_for(cfg, b, s, decode=False),
            "caches": caches,
        }
    else:                                    # decode: 1 new token, full cache
        caches = jax.eval_shape(
            functools.partial(T.init_caches, cfg, b, s, jnp.bfloat16))
        specs = {
            "tokens": input_spec_for(cfg, b, s, decode=True),
            "caches": caches,
        }
    return specs


def build_step(cfg: ModelConfig, shape: InputShape,
               xent_chunk: Optional[int] = None,
               mesh=None, gather_rules=None, impl: str = "xla"):
    """Returns (step_fn, arg ShapeDtypeStructs (params/opt added), logical
    sharding-axes trees for every argument).

    ``gather_rules``: ZeRO-3-style FSDP done right — params arrive sharded
    over the data axis (``fsdp_rules`` in_shardings) and are re-sharded ONCE
    per step to these (compute) rules via an explicit constraint, so XLA
    all-gathers each weight once instead of at every use; grads reduce-
    scatter back to the data-sharded optimizer update.
    """
    captured = {}

    def _init(key):
        p, a = T.init_params(cfg, key)
        captured["axes"] = a                  # plain-python side channel
        return p

    params_shapes = jax.eval_shape(_init, jax.random.PRNGKey(0))
    axes = captured["axes"]
    specs = input_specs(cfg, shape)
    opt_cfg = AdamWConfig()
    gather_sh = None
    if gather_rules is not None and mesh is not None:
        gather_sh = shape_aware_sharding_tree(params_shapes, axes, mesh,
                                              gather_rules)

    if shape.phase == "train":
        def step(params, opt, tokens, labels):
            def loss_fn(p):
                if gather_sh is not None:     # one explicit gather per step
                    p = jax.tree.map(jax.lax.with_sharding_constraint,
                                     p, gather_sh)
                return T.train_loss(cfg, p, tokens, labels,
                                    xent_chunk=xent_chunk, impl=impl)[0]
            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt, metrics = adamw_update(opt_cfg, grads, opt,
                                                        params)
            metrics["loss"] = loss
            return new_params, new_opt, metrics

        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        args = (params_shapes, opt_shapes, specs["tokens"], specs["labels"])
        arg_axes = (axes, AdamWState(step=(), mu=axes, nu=axes),
                    ("batch", None) if cfg.frontend is None
                    else ("batch", None, "embed"),
                    ("batch", None))
    elif shape.phase == "prefill":
        def step(params, tokens, caches):
            logits, caches, _ = T.forward(cfg, params, tokens,
                                          mode="prefill", caches=caches,
                                          impl=impl)
            return logits[:, -1], caches

        args = (params_shapes, specs["tokens"], specs["caches"])
        arg_axes = (axes,
                    ("batch", None) if cfg.frontend is None
                    else ("batch", None, "embed"),
                    T.cache_axes(cfg))
    else:
        def step(params, tokens, caches):
            return T.decode_step(cfg, params, tokens, caches)

        args = (params_shapes, specs["tokens"], specs["caches"])
        arg_axes = (axes, ("batch",), T.cache_axes(cfg))
    return step, args, arg_axes


_COLL_RE = re.compile(
    r"= (?P<types>[^=]*?) "
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_TYPE_RE = re.compile(
    r"\b(f64|f32|bf16|f16|f8e\w+|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
    r"\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
                "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
                "pred": 1}


def _line_bytes(types: str) -> float:
    total = 0.0
    for t in _TYPE_RE.finditer(types):
        dt, dims = t.groups()
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        nb = 1 if dt.startswith("f8") else _DTYPE_BYTES.get(dt, 4)
        total += size * nb
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))           # [n_groups, group_size]<=[...]
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device operand bytes of every collective in the optimized
    (partitioned, per-device) HLO.  Result shape == operand shape for
    all-reduce / all-to-all / collective-permute; all-gather operands are
    result / group_size."""
    out: Dict[str, float] = {k: 0.0 for k in
                             ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    kinds = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
    for line in hlo_text.splitlines():
        eq = line.find("= ")
        if eq < 0:
            continue
        for kind in kinds:
            idx = line.find(f" {kind}(", eq)
            if idx < 0:
                idx = line.find(f" {kind}-start(", eq)
            if idx < 0:
                continue
            nbytes = _line_bytes(line[eq + 2:idx])
            if kind == "all-gather":
                nbytes /= max(_group_size(line), 1)
            out[kind] += nbytes
            break
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _compile_and_analyse(cfg: ModelConfig, shape: InputShape, mesh, rules,
                         param_rules=None, xent_chunk: Optional[int] = None,
                         donate: bool = False,
                         gather: bool = False,
                         impl: str = "xla") -> Dict[str, Any]:
    """Lower + compile one (cfg, shape) and extract all analyses.

    ``param_rules``: optional separate rules for parameter/opt in_shardings
    (the FSDP §Perf variant); activation constraints keep ``rules``.
    ``donate``: donate the mutable state argument (decode caches / train
    params+opt) so XLA updates in place instead of copying (§Perf).
    """
    step, args, arg_axes = build_step(
        cfg, shape, xent_chunk=xent_chunk, mesh=mesh if gather else None,
        gather_rules=rules if gather else None, impl=impl)
    donate_argnums = ()
    if donate:
        donate_argnums = (0, 1) if shape.phase == "train" else (2,)
    pr = param_rules or rules
    # args 0 (params) and, for train, 1 (opt state) are parameter trees
    n_param_args = 2 if shape.phase == "train" else 1
    in_shardings = tuple(
        shape_aware_sharding_tree(a, ax, mesh,
                                  pr if i < n_param_args else rules)
        for i, (a, ax) in enumerate(zip(args, arg_axes)))
    rec: Dict[str, Any] = {}
    t0 = time.time()
    with use_mesh(mesh, rules):
        lowered = jax.jit(step, in_shardings=in_shardings,
                          donate_argnums=donate_argnums).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                            if isinstance(v, (int, float))
                            and k in ("flops", "bytes accessed",
                                      "bytes accessed output",
                                      "optimal_seconds")}
    ma = compiled.memory_analysis()
    if ma is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                rec[attr] = int(v)
    hlo = compiled.as_text()
    rec["collective_bytes"] = collective_bytes(hlo)
    rec["hlo_bytes_len"] = len(hlo)
    arg_bytes = 0
    for a in args:
        for leaf in jax.tree.leaves(a):
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            arg_bytes += n * leaf.dtype.itemsize
    rec["global_argument_bytes"] = arg_bytes
    return rec


def _scan_corrected(cfg: ModelConfig, shape: InputShape, mesh, rules,
                    full: Dict[str, Any], param_rules=None,
                    xent_chunk: Optional[int] = None,
                    donate: bool = False, gather: bool = False,
                    impl: str = "xla") -> Dict[str, Any]:
    """Correct XLA's while-body-counted-once cost analysis.

    ``cost_analysis()`` counts a ``lax.scan`` body ONCE regardless of trip
    count.  We compile two unrolled variants — 1 period and 2 periods as a
    single scan iteration — whose difference is the exact HLO cost of one
    period, then extrapolate:

        corrected = full + (n_full_periods - 1) * marginal_per_period

    (the full compile already counts one body instance + tail blocks).
    """
    import dataclasses as _dc
    if cfg.n_full_periods <= 1:
        return {}
    p = cfg.period
    cfg1 = _dc.replace(cfg, n_layers=p)
    cfg2 = _dc.replace(cfg, pattern=cfg.pattern * 2, n_layers=2 * p)
    r1 = _compile_and_analyse(cfg1, shape, mesh, rules, param_rules,
                              xent_chunk, donate, gather, impl)
    r2 = _compile_and_analyse(cfg2, shape, mesh, rules, param_rules,
                              xent_chunk, donate, gather, impl)
    k = cfg.n_full_periods - 1
    out: Dict[str, Any] = {"marginal_from": {"p1": r1["cost_analysis"],
                                             "p2": r2["cost_analysis"]}}
    corr_ca = {}
    for key in ("flops", "bytes accessed"):
        m = r2["cost_analysis"].get(key, 0.0) - r1["cost_analysis"].get(key, 0.0)
        corr_ca[key] = full["cost_analysis"].get(key, 0.0) + k * max(m, 0.0)
    out["cost_analysis_corrected"] = corr_ca
    coll = {}
    for kind, v in full["collective_bytes"].items():
        m = (r2["collective_bytes"].get(kind, 0.0)
             - r1["collective_bytes"].get(kind, 0.0))
        coll[kind] = v + k * max(m, 0.0)
    out["collective_bytes_corrected"] = coll
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            variant: Optional[str] = None, out_dir: Optional[str] = None,
            mesh=None, rules_variant: Optional[str] = None,
            fsdp: bool = False, xent_chunk: Optional[int] = None,
            donate: bool = False, fsdp_gather: bool = False,
            impl: str = "xla", tag_suffix: str = "") -> Dict[str, Any]:
    from repro.models.attention import _check_decode_impl
    _check_decode_impl(impl)   # library callers bypass argparse choices
    cfg = get_config(arch, variant=variant)
    shape = SHAPES[shape_name]
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    long_ctx = (shape.phase == "decode"
                and shape.global_batch < mesh.shape["data"])
    if rules_variant == "decode-seq-model":
        rules = decode_seq_model_rules(multi_pod)
    elif long_ctx:
        rules = long_context_rules(multi_pod)
    else:
        rules = default_rules(multi_pod)
    if fsdp_gather:
        fsdp = True
    param_rules = fsdp_rules(multi_pod) if fsdp else None

    rec: Dict[str, Any] = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": dict(mesh.shape), "chips": n_chips(mesh),
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "phase": shape.phase, "long_context_rules": bool(long_ctx),
        "rules_variant": rules_variant, "fsdp": fsdp,
        "xent_chunk": xent_chunk, "donate": donate,
        "fsdp_gather": fsdp_gather,
        "impl": impl if impl != "xla" else None,
    }
    rec.update(_compile_and_analyse(cfg, shape, mesh, rules,
                                    param_rules=param_rules,
                                    xent_chunk=xent_chunk, donate=donate,
                                    gather=fsdp_gather, impl=impl))
    rec.update(_scan_corrected(cfg, shape, mesh, rules, rec,
                               param_rules=param_rules,
                               xent_chunk=xent_chunk, donate=donate,
                               gather=fsdp_gather, impl=impl))
    rec["ok"] = True
    if out_dir:
        Path(out_dir).mkdir(parents=True, exist_ok=True)
        tag = f"{cfg.name}{tag_suffix}_{shape_name}_" \
              f"{'multipod' if multi_pod else 'pod'}"
        Path(out_dir, tag.replace("/", "-") + ".json").write_text(
            json.dumps(rec, indent=1))
    return rec


#: which variant each arch needs for long_500k (None = skip impossible)
def long500k_variant(arch: str) -> Optional[str]:
    if arch in LONG_CONTEXT_NATIVE:
        return None            # native sub-quadratic / sliding support
    return "swa"               # documented sliding-window override


def iter_all(multi_pod: bool = False):
    from repro.configs import ASSIGNED
    for arch in ASSIGNED:
        for shape_name in SHAPES:
            variant = None
            if shape_name == "long_500k":
                variant = long500k_variant(arch)
            yield arch, shape_name, variant


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="benchmarks/results/dryrun")
    ap.add_argument("--rules", default=None, dest="rules_variant",
                    choices=[None, "decode-seq-model"],
                    help="sharding-rule variant (perf iterations)")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params+opt over the data axis (ZeRO-3-ish)")
    ap.add_argument("--xent-chunk", type=int, default=None,
                    help="chunked cross-entropy (never materialize logits)")
    ap.add_argument("--donate", action="store_true",
                    help="donate mutable state (caches / params+opt)")
    ap.add_argument("--fsdp-gather", action="store_true",
                    help="FSDP with one explicit per-step weight gather "
                         "(ZeRO-3 pattern; implies --fsdp)")
    ap.add_argument("--impl", default="xla", choices=["xla", "chunked"],
                    help="attention impl for train/prefill (chunked = "
                         "flash-style online softmax, no S^2 buffer)")
    ap.add_argument("--tag-suffix", default="",
                    help="suffix for the output json (perf iterations)")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    if args.all:
        for arch, shape_name, variant in iter_all(args.multi_pod):
            try:
                rec = run_one(arch, shape_name, args.multi_pod, variant,
                              args.out_dir, mesh=mesh)
                print(f"OK  {arch:24s} {shape_name:12s} "
                      f"compile={rec['compile_s']:.1f}s "
                      f"flops={rec['cost_analysis'].get('flops', 0):.3g} "
                      f"coll={rec['collective_bytes']['total']:.3g}B")
            except Exception as e:  # noqa: BLE001 — report and continue
                print(f"FAIL {arch:24s} {shape_name:12s} {type(e).__name__}: {e}")
    else:
        rec = run_one(args.arch, args.shape, args.multi_pod, args.variant,
                      args.out_dir, mesh=mesh,
                      rules_variant=args.rules_variant, fsdp=args.fsdp,
                      xent_chunk=args.xent_chunk, donate=args.donate,
                      fsdp_gather=args.fsdp_gather, impl=args.impl,
                      tag_suffix=args.tag_suffix)
        print(json.dumps({k: v for k, v in rec.items()
                          if k != "hlo_bytes_len"}, indent=1))


if __name__ == "__main__":
    main()
