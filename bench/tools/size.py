#!/usr/bin/env python3
"""Size a one-chip tensor deployment: compile the program's prefill at the
widest bucket for a few slot counts and print what XLA says it needs.

    python3 bench/tools/size.py --config qwen3-0.6b --width 2048 --slots 4 6 8

For each slot count it prints the prefill program's argument, output and
temporary bytes from ``memory_analysis()``, the pool's bytes per block, and
the blocks left on a chip of the kind ``bench/peaks.json`` lists after the
weights, the prefill's own bytes and a 5% reserve.  Run it on the chip
(it compiles for the attached device); it serves nothing.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--width", type=int, required=True)
    ap.add_argument("--slots", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from harness import model as M
    from harness.roofline import ModelCost
    from repro.models import transformer as T

    c = json.loads((ROOT / "bench" / "configs" /
                    f"{args.config}.json").read_text())
    dep = c["deployment"]
    cfg = M.program_config(c)
    dev = jax.devices()[0]
    hbm = json.loads((ROOT / "bench" / "peaks.json").read_text())[
        dev.device_kind]["hbm_bytes"]
    shapes, _ = M.program_params(cfg)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    cost = ModelCost.from_config(c)
    per_block = dep["block_size"] * cost.n_layers * 2 * cost.n_kv_heads * \
        cost.head_dim * cost.kv_itemsize
    print(f"{dev.device_kind}: weights {weights} bytes, pool block "
          f"{per_block} bytes ({dep['block_size']} tokens, "
          f"{dep['cache_dtype']})")
    fn = jax.jit(functools.partial(T.forward, cfg, mode="prefill",
                                   impl=dep["impl"]))
    for n in args.slots:
        caches = jax.eval_shape(functools.partial(
            T.init_caches, cfg, n, args.width, jnp.dtype(dep["cache_dtype"])))
        lowered = fn.lower(shapes, jax.ShapeDtypeStruct((n, args.width),
                                                        jnp.int32),
                           caches=caches,
                           prompt_lens=jax.ShapeDtypeStruct((n,), jnp.int32))
        m = lowered.compile().memory_analysis()
        need = m.temp_size_in_bytes + m.output_size_in_bytes + \
            m.argument_size_in_bytes - weights
        left = int(0.95 * hbm) - weights - need
        print(f"slots {n} width {args.width}: arguments "
              f"{m.argument_size_in_bytes} outputs {m.output_size_in_bytes} "
              f"temporaries {m.temp_size_in_bytes}; beside the weights the "
              f"prefill needs {need} bytes, leaving {left} bytes = "
              f"{left // per_block} blocks for the pool")


if __name__ == "__main__":
    main()
