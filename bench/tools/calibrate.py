#!/usr/bin/env python3
"""Readings that set a cell's check limits: for each seed, one run of the
cell (all in one process) and the numbers of the logit gaps of what it
served (widest, mean, and the share of tokens that are not the
reference's first choice); for the first ``--control`` seeds the same
numbers of each control, the reference at int8 and at fp8 put in the
program's place at the same positions.  Each line also gives the share of
the window's requests that met both of the traffic file's limits, where
it has them.

    python3 bench/tools/calibrate.py --workload qwen3-0.6b.docqa \\
        --seconds 20 --control 3 --seeds 101 102 103 ...

One JSON line per seed on standard output.  Runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from sweep import attainment  # noqa: E402  (beside this file)


def numbers(gaps) -> dict:
    from harness import check
    return {**check.stats(gaps), "miss_share": float((gaps > 0).mean()),
            "mean_sq_gap": float((gaps ** 2).mean()),
            "positions": int(len(gaps))}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precisions", nargs="+", default=["int8", "fp8"])
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness import cell as C
    from harness import check
    from harness.spec import Cell

    cell = Cell(ROOT, args.workload)
    peaks = cell.peaks_table[jax.devices()[0].device_kind]
    limits = cell.traffic.get("limits")

    def readings(with_control):
        def probe(c, seed, picked, rec):
            out = {"program": numbers(rec.gaps)}
            if limits:
                out["met"] = attainment(rec, limits)
            for p in args.precisions if with_control else ():
                out[p] = numbers(check.control_gaps(c, seed, picked, p))
            return out
        return probe

    for i, seed in enumerate(args.seeds):
        r = C.run(cell, seed, args.seconds, False, jax.devices(), peaks,
                  time.perf_counter(), log=lambda s: print(s, file=sys.stderr),
                  probe=readings(i < args.control))
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          **r["probe"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)


if __name__ == "__main__":
    main()
