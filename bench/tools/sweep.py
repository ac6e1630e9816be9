#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve its traffic at several fixed rates
(one process, one window each) and report, per rate, the share of
requests that met both of the traffic file's limits and whether the queue
grew over the window.

    python3 bench/tools/sweep.py --workload qwen3-0.6b.docqa --seconds 20 \\
        --seed 7 --rates 1 1.5 2 2.5

The knee is the highest rate at which 90% of requests meet both limits and
the queue does not grow; the cell's traffic file is then set below it.
One JSON line per rate on standard output.  Runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def attainment(rec, limits: dict) -> dict:
    """Share of requests meeting both limits, and queue wait of the first
    and the last third of the window's arrivals."""
    ok, waits = 0, []
    for r in sorted(rec.reqs, key=lambda r: r.sched):
        ttft = (r.first if r.first is not None else rec.drain_end) - r.sched
        tpot = 1e3 * (r.finish - r.first) / (len(r.tokens) - 1) \
            if r.finish is not None and len(r.tokens) > 1 else 0.0
        ok += int(r.finish is not None and ttft <= limits["ttft_s"]
                  and tpot <= limits["tpot_ms"])
        waits.append((r.admitted if r.admitted is not None
                      else rec.drain_end) - r.sched)
    third = max(len(waits) // 3, 1)
    ttfts = rec.ttfts()
    return {"met_share": ok / max(len(rec.reqs), 1),
            "ttft_p90_s": float(np.percentile(ttfts, 90)) if ttfts else None,
            "queue_wait_first_third_s": float(np.mean(waits[:third])),
            "queue_wait_last_third_s": float(np.mean(waits[-third:]))}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness import cell as C
    from harness.spec import Cell

    cell = Cell(ROOT, args.workload)
    peaks = cell.peaks_table[jax.devices()[0].device_kind]
    limits = cell.traffic["limits"]
    for rate in args.rates:
        cell.traffic["arrivals"]["rate_rps"] = rate
        r = C.run(cell, args.seed, args.seconds, False, jax.devices(), peaks,
                  time.perf_counter(), log=lambda s: print(s, file=sys.stderr),
                  probe=lambda c, seed, picked, rec: attainment(rec, limits))
        print(json.dumps({"rate_rps": rate, "correct": r["correct"],
                          "attempted": r["attempted"], **r["probe"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)


if __name__ == "__main__":
    main()
