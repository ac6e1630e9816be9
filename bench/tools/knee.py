#!/usr/bin/env python3
"""Read ``sweep.py``'s lines and print the knee: the highest swept rate at
which 90% of requests met both limits and the queue did not grow (the
last third of the window's arrivals waited no more than twice as long as
the first third, give or take 50 ms).

    python3 bench/tools/knee.py sweep.out [--set-traffic docqa --share 0.8]

With ``--set-traffic`` it also writes ``share`` x knee into that traffic
file's ``rate_rps``.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[2]


def knee(lines) -> Optional[float]:
    """The knee, or None where no swept rate met the rule: the sweep has
    to reach lower."""
    ok = [d["rate_rps"] for d in lines
          if d["met_share"] >= 0.9 and d["queue_wait_last_third_s"]
          <= 2 * d["queue_wait_first_third_s"] + 0.05]
    return max(ok) if ok else None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("sweep")
    ap.add_argument("--set-traffic", default=None)
    ap.add_argument("--share", type=float, default=0.8)
    args = ap.parse_args()
    lines = [json.loads(x) for x in Path(args.sweep).read_text().splitlines()
             if x.startswith("{")]
    k = knee(lines)
    if k is None:
        raise SystemExit("no swept rate met the rule: sweep lower rates")
    print(f"knee {k} req/s")
    if args.set_traffic:
        p = ROOT / "bench" / "traffic" / f"{args.set_traffic}.json"
        t = json.loads(p.read_text())
        t["arrivals"]["rate_rps"] = round(args.share * k, 3)
        p.write_text(json.dumps(t, indent=2) + "\n")
        print(f"{p.name}: rate_rps {t['arrivals']['rate_rps']}")


if __name__ == "__main__":
    main()
