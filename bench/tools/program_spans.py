#!/usr/bin/env python3
"""Reduce a kept trace to the program's own spans and named programs.

    python3 bench/run.py --workload <cell> --seed <n> --seconds 45 \\
        --trace 1 --keep-trace <dir>
    python3 bench/tools/program_spans.py <dir>/<cell>.<seed>.xplane.pb

Prints one JSON object: the per-layer quantities of
``harness/progtrace.py`` (null where the trace holds no ``repro.*`` span or
no named program), the device's idle seconds apportioned over the innermost
program span and over the chain of open spans (also per traced decode
step, in ms), the count and mean of each span, and each program's
executions and device time.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import progtrace as P  # noqa: E402


def report(trace: P.ProgramTrace) -> dict:
    steps = len(trace.inside("repro.backend.decode_step"))
    spans = defaultdict(list)
    for s in trace.spans:
        spans[s.name].append(s.ns * 1e-6)
    programs = defaultdict(lambda: [0, 0.0])
    for evs in trace.modules.values():
        for name, a, b in evs:
            programs[name][0] += 1
            programs[name][1] += (b - a) * 1e-9
    by_path = P.apportion(trace, by_path=True)
    return {
        "window_s": (trace.window[1] - trace.window[0]) * 1e-9,
        "metrics": {f.__name__: f(trace) for f in (
            P.sched_self_ms, P.prefill_device_ms, P.decode_device_ms,
            P.decode_idle_ms)},
        "idle_s": P.apportion(trace),
        "idle_s_by_path": by_path,
        "decode_steps": steps,
        "idle_ms_per_decode_step_by_path": {
            k: 1e3 * v / steps for k, v in by_path.items()} if steps else {},
        "spans": {k: {"n": len(v), "mean_ms": sum(v) / len(v)}
                  for k, v in sorted(spans.items())},
        "programs": {k: {"n": n, "device_s": t} for k, (n, t) in
                     sorted(programs.items(), key=lambda kv: -kv[1][1])},
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    args = ap.parse_args()
    print(json.dumps(report(P.read(args.path)), indent=1))


if __name__ == "__main__":
    main()
