#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a model configuration under a traffic mix) is looked up in
``BENCHMARK.json``.  Set-up plans the deployment, makes the weights from
the seed on the device, and warms up every program the traffic reaches;
then the window serves the traffic for ``--seconds`` of wall time, and a
reference compares a sample of what it served.  With ``--trace 0`` the
result holds the cell's end-to-end metrics; with ``--trace 1`` it traces
the end of the window and holds the per-layer metrics.  Progress and the
checks go to standard error; the last line of standard output is the
result, one JSON object.

Without a TPU, with fewer chips than the cell asks for, or on a device
that ``bench/peaks.json`` does not list, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str, code: int = 3) -> None:
    log(f"bench: {msg}")
    sys.exit(code)


def chips(cell):
    """The machine's devices, refused unless they are TPUs of a kind the
    table of peaks lists, and as many as the cell asks for."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform {dev.platform!r} ({dev.device_kind})")
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
    peaks = cell.peaks_table.get(dev.device_kind)
    if peaks is None:
        fail(f"device kind {dev.device_kind!r} is not in bench/peaks.json")
    return devices, peaks


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the trace file into this directory")
    args = ap.parse_args(argv)

    from harness.spec import Cell
    try:
        cell = Cell(ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(str(e), code=2)
    # the TPU runtime's own logs would go to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices, peaks = chips(cell)
    log(f"{args.workload} seed {args.seed}: {len(devices)} x "
        f"{devices[0].device_kind}, jax {jax.__version__}, compile cache "
        f"{cache}")

    from harness import cell as run_cell
    result = run_cell.run(cell, args.seed, args.seconds, bool(args.trace),
                          devices, peaks, T_START, keep_trace=args.keep_trace,
                          log=log)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
