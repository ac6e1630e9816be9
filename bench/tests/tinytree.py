"""A copy of the benchmark with small cells added as data files only, for
tests that drive whole runs on the CPU.

``make(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp`` and
adds, by new files and new ``BENCHMARK.json`` entries alone, one small
dense configuration (``tiny``, the shapes of qwen3-0.6b's block at toy
widths), a small traffic mix and a cell on each, plus a four-stage
pipeline cell (``tinypipe``, the shapes of the program's starcoder2-7b
block at toy widths, from ``data/starcoder2-7b.program.json``).
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: the program's starcoder2-7b configuration, which departs from the
#: published model: a test fixture, not a benchmark configuration
PROGRAM_STARCODER2 = BENCH / "tests" / "data" / "starcoder2-7b.program.json"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}

#: toy peaks: the arithmetic of the readers, not a device
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 2 ** 30}


def _ignore(_dir, names):
    return [n for n in names if n in ("__pycache__", "tests")]


#: a wider toy model, where the control's precision departs clearly
WIDER = dict(TINY, hidden_size=256, intermediate_size=768, head_dim=64,
             vocab_size=4096, num_hidden_layers=4)


def make(tmp: Path, logit_gap: float = 0.5, sizes: dict = TINY,
         sample_tokens: int = 48, mean_gap: float = 0.5) -> Path:
    root = Path(tmp) / "tree"
    (root).mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "bench", ignore=_ignore, dirs_exist_ok=True)
    b = json.loads((root / "BENCHMARK.json").read_text())

    chat = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    chat.update(sizes, name="tiny", repro_config=None)
    chat["deployment"].update(n_slots=4, max_len=96, num_blocks=None,
                              min_bucket=8)
    pipe = json.loads((PROGRAM_STARCODER2).read_text())
    pipe.update(TINY, name="tinypipe", repro_config=None, num_hidden_layers=4)
    pipe["deployment"].update(max_len=64)
    traffic = {
        "tinychat": {"loop": "open",
                     "arrivals": {"process": "mmpp", "rate_rps": 4.0,
                                  "burst_factor": 8.0, "p_enter": 0.05,
                                  "p_exit": 0.15, "shape_seed": 1},
                     "prompt": {"dist": "lognormal", "median": 20,
                                "sigma": 0.8, "min": 8, "max": 60},
                     "output": {"dist": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 4, "max": 24}},
        "tinyclosed": {"loop": "closed", "clients": 4, "requests": 16,
                       "prompt": {"dist": "uniform", "min": 4, "max": 12},
                       "output": {"dist": "uniform", "min": 4, "max": 12}},
    }
    for name, c in (("tiny", chat), ("tinypipe", pipe)):
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(c))
        b["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    for name, t in traffic.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    cells = {"tiny.chat": ("tiny", "tinychat", 1, "qwen3-0.6b.docqa"),
             "tinypipe.closed": ("tinypipe", "tinyclosed", 4, None)}
    for cell, (cfg, mix, chips, like) in cells.items():
        b["workloads"].append({"name": cell, "config": cfg, "traffic": mix,
                               "chips": chips, "why": "test"})
        for m in b["end_to_end"] + b["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
        (root / "bench" / "checks" / f"{cell}.json").write_text(json.dumps(
            {"logit_gap": logit_gap, "mean_gap": mean_gap,
             "sample_tokens": sample_tokens,
             "sample_requests": 16}))
    (root / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    return root


def run(root: Path, cell: str, seed: int = 7, seconds: float = 3.0,
        probe=None, drain_s: float = 60.0):
    """One run of ``cell`` in the tree at ``root``, on this process's
    devices, past the look for a chip."""
    import time

    import jax

    from harness import cell as C
    from harness.spec import Cell
    t0 = time.perf_counter()
    return C.run(Cell(root, cell), seed, seconds, False, jax.devices(), PEAKS,
                 t0, log=lambda s: None, probe=probe, drain_s=drain_s)
