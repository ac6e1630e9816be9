"""The entry point's refusals, and the harness finding a cell, a traffic
mix, a configuration and a metric that are only dropped in as files."""
import json
import os
import shutil
import subprocess
import sys

from tinytree import BENCH, ROOT, make
from harness.spec import Cell


def _run(cwd, *args, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH="", **env))


def test_refuses_without_a_tpu():
    r = _run(ROOT, "--workload", "qwen3-0.6b.docqa", "--seed", "1",
             "--seconds", "1", "--trace", "0", JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_refuses_an_unknown_cell():
    r = _run(ROOT, "--workload", "nope", "--seed", "1", "--seconds", "1",
             JAX_PLATFORMS="cpu")
    assert r.returncode == 2 and r.stdout.strip() == ""


def test_refuses_in_a_tree_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has nothing to
    serve: the run fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, "--workload", "qwen3-0.6b.docqa", "--seed", "1",
             "--seconds", "1", JAX_PLATFORMS="cpu")
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_unknown_device_kind_is_refused(tmp_path):
    root = make(tmp_path)
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    assert "cpu" not in peaks
    assert Cell(root, "tiny.chat").peaks_table.get("cpu") is None


def test_dropped_in_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix, cell and metric need new files and
    BENCHMARK.json entries only."""
    root = make(tmp_path)
    (root / "bench" / "metrics" / "twice_setup_s.py").write_text(
        "def read(run):\n    return 2 * run.setup_s\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "twice_setup_s", "unit": "s",
                           "better": "lower", "source": "host_clock",
                           "layer": "backend", "moves": "setup_s",
                           "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = Cell(root, "tiny.chat")
    assert cell.config["name"] == "tiny"
    assert cell.traffic["prompt"]["max"] == 60
    assert "twice_setup_s" in cell.metrics(trace=True)
    assert "twice_setup_s" not in Cell(root, "qwen3-0.6b.docqa").metrics(True)

    class R:
        setup_s = 4.0
    assert cell.reader("twice_setup_s")(R) == 8.0
    # the shipped cells are untouched by the additions
    assert set(Cell(ROOT, "qwen3-0.6b.docqa").metrics(False)) == {
        "ttft_p50_s", "tpot_p95_ms", "setup_s"}
