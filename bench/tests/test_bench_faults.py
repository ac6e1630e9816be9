"""Whole runs on the CPU, past the look for a chip, with the timed path
broken underneath: each fault a cell can have must turn ``correct`` false,
and the sound run must stay correct.

The one-chip faults patch the program's decode step and the scheduler's
sampling in this process.  The pipeline needs four devices, which a CPU
process only gets before JAX starts, so its runs go to one child process
(``_pipeline_child.py``) that reports each case as a JSON line.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tinytree import make, run

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make(tmp_path_factory.mktemp("faults"))


def _state_unchanged(monkeypatch):
    from repro.models import transformer as T
    real = T.decode_step

    def step(cfg, params, inputs, caches, **kw):
        logits, _ = real(cfg, params, inputs, caches, **kw)
        return logits, caches
    monkeypatch.setattr(T, "decode_step", step)


def _half_batch(monkeypatch):
    import jax.numpy as jnp

    from repro.models import transformer as T
    real = T.decode_step

    def step(cfg, params, inputs, caches, **kw):
        logits, new = real(cfg, params, inputs, caches, **kw)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:logits.shape[0] - half]]), new
    monkeypatch.setattr(T, "decode_step", step)


def _token_altered(monkeypatch):
    from repro.serving.scheduler import ContinuousBatcher
    real = ContinuousBatcher._sample

    def sample(self, req, ev):
        tok = real(self, req, ev)
        return (tok + 1) % len(ev.logits) if len(req.generated) % 3 == 1 \
            else tok
    monkeypatch.setattr(ContinuousBatcher, "_sample", sample)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


def test_sound_run_is_correct(tree):
    r = run(tree, "tiny.chat")
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["deployment"]["compiles_in_window"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"ttft_p50_s", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(tree, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    r = run(tree, "tiny.chat")
    assert not r["correct"], r["checks"]


@pytest.fixture(scope="module")
def pipeline_cases(tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(HERE / "_pipeline_child.py"),
                        str(tree)], capture_output=True, text=True,
                       timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return {d["case"]: d for d in map(json.loads, r.stdout.splitlines())}


def test_pipeline_sound_run_is_correct(pipeline_cases):
    r = pipeline_cases["sound"]
    assert r["correct"], r["checks"]
    assert r["stages"] == [1, 1, 1, 1]


@pytest.mark.parametrize("fault", ["no_exchange", "state_unchanged",
                                   "half_batch", "token_altered"])
def test_pipeline_fault_is_not_correct(pipeline_cases, fault):
    r = pipeline_cases[fault]
    assert not r["correct"], r["checks"]
