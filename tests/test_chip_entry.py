"""The chip entry points' guards, checked on the CPU: where the compile
cache goes, ``chip_smoke.py`` refusing to run without a TPU, and the
serving launcher keeping fake host devices to the CPU platform."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_location(monkeypatch, env_dir):
    """The environment's directory wins and nothing is set in code;
    otherwise one fixed directory inside the checkout."""
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, PYTHONPATH="src", **env))


def test_chip_smoke_refuses_without_tpu():
    r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_serve_fakes_devices_only_on_cpu():
    """--devices fakes host devices; off the CPU platform it is refused
    before JAX starts (so a chip is never asked for)."""
    r = _run(["-m", "repro.launch.serve", "--arch", "qwen3-0.6b", "--smoke",
              "--mode", "pipeline", "--devices", "4"], JAX_PLATFORMS="tpu")
    assert r.returncode != 0
    assert "only the CPU platform has" in r.stderr
    assert "served" not in r.stdout
