"""Unified runtime: backend protocol, continuous batching, planner->backend.

Multi-device pipeline tests re-exec in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (same pattern as
test_pipeline_runtime.py); single-device tests run inline.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(body: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # the children never ask for a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_tensor_pipeline_greedy_parity_under_batcher():
    """Acceptance: ContinuousBatcher over PipelineBackend (>= 2 stages,
    uneven periods-per-stage from a planner Plan) produces greedy outputs
    token-for-token identical to TensorBackend — including slot recycling
    (more requests than slots)."""
    run_subprocess("""
import jax, numpy as np
from repro.configs import get_config
from repro.core import pipeline as PL
from repro.core.devices import DeviceSpec, ClusterSpec, uniform_bandwidth, GIB
from repro.core.partition import solve_throughput
from repro.core.planner import build_problem
from repro.core.profile import Workload
from repro.models import transformer as T
from repro.runtime import PipelineBackend, TensorBackend
from repro.serving import ContinuousBatcher, Request, SamplingParams
from repro.sharding import make_mesh

cfg = get_config("qwen3-0.6b").reduced(n_layers=6)
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))

# heterogeneous 3-device cluster so the throughput DP plans uneven stages
devs = [DeviceSpec("big", 64 * GIB, 40e12, 500e9),
        DeviceSpec("mid", 64 * GIB, 20e12, 250e9),
        DeviceSpec("small", 64 * GIB, 10e12, 125e9)]
cluster = ClusterSpec(devs, uniform_bandwidth(3, 1e9))
prob = build_problem(cfg, cluster, Workload(dtype_bytes=2))
plan = solve_throughput(prob)
spec = PL.spec_from_plan(cfg, plan, 3)
assert spec.n_stages >= 2
assert len(set(spec.periods_per_stage)) > 1, spec   # genuinely uneven

mesh = make_mesh((1, 3), ("data", "model"))
rng = np.random.default_rng(0)
N, plen, gen = 7, 6, 5
prompts = rng.integers(0, cfg.vocab_size, (N, plen)).astype(np.int32)

def serve(backend):
    b = ContinuousBatcher(backend)
    for uid in range(N):
        b.submit(Request(prompts[uid], SamplingParams(max_tokens=gen),
                         uid=uid))
    done = b.run()
    assert sorted(done) == list(range(N))
    return np.stack([done[u].generated for u in range(N)])

pipe = serve(PipelineBackend(cfg, params, spec, mesh, n_slots=4, max_len=32))
tens = serve(TensorBackend(cfg, params, n_slots=4, max_len=32))
assert len(np.unique(tens)) > 2, "degenerate reference"
np.testing.assert_array_equal(pipe, tens)
""")


@pytest.mark.slow
def test_from_deployment_pipeline_matches_tensor():
    """planner Deployment -> running PipelineBackend in one call."""
    run_subprocess("""
import jax, numpy as np
from repro import runtime
from repro.configs import get_config
from repro.core.devices import tpu_pod_cluster
from repro.core.planner import plan_deployment
from repro.core.profile import Workload
from repro.models import transformer as T
from repro.serving import ContinuousBatcher, Request, SamplingParams

cfg = get_config("qwen3-0.6b").reduced(n_layers=4)
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
cluster = tpu_pod_cluster(n_chips=2)
dep = plan_deployment(cfg, cluster, Workload(dtype_bytes=2),
                      objective="throughput")
backend = runtime.from_deployment(dep, cluster, cfg, kind="pipeline",
                                  params=params, max_len=32)
prompts = np.random.default_rng(1).integers(
    0, cfg.vocab_size, (3, 4)).astype(np.int32)

def serve(be):
    b = ContinuousBatcher(be)
    for uid in range(3):
        b.submit(Request(prompts[uid], SamplingParams(max_tokens=4), uid=uid))
    done = b.run()
    return np.stack([done[u].generated for u in range(3)])

pipe = serve(backend)
tens = serve(runtime.TensorBackend(cfg, params, n_slots=3, max_len=32))
np.testing.assert_array_equal(pipe, tens)
""")


# --------------------------------------------------------------------------- #
# single-device: scheduler behavior over TensorBackend / SimBackend
# --------------------------------------------------------------------------- #

def _tiny_tensor_backend(n_slots=2, max_len=64):
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    cfg = get_config("qwen3-0.6b").reduced(n_layers=2)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, TensorBackend(cfg, params, n_slots=n_slots, max_len=max_len)


def test_scheduler_stats_staggered_arrival_completion():
    """Utilization accounting under staggered request arrival (at_step) and
    completion (different max_tokens): busy slot-steps land between the
    all-busy and single-slot bounds, and slots are recycled mid-flight."""
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    cfg, backend = _tiny_tensor_backend(n_slots=2)
    rng = np.random.default_rng(0)
    b = ContinuousBatcher(backend)
    for uid, (n_tok, at) in enumerate(
            [(6, 0), (2, 0), (4, 3), (3, 8)]):
        b.submit(Request(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                         SamplingParams(max_tokens=n_tok), uid=uid),
                 at_step=at)
    done = b.run()
    assert sorted(done) == [0, 1, 2, 3]
    for uid, (n_tok, _) in enumerate([(6, 0), (2, 0), (4, 3), (3, 8)]):
        assert len(done[uid].generated) == n_tok
    st = b.stats
    assert st.served == 4
    assert st.prefills >= 2                     # staggered admission waves
    assert st.slot_total_steps == 2 * st.decode_steps
    # staggered completion means some steps ran with an idle slot ...
    assert 0.0 < st.utilization < 1.0
    # ... but recycling keeps utilization above the no-recycling floor
    assert st.utilization > 0.5


def test_scheduler_per_request_sampling_state():
    """Mixed greedy + stochastic requests in one batch: greedy outputs match
    a pure-greedy run (per-request PRNG state is isolated)."""
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    cfg, backend = _tiny_tensor_backend(n_slots=2)
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)

    b1 = ContinuousBatcher(backend, seed=7)
    b1.submit(Request(prompts[0], SamplingParams(max_tokens=5), uid=0))
    b1.submit(Request(prompts[1], SamplingParams(max_tokens=5,
                                                 temperature=1.0), uid=1))
    d1 = b1.run()

    _, backend2 = _tiny_tensor_backend(n_slots=2)
    b2 = ContinuousBatcher(backend2, seed=7)
    b2.submit(Request(prompts[0], SamplingParams(max_tokens=5), uid=0))
    d2 = b2.run()
    np.testing.assert_array_equal(d1[0].generated, d2[0].generated)


def test_sim_backend_nobubbles_beats_bubbles():
    """SimBackend under the batcher reproduces the Fig. 10 ordering."""
    from repro.core.simulator import StageCosts
    from repro.runtime import SimBackend
    from repro.serving import ContinuousBatcher, Request, SamplingParams
    costs = StageCosts(prefill=np.array([.02, .01, .03]),
                       decode=np.array([.002, .001, .003]),
                       comm_prefill=np.array([.004, .004]),
                       comm_decode=np.array([.0005, .0005]),
                       return_comm=.0005)
    thr = {}
    for schedule in ("bubbles", "nobubbles"):
        be = SimBackend(costs, n_slots=6, schedule=schedule)
        b = ContinuousBatcher(be)
        for uid in range(6):
            b.submit(Request(np.zeros(4, np.int32),
                             SamplingParams(max_tokens=16), uid=uid))
        done = b.run()
        assert all(len(r.generated) == 16 for r in done.values())
        thr[schedule] = be.sim_result().throughput
    assert thr["nobubbles"] > thr["bubbles"] * 1.01


def test_backend_info_metadata():
    from repro.runtime import SimBackend
    from repro.core.simulator import StageCosts
    cfg, backend = _tiny_tensor_backend(n_slots=3, max_len=32)
    info = backend.info
    assert info.n_slots == 3 and info.max_len == 32
    assert info.cache_bytes_per_slot > 0
    assert info.cache_bytes == 3 * info.cache_bytes_per_slot
    assert info.param_bytes > 0
    assert not info.samples_in_backend
    sim = SimBackend(StageCosts(np.array([.1]), np.array([.01]),
                                np.zeros(0), np.zeros(0), 0.0), n_slots=2)
    assert sim.info.samples_in_backend
