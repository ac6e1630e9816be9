"""End-to-end driver: serve variable-length requests through the ``LLM``
facade on the EdgeShard shard_map pipeline (no-bubbles decode over 8 XLA
devices).

This is the paper's deployment mode on the TPU-native runtime:
1. ``LLM.from_plan`` plans an (uneven) stage partition with the throughput
   DP and materializes it as a running ``PipelineBackend`` (params restacked
   into per-stage slabs) behind one serving facade,
2. ``generate()`` streams requests of *different prompt lengths* through the
   no-bubbles tick protocol — more requests than micro-batch slots, so slots
   are recycled mid-flight, and admission buckets prompts by length (no
   caller-side padding),
3. cross-check every generated token against the TensorBackend (single
   engine) serving the identical requests,
4. demo the streaming interface on the tensor engine.

A CPU example: it runs on 8 fake host devices, so it must run in its own
process (on a TPU, ``chip_smoke.py --four-chips`` runs the pipeline on
real chips):
    PYTHONPATH=src python examples/serve_pipeline.py
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np

from repro import runtime
from repro.configs import get_config
from repro.core.devices import tpu_pod_cluster
from repro.core.profile import Workload
from repro.models import transformer as T
from repro.serving import LLM, SamplingParams


def main():
    cfg = get_config("qwen3-0.6b").reduced(n_layers=8, max_d_model=256)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    n_stages = 4

    # 1. plan (paper's throughput DP over a 4-chip homogeneous "cluster"
    #    profile) -> running pipeline backend -> serving facade, one call
    llm = LLM.from_plan(cfg, tpu_pod_cluster(n_chips=n_stages),
                        Workload(dtype_bytes=2), objective="throughput",
                        kind="pipeline", params=params, max_len=64)
    print(f"stage layout (periods per stage): "
          f"{llm.backend.spec.periods_per_stage}")

    # 2. continuous batching: 8 variable-length requests over 4 micro-batch
    #    slots (admission buckets by length; nobody pads)
    n_req, gen = 8, 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in rng.integers(3, 7, n_req)]
    sp = SamplingParams(max_tokens=gen)
    t0 = time.time()
    outs = llm.generate(prompts, sp)
    dt = time.time() - t0
    total = sum(o.n_generated for o in outs)
    print(f"pipeline: {total} tokens for prompt lengths "
          f"{[o.n_prompt for o in outs]} in {dt:.2f}s "
          f"({total / dt:.1f} tok/s on CPU-interpreted SPMD) — {llm.stats}")

    # 3. verify against the tensor backend serving the same requests
    ref_llm = LLM.from_backend(
        runtime.TensorBackend(cfg, params, n_slots=4, max_len=64))
    refs = ref_llm.generate(prompts, sp)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o.tokens, r.tokens)
    print("all pipeline tokens match the tensor backend — OK")

    # 4. streaming: tokens surface the step they decode, interleaved across
    #    requests
    stream_llm = LLM.from_backend(
        runtime.TensorBackend(cfg, params, n_slots=2, max_len=64))
    events = list(stream_llm.stream(prompts[:2], SamplingParams(max_tokens=4)))
    for ev in events:
        print(f"  step {ev.step} req {ev.uid} tok[{ev.index}]={ev.token}"
              + (f" <{ev.finish_reason}>" if ev.finished else ""))
    assert sum(ev.finished for ev in events) == 2


if __name__ == "__main__":
    main()
