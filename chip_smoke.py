#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU, through the entry points a
user calls.  Run it from the root of a checkout:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip: qwen3-0.6b at its published widths (28 layers, d_model 1024,
vocab 151936; random bf16 weights from a seed) serves 8 greedy requests
(prompts of 32-384 tokens, 32 new tokens each) through ``LLM`` ->
``ContinuousBatcher`` -> ``TensorBackend`` on the paged KV pool with the
Pallas kernels, on fewer slots than requests.  The same backend with
``impl="xla"`` (the jnp reference) is then fed the same tokens, and the
prefill and decode logits must agree.

``--four-chips``: llama2-7b at its published widths is built directly in
its sharded layout and served through ``LLM.from_plan(kind="pipeline")`` on
the planner's stages, one chip each; its logits are compared with a
``TensorBackend`` on a (1, 4) mesh of the same chips and the same weights,
and no chip may hold the whole model.  No other phase runs.

Every line but the last is a smoke reading, not a benchmark number.  The
last line is one JSON object, ``{"ok": true, "device": {...}}``; a failed
check exits non-zero before it, and so does a run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
#: max |logit difference| allowed, as a share of the reference's max |logit|
LOGIT_TOL = 0.05


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def teacher_forced_logits(backend, prompts, conts) -> np.ndarray:
    """Logits of ``backend`` fed fixed tokens through the backend protocol.

    Prompt ``i`` is prefilled into slot ``i`` (left-padded to the
    batcher's power-of-two bucket, so the compiled prefill is reused), then
    ``conts[i]`` is fed one token per quantum.  Returns float32
    ``[n, 1 + conts.shape[1], V]``: the logits after the last prompt token
    and after each fed token.  Works for every backend: the pipeline emits a
    slot's logits only when they come back round the ring."""
    n, steps = conts.shape
    longest = max(len(p) for p in prompts)
    width = 1 << max(longest - 1, 0).bit_length()
    padded = np.zeros((n, width), np.int32)
    for i, p in enumerate(prompts):
        padded[i, width - len(p):] = p
    got = {s: [] for s in range(n)}

    def take(events):
        for ev in events:
            got[ev.slot].append(np.asarray(ev.logits, np.float32))

    take(backend.prefill(list(range(n)), padded, [len(p) for p in prompts]))
    for _ in range(n * (width + steps + 8) + 8):
        if all(len(got[s]) > steps for s in range(n)):
            break
        take(backend.decode_step({s: int(conts[s, len(got[s]) - 1])
                                  for s in range(n)
                                  if 1 <= len(got[s]) <= steps}))
    else:
        fail(f"backend produced {[len(g) for g in got.values()]} logits "
             f"rows, wanted {steps + 1} per slot")
    for s in range(n):
        backend.free_slot(s)
    return np.stack([np.stack(got[s][:steps + 1]) for s in range(n)])


def compare_logits(name: str, got: np.ndarray, ref: np.ndarray) -> None:
    """Print and check max |got - ref| against LOGIT_TOL x max |ref|."""
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        fail(f"{name}: non-finite logits")
    scale = float(np.abs(ref).max())
    tol = LOGIT_TOL * scale
    for part, sl in (("prefill", np.s_[:, :1]), ("decode", np.s_[:, 1:])):
        diff = float(np.abs(got[sl] - ref[sl]).max())
        print(f"  {name} {part} logits: max|diff| {diff!r} "
              f"(tolerance {tol!r} = {LOGIT_TOL} x max|ref logit| {scale!r})")
        if not diff <= tol:
            fail(f"{name} {part} logits differ by {diff!r} > {tol!r}")


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def assert_compiled_kernel(jitted, *args) -> None:
    """The program really holds a compiled Pallas kernel (on a TPU the
    wrappers choose compiled over interpreted kernels)."""
    if "tpu_custom_call" not in jitted.lower(*args).compile().as_text():
        fail("the compiled decode program holds no tpu_custom_call")
    print("  compiled decode program holds a tpu_custom_call "
          "(Pallas kernel compiled, not interpreted)")


def one_chip(cfg, lens=(32, 61, 97, 130, 170, 222, 300, 384),
             new_tokens: int = 32, n_slots: int = 4, max_len: int = 512,
             check_idx=(0, 2, 4, 5), check_steps: int = 4) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    from repro.serving import LLM, SamplingParams

    dev = jax.devices()[0]
    params, _ = T.init_params(cfg, jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab_size}, "
          f"{n_params} params in {cfg.dtype}")
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    sp = SamplingParams(max_tokens=new_tokens)          # greedy

    def backend(impl):
        return TensorBackend(cfg, params, n_slots=n_slots, max_len=max_len,
                             cache_layout="paged", impl=impl)

    pallas = backend("pallas")
    if pallas.info.attn_impl != "pallas":
        fail(f"backend runs attn_impl={pallas.info.attn_impl!r}")
    llm = LLM.from_backend(pallas, seed=SEED)
    t0 = time.perf_counter()
    cold = llm.generate(prompts, sp)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = llm.generate(prompts, sp)
    t_warm = time.perf_counter() - t0
    n_tok = sum(len(o.tokens) for o in warm)
    print(f"served {len(prompts)} requests (prompt lengths {list(lens)}, "
          f"{new_tokens} new tokens each) on {n_slots} slots, paged KV "
          f"({pallas.info.total_blocks} blocks of {pallas.info.block_size}), "
          f"impl=pallas: {n_tok} tokens; stats {llm.stats}")
    if n_tok != len(prompts) * new_tokens:
        fail(f"{n_tok} tokens served, wanted {len(prompts) * new_tokens}")
    if [o.tokens for o in cold] != [o.tokens for o in warm]:
        fail("two identical greedy runs produced different tokens")
    print(f"  smoke timing, not a benchmark: first run (compiles included) "
          f"{t_cold!r} s, second run {t_warm!r} s")
    assert_compiled_kernel(pallas._decode_fn, params,
                           jnp.zeros(n_slots, jnp.int32), pallas.caches,
                           jnp.zeros(n_slots, bool))

    xla = backend("xla")
    ref = LLM.from_backend(xla, seed=SEED).generate(prompts, sp)
    same = sum(int(a == b) for o, r in zip(warm, ref)
               for a, b in zip(o.tokens, r.tokens))
    print(f"  greedy tokens equal to the impl=xla run: {same}/{n_tok} "
          f"(reported only: random weights give near-ties)")

    chk = [prompts[i] for i in check_idx]
    conts = rng.integers(0, cfg.vocab_size, (len(chk), check_steps))
    compare_logits("pallas vs xla",
                   teacher_forced_logits(pallas, chk, conts),
                   teacher_forced_logits(xla, chk, conts))
    print(f"  peak_bytes_in_use {peak_bytes(dev)} on {dev.device_kind}")


def four_chips(cfg, lens=(40, 23, 31, 17), new_tokens: int = 16,
               max_len: int = 128, check_steps: int = 4) -> None:
    import jax

    from repro.core.devices import tpu_pod_cluster
    from repro.core.profile import Workload
    from repro.models import transformer as T
    from repro.runtime import TensorBackend
    from repro.serving import LLM, SamplingParams
    from repro.sharding import make_mesh

    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
    params = T.init_params_on_mesh(cfg, jax.random.PRNGKey(SEED), mesh)
    model_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {model_bytes} bytes of {cfg.dtype} "
          f"weights, built sharded over {len(mesh.devices.flat)} chips")
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    t0 = time.perf_counter()
    llm = LLM.from_plan(
        cfg, tpu_pod_cluster(n_chips=4),
        Workload(prompt_len=max(lens), gen_tokens=new_tokens, dtype_bytes=2),
        kind="pipeline", params=params, mesh=mesh, n_slots=4,
        max_len=max_len, cache_layout="paged", impl="pallas", seed=SEED)
    pipe = llm.backend
    print(f"planner stages (periods per stage): "
          f"{pipe.spec.periods_per_stage} on devices "
          f"{[d.id for d in mesh.devices.flat]}")
    outs = llm.generate(prompts, SamplingParams(max_tokens=new_tokens))
    n_tok = sum(len(o.tokens) for o in outs)
    if n_tok != len(prompts) * new_tokens:
        fail(f"{n_tok} tokens served, wanted {len(prompts) * new_tokens}")
    print(f"  pipeline served {len(prompts)} requests, {n_tok} tokens; "
          f"smoke timing, not a benchmark: {time.perf_counter() - t0!r} s "
          f"including compiles")

    stage_bytes = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(pipe.stage_params):
        for shard in leaf.addressable_shards:
            stage_bytes[shard.device] += shard.data.nbytes
    for d in mesh.devices.flat:
        print(f"  chip {d.id}: stage weights {stage_bytes[d]} bytes, "
              f"peak_bytes_in_use {peak_bytes(d)}")
        if stage_bytes[d] >= model_bytes / 2:
            fail(f"chip {d.id} holds {stage_bytes[d]} of {model_bytes} "
                 f"bytes of weights")

    conts = rng.integers(0, cfg.vocab_size, (len(prompts), check_steps))
    got = teacher_forced_logits(pipe, prompts, conts)
    tens = TensorBackend(cfg, params, n_slots=4, max_len=max_len, mesh=mesh,
                         cache_layout="paged", impl="xla")
    compare_logits("pipeline (pallas) vs 4-chip tensor (xla)", got,
                   teacher_forced_logits(tens, prompts, conts))
    for d in mesh.devices.flat:
        print(f"  chip {d.id}: peak_bytes_in_use {peak_bytes(d)} "
              f"(whole model {model_bytes})")
        if not 0 <= peak_bytes(d) < model_bytes:
            fail(f"chip {d.id} peaked at {peak_bytes(d)} bytes, the whole "
                 f"model is {model_bytes}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the llama2-7b pipeline on four chips and "
                         "its tensor-parallel comparison")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    from repro.configs import get_config

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform {dev.platform!r} "
             f"({dev.device_kind}); this smoke run needs a TPU chip")
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(get_config("llama2-7b"))
    else:
        one_chip(get_config("qwen3-0.6b"))
    print(f"smoke wall time {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
