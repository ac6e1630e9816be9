"""Serving launcher: request-lifecycle generation through the ``LLM`` facade.

Both modes route through ``serving.LLM`` (continuous batching over an
``repro.runtime.InferenceBackend``) — the launcher owns no generation loop
and never pads a prompt:

- ``--mode tp``        TensorBackend (pjit tensor-parallel / single device),
- ``--mode pipeline``  the paper's deployment mode — ``LLM.from_plan`` runs
  the throughput DP over a cluster profile and materializes the (possibly
  uneven) stage plan as a running no-bubbles pipeline in one call.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --mode tp --batch 4 --gen 16 [--kvint8] [--stream] [--varlen] \
        [--cache-layout paged --impl pallas] \
        [--cache-layout paged --spec-k 4 --draft ngram] \
        [--policy edf --ttft-slo 8 --e2e-slo 64] \
        [--inject-faults transient@decode_step:5x2 --max-retries 3]
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --mode pipeline --stages 4            # devices default to --stages
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="tp", choices=["tp", "pipeline"])
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to serve")
    ap.add_argument("--slots", type=int, default=0,
                    help="backend slots (default: batch for tp, "
                         "stages for pipeline)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--varlen", action="store_true",
                    help="vary prompt lengths in [prompt_len/2, prompt_len] "
                         "(bucketed admission serves them in one batch)")
    ap.add_argument("--min-bucket", type=int, default=1,
                    help="admission bucket floor (pow-2 padding; masked "
                         "prefill makes any bucket size output-identical, "
                         "so this is purely a compile-shape knob)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--kvint8", action="store_true",
                    help="int8 KV cache (EXPERIMENTS.md §Perf-A3)")
    ap.add_argument("--cache-layout", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="KV layout: worst-case per-slot rings, or block "
                         "tables over a shared pool (vLLM-style)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged layout)")
    ap.add_argument("--impl", default="xla",
                    choices=["xla", "chunked", "pallas"],
                    help="attention implementation: pure-jnp reference, "
                         "chunked online-softmax prefill, or the Pallas "
                         "kernels (paged decode fuses the block-table "
                         "indirection; interpreted on CPU, compiled on TPU)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="shared pool size in blocks; 0 = worst-case "
                         "provisioning (no overcommit).  Smaller pools "
                         "overcommit: admission goes block-budgeted and "
                         "exhaustion preempts the youngest request")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed shared-prefix KV reuse over the "
                         "paged pool (copy-on-write block adoption at "
                         "admission; requires --cache-layout paged and an "
                         "all-attention model, else silently ignored)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: stream prompts through prefill "
                         "this many tokens per scheduler quantum, "
                         "interleaved with decode (0 = monolithic)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: verify up to K tokens per "
                         "quantum (the last emitted token + K-1 drafts) in "
                         "one multi-query pass; greedy outputs stay "
                         "bit-identical.  Needs --cache-layout paged; "
                         "0/1 = off")
    ap.add_argument("--draft", default="ngram",
                    help="draft source for --spec-k: 'ngram' (prompt-lookup "
                         "self-speculation, default), 'ngram:<max>', or "
                         "'off' (verify quantum carries no drafts)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same random prefix of this "
                         "many tokens (demo/validation workload for "
                         "--prefix-cache)")
    ap.add_argument("--expect-prefix-hits", action="store_true",
                    help="exit nonzero unless the run recorded at least one "
                         "prefix-cache hit (CI smoke guard)")
    ap.add_argument("--devices", type=int, default=0,
                    help="fake XLA host devices (pipeline mode defaults "
                         "to --stages)")
    ap.add_argument("--stages", type=int, default=4,
                    help="pipeline stages (pipeline mode)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they decode (streaming API)")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "priority", "edf"],
                    help="admission/preemption policy (serving.sched): "
                         "arrival order, service-class priority, or "
                         "earliest-deadline-first over --ttft-slo/--e2e-slo")
    ap.add_argument("--priority", type=int, default=None,
                    help="service-class priority for every request "
                         "(higher = served first under --policy priority)")
    ap.add_argument("--ttft-slo", type=int, default=None,
                    help="first-token deadline in scheduler steps from "
                         "arrival (drives --policy edf; misses are counted "
                         "in the scheduler stats)")
    ap.add_argument("--e2e-slo", type=int, default=None,
                    help="completion deadline in scheduler steps from "
                         "arrival (see --ttft-slo)")
    ap.add_argument("--inject-faults", default="",
                    help="deterministic fault schedule wrapped around the "
                         "backend (runtime.faults), e.g. "
                         "'transient@decode_step:5x2' or 'timeout@any~0.01' "
                         "— exercises the scheduler's retry/backoff path "
                         "(tp mode only)")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="consecutive transient backend failures absorbed "
                         "with exponential backoff before the scheduler "
                         "gives up (BackendError taxonomy; docs/runtime.md "
                         "'Fault tolerance')")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.inject_faults and args.mode != "tp":
        ap.error("--inject-faults wraps the single tp-mode backend; chaos "
                 "over a multi-backend fleet is benchmarks/chaos_bench.py")

    if args.policy != "fifo" and args.priority is None \
            and args.ttft_slo is None and args.e2e_slo is None:
        ap.error(
            f"--policy {args.policy} without --priority/--ttft-slo/--e2e-slo "
            f"degenerates to FIFO (every request gets the default service "
            f"class): pass the service-class flags the policy orders by, or "
            f"drop --policy")
    if args.policy == "edf" and args.ttft_slo is None \
            and args.e2e_slo is None:
        ap.error("--policy edf orders by deadlines: pass --ttft-slo and/or "
                 "--e2e-slo (steps from arrival); --priority alone only "
                 "affects --policy priority")

    # fake host devices exist only on the CPU platform; on a chip the
    # launcher uses the real jax.devices()
    on_cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
    if args.devices and not on_cpu:
        ap.error("--devices fakes XLA host devices, which only the CPU "
                 "platform has: run with JAX_PLATFORMS=cpu, or drop --devices "
                 "to serve on the real devices")
    if args.mode == "pipeline" and on_cpu and not args.devices:
        args.devices = args.stages      # one fake XLA device per stage
    if args.mode == "pipeline" and on_cpu and args.devices < args.stages:
        ap.error(f"--mode pipeline plans {args.stages} stages and needs one "
                 f"XLA device per stage: pass --devices >= {args.stages}, "
                 f"lower --stages, or drop --devices to default it")
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    import dataclasses

    import jax
    import numpy as np

    from repro import runtime
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as T
    from repro.serving import LLM, SamplingParams
    from repro.sharding import make_mesh

    enable_compile_cache()
    devices = jax.devices()
    if args.mode == "pipeline" and len(devices) < args.stages:
        ap.error(f"--mode pipeline plans {args.stages} stages, one device "
                 f"each, but {devices[0].platform} has {len(devices)}: pass "
                 f"--stages {len(devices)} or fewer (on the CPU, set "
                 f"JAX_PLATFORMS=cpu to get one host device per stage)")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.kvint8:
        cfg = dataclasses.replace(cfg, kv_dtype="int8")
    key = jax.random.PRNGKey(args.seed)
    if args.mode == "pipeline":
        # built in place across the stage devices: no device ever holds
        # the whole model (each stage's slab is then moved to its device)
        params = T.init_params_on_mesh(
            cfg, key, make_mesh((1, args.stages), ("data", "model")))
    else:
        params, _ = T.init_params(cfg, key)
    rng = np.random.default_rng(args.seed)
    lens = [args.prompt_len] * args.batch
    if args.varlen:
        lens = [int(x) for x in rng.integers(
            max(args.prompt_len // 2, 1), args.prompt_len + 1, args.batch)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    if args.shared_prefix:
        if args.shared_prefix >= min(lens):
            ap.error(f"--shared-prefix {args.shared_prefix} must be shorter "
                     f"than every prompt (min {min(lens)})")
        pre = rng.integers(0, cfg.vocab_size,
                           args.shared_prefix).astype(np.int32)
        prompts = [np.concatenate([pre, p[args.shared_prefix:]])
                   for p in prompts]

    kv_kw = dict(cache_layout=args.cache_layout,
                 block_size=args.block_size,
                 num_blocks=args.kv_blocks or None,
                 prefix_cache=args.prefix_cache)
    chunk = args.prefill_chunk or None
    if args.mode == "tp":
        mesh = None
        if args.devices:
            mesh = make_mesh((1, args.devices), ("data", "model"))
        backend = runtime.TensorBackend(
            cfg, params, n_slots=args.slots or args.batch,
            max_len=args.max_len, mesh=mesh, impl=args.impl, **kv_kw)
        if args.inject_faults:
            backend = runtime.FaultInjectionBackend(
                backend, args.inject_faults, seed=args.seed)
        llm = LLM.from_backend(
            backend,
            seed=args.seed, min_bucket=args.min_bucket, prefill_chunk=chunk,
            policy=args.policy, spec_k=args.spec_k, draft=args.draft,
            max_retries=args.max_retries)
    else:
        # planner -> backend -> serving in one call: the DP chooses the
        # (possibly uneven) stage layout over a homogeneous cluster profile
        # of --stages chips; request-granular slots use lanes=1, so the
        # mesh carries stages only (data-parallel lanes are a ROADMAP item)
        from repro.core.devices import tpu_pod_cluster
        from repro.core.profile import Workload
        llm = LLM.from_plan(
            cfg, tpu_pod_cluster(n_chips=args.stages),
            Workload(prompt_len=args.prompt_len, gen_tokens=args.gen,
                     dtype_bytes=2),
            objective="throughput", kind="pipeline", params=params,
            n_slots=args.slots or None, max_len=args.max_len, seed=args.seed,
            min_bucket=args.min_bucket, impl=args.impl, prefill_chunk=chunk,
            policy=args.policy, spec_k=args.spec_k, draft=args.draft, **kv_kw)
        n_stages = llm.backend.spec.n_stages
        if args.devices > n_stages:
            print(f"note: using {n_stages} of {args.devices} devices "
                  f"(stage axis only; no data-parallel lanes yet)")
        print(f"planned stages (periods per stage): "
              f"{llm.backend.spec.periods_per_stage}")

    # every user-passed flag that ends up inert gets one explicit line —
    # "silently ignored" cost real debugging time (see docs/runtime.md)
    def _inert(flag, why):
        print(f"note: {flag} has no effect on this deployment: {why}")

    info = llm.backend.info
    if args.prefix_cache and not info.prefix_caching:
        _inert("--prefix-cache",
               f"backend reports prefix_caching=False over cache_layout="
               f"{info.cache_layout!r} (needs --cache-layout paged and an "
               f"all-attention model)")
    if args.cache_layout != "paged":
        if args.block_size != 16:
            _inert("--block-size", "only the paged layout blocks the KV pool")
        if args.kv_blocks:
            _inert("--kv-blocks",
                   "only the paged layout has a shared block pool")
    if args.spec_k >= 2 and not info.spec_decode:
        _inert("--spec-k",
               f"backend reports spec_decode=False (cache_layout="
               f"{info.cache_layout!r}); serving plain decode")
    if args.draft != "ngram" and args.spec_k < 2:
        _inert("--draft", "draft sources only feed --spec-k >= 2")
    if args.priority is not None and args.policy == "fifo":
        _inert("--priority", "FIFO ignores service classes; pass "
                             "--policy priority")

    sp = SamplingParams(max_tokens=args.gen,
                        priority=args.priority or 0,
                        ttft_slo=args.ttft_slo, e2e_slo=args.e2e_slo)
    t0 = time.time()
    if args.stream:
        outs = {}
        for ev in llm.stream(prompts, sp):
            print(f"  step {ev.step:4d} req {ev.uid} tok[{ev.index}]="
                  f"{ev.token}" + (f" <{ev.finish_reason}>"
                                   if ev.finished else ""))
            if ev.finished:
                outs[ev.uid] = llm.poll(ev.uid)
        outs = list(outs.values())
    else:
        outs = llm.generate(prompts, sp)
    dt = time.time() - t0
    total = sum(o.n_generated for o in outs)
    print(f"served {len(outs)} requests ({[o.n_prompt for o in outs]} prompt "
          f"tokens), {total} generated in {dt:.2f}s ({total / dt:.1f} tok/s) "
          f"— {llm.stats}")
    st = llm.stats
    if args.inject_faults:
        inj = llm.backend.injected
        print(f"  faults ({args.inject_faults}): injected "
              f"{ {k: v for k, v in inj.items() if v} }, "
              f"absorbed with {st.retries} retries "
              f"({st.failures} failures) — backend {llm.backend.health()}")
    if st.prefix_hits or st.prefill_chunks:
        print(f"  prefix cache: {st.prefix_hits} hits "
              f"({st.prefix_hit_tokens} prompt tokens reused); "
              f"{st.prefill_chunks} prefill chunk passes")
    if st.spec_drafted:
        print(f"  spec decode (k={args.spec_k}, draft={args.draft}): "
              f"{st.spec_accepted}/{st.spec_drafted} drafts accepted "
              f"({st.spec_acceptance:.0%}), {total} tokens in "
              f"{st.decode_steps} verify quanta "
              f"({total / max(st.decode_steps, 1):.2f} tokens/quantum)")
    if args.ttft_slo is not None or args.e2e_slo is not None:
        met = sum(1 for o in outs if o.slo_met())
        print(f"  SLO ({args.policy}): {met}/{len(outs)} met "
              f"(ttft_misses={st.ttft_misses}, e2e_misses={st.e2e_misses}, "
              f"slo_preemptions={st.slo_preemptions})")
    for o in outs[:4]:
        ttft = f"{o.timing.ttft_s:.2f}s" if o.timing.ttft_s else "-"
        print(f"  req {o.uid}: {o.finish_reason} after {o.n_generated} toks "
              f"(ttft {ttft}) {o.tokens[:10]}")
    if args.expect_prefix_hits and not st.prefix_hits:
        raise SystemExit(
            "--expect-prefix-hits: no prefix-cache hits were recorded "
            f"(prefix_caching={llm.backend.info.prefix_caching}); check "
            "--cache-layout paged / --prefix-cache / --shared-prefix")


if __name__ == "__main__":
    main()
