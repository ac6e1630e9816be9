"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 50 [--devices 8 --mesh-model 4] [--ckpt-dir ckpts/]

On this CPU container use ``--smoke`` (reduced same-family config) and
optionally ``--devices N`` to train data/tensor-parallel on host devices —
the same code path a real pod uses (pjit + logical sharding rules).  Full
configs are for TPU; their distributed lowering is proven by
``repro.launch.dryrun``.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--data-vocab", type=int, default=64,
                    help="token support of the synthetic stream")
    ap.add_argument("--corpus", default=None,
                    help="byte-level corpus file (default: synthetic)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="host device count for a (data, model) mesh")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis size when --devices is set")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    from repro.configs import get_config
    from repro.sharding import make_mesh
    from repro.training import AdamWConfig, DataConfig, TrainConfig, train

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    mesh = None
    if args.devices:
        assert args.devices % args.mesh_model == 0
        mesh = make_mesh((args.devices // args.mesh_model, args.mesh_model),
                         ("data", "model"))
    tcfg = TrainConfig(
        steps=args.steps, log_every=args.log_every,
        ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        grad_accum=args.grad_accum,
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=max(1, args.steps // 10),
                              total_steps=args.steps))
    dcfg = DataConfig(vocab_size=min(args.data_vocab, cfg.vocab_size),
                      seq_len=args.seq_len, batch=args.batch,
                      seed=args.seed, corpus_path=args.corpus)
    metrics = train(cfg, tcfg, dcfg, mesh=mesh, seed=args.seed)
    print(f"first loss {metrics['first_loss']:.4f} -> "
          f"final {metrics['final_loss']:.4f} "
          f"(mean last-10 {metrics['mean_last10']:.4f})")


if __name__ == "__main__":
    main()
