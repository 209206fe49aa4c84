"""Production training launcher.

Selects an assigned architecture by ``--arch``, builds the mesh, and runs
the elastic training loop (checkpointing, straggler watch).  On this
container it is exercised with reduced configs / virtual devices; on a
TPU pod slice the same entrypoint runs per host under the usual
`JAX distributed` initialization (see --coordinator).

Examples:
  # reduced config, single host
  PYTHONPATH=src python -m repro.launch.train --arch granite_8b \\
      --reduced --mesh 1x1 --steps 20

  # 8 virtual devices, zero1 layout
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train --arch mixtral_8x7b \\
      --reduced --mesh 4x2 --param-mode zero1 --steps 20

  # production pod (on real hardware)
  python -m repro.launch.train --arch command_r_plus_104b \\
      --mesh 16x16 --param-mode fsdp --seq 4096 --global-batch 256 \\
      --coordinator $COORD:8476 --num-processes 64 --process-id $ID
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test sized config")
    ap.add_argument("--mesh", default="1x1", help="DPxTP or PODxDPxTP")
    ap.add_argument("--param-mode", default="fsdp",
                    choices=["dp", "zero1", "fsdp"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-r", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    # multi-host bring-up (real clusters)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.coordinator:
        jax.distributed.initialize(args.coordinator, args.num_processes,
                                   args.process_id)

    from repro.configs import get_config, get_reduced
    from repro.data.pipeline import DataConfig
    from repro.runtime.elastic import ElasticConfig, ElasticRunner
    from repro.train.optimizer import OptConfig

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    dims = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    oc = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                   total_steps=args.steps)
    ec = ElasticConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       param_mode=args.param_mode, grad_r=args.grad_r)
    dc = DataConfig(seq_len=args.seq, global_batch=args.global_batch)
    runner = ElasticRunner(cfg, oc, ec, dc, dims, axes=axes)
    n = sum(x.size for x in jax.tree.leaves(runner.params))
    print(f"[train] {cfg.name}: {n/1e6:.1f}M params, mesh={args.mesh}, "
          f"mode={args.param_mode}")
    logs = runner.run(args.steps)
    print(f"[train] done: loss {logs[0]['loss']:.4f} -> "
          f"{logs[-1]['loss']:.4f} over {args.steps} steps")
    runner.ckpt.wait()


if __name__ == "__main__":
    main()
