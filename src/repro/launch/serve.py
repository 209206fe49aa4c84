"""Serving launcher: continuous-batching generation with the production
engine (paged KV cache, per-slot prefill/decode, streaming).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch granite_8b --reduced \\
      --requests 6 --max-new 8
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma_2b \\
      --reduced --mesh 2x4
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV cache block size (tokens)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated")
    ap.add_argument("--decode-collectives", default="plan",
                    choices=("plan", "xla"),
                    help="TP decode psum/gather: ExecPlan schedules "
                         "picked by autotune.choose() (default) or "
                         "XLA natives")
    ap.add_argument("--tuning", action="store_true",
                    help="consult the measured tuning table "
                         "(populate with `python benchmarks/run.py tune`)")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import numpy as np
    from repro.configs import get_config, get_reduced
    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.models.model import init_params
    from repro.serve.engine import Engine, Request

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if not cfg.is_decoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    dims = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dims, ("data", "model")[-len(dims):]
                     if len(dims) <= 2 else ("pod", "data", "model"))
    pc = parallel_config_for(mesh, param_mode="dp", tuning=args.tuning)
    params, _ = init_params(cfg, pc, jax.random.PRNGKey(0))
    eng = Engine(cfg, pc, mesh, params, batch_slots=args.batch_slots,
                 max_len=args.max_len, prefill_chunk=args.prefill_chunk,
                 block_size=args.block_size,
                 temperature=args.temperature,
                 decode_collectives=args.decode_collectives)
    stream = (lambda r, t: print(f"[serve] req {r.uid} += {t}")) \
        if args.stream else None
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(4, 16)))
                    .astype(np.int32), max_new_tokens=args.max_new,
                    stream=stream)
            for _ in range(args.requests)]
    eng.generate(reqs)
    for i, r in enumerate(reqs):
        print(f"[serve] req {i}: {len(r.prompt)} prompt -> {r.out_tokens}")
    for op, nbytes, choice in eng.decode_choices:
        print(f"[serve] decode {op}: {nbytes}B -> {choice.kind}(r="
              f"{choice.r}) source={choice.source}")


if __name__ == "__main__":
    main()
