"""Find a serving cell's knee: the highest offered rate its engine
sustains.  Run once on the chip when a cell is defined; the cell then
fixes its rate in its traffic file (the benchmark never searches).

    python3 bench/sweep.py --workload granite-8b-serve.conv \
        --seconds 30 --rates 0.5,1,1.5,2

One process: set-up once, then for each rate one open-loop window of
the cell's mix at that rate, after which the engine serves everything
it holds before the next rate starts.  Prints one line per rate:
offered and finished requests, the queue left at the window's close,
TTFT percentiles and the mean gap between tokens.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_PROCESS = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import numpy as np

    import harness as H
    cell = H.load_cell(args.workload)
    sys.path.insert(0, H.SRC)
    try:
        devs = H.chips(cell.chips)
    except H.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve = H.load_module(os.path.join(H.BENCH, "modes", "serve.py"),
                          "bench_mode_serve")
    ctx = H.RunCtx(cell=cell, seed=args.seed, seconds=args.seconds,
                   devices=devs, clock=H.CompileClock(),
                   tracer=H.Tracer(False, cell.name), t_process=T_PROCESS,
                   log=lambda m: print(m, file=sys.stderr))
    eng, cfg, _ = serve.build(ctx)
    gen = H.traffic_kind(cell.traffic)
    print(f"sweep {cell.name}: setup_s={time.perf_counter() - T_PROCESS:.1f}",
          flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        tt = gen.schedule(cell.traffic, args.seed, args.seconds, cfg.vocab,
                          rate=rate)
        reqs, times, t0, t_end, late, _ = serve.drive(
            ctx, eng, tt, args.seconds, drain_s=0.0)
        queued = len(eng.queue)
        ttft, itl, failed = serve.latency(reqs, times, tt, t0, t_end,
                                          args.seconds)
        fin = sum(r.done for r in reqs)
        eng.run()
        print(f"rate={rate} offered={len(tt)} finished_in_window={fin} "
              f"queued_at_close={queued} no_first_token={failed} "
              f"ttft_p50_ms={np.percentile(ttft, 50):.0f} "
              f"ttft_p90_ms={np.percentile(ttft, 90):.0f} "
              f"itl_mean_ms={1e3 * np.mean(itl):.1f} "
              f"late_max_ms={1e3 * max(late):.1f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
