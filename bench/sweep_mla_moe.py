"""Find a serving cell's knee, set up by the mode its configuration
names (``serve_mla_moe`` for the DeepSeek-V2 cell; ``sweep.py`` sets up
through ``serve``): the highest offered rate its engine sustains.  Run
once on the chip when a cell is defined; the cell then
fixes its rate in its traffic file (the benchmark never searches).

    python3 bench/sweep_mla_moe.py --workload deepseek-v2-lite-serve.chat \
        --warm 45 --seconds 90 --rates 1.4,1.7,2.0,2.3

One process: set-up once, then the rates in the order given, with no
drain between them.  Each rate runs its open-loop mix for ``--warm``
seconds, which should outlast a request's life in a slot, so that the
engine reaches the rate's steady state from whatever the rate before
left; the steady stretch of ``--seconds`` that follows is measured.  A
window that starts from an empty engine cannot queue anything while its
slots fill, so it measures the slot count rather than the knee.

Prints one line per rate, over the steady stretch: requests offered and
finished per second, output tokens per second, the mean number of busy
slots, the queue (requests not yet admitted) at the stretch's start and
end and its mean, the share of chunk ticks, the mean tick, TTFT
percentiles and the mean gap between tokens.  Below the knee finished
follows offered and the queue stays near empty; above it the finished
rate is the capacity and the queue grows through the stretch.
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
    ap.add_argument("--warm", type=float, default=45.0)
    ap.add_argument("--seconds", type=float, default=90.0)
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
    mode = H.load_module(os.path.join(H.BENCH, "modes",
                                      cell.config["mode"] + ".py"),
                         "bench_mode_" + cell.config["mode"])
    ctx = H.RunCtx(cell=cell, seed=args.seed, seconds=args.seconds,
                   devices=devs, clock=H.CompileClock(),
                   tracer=H.Tracer(False, cell.name), t_process=T_PROCESS,
                   log=lambda m: print(m, file=sys.stderr))
    eng, cfg, _ = mode.build(ctx)
    gen = H.traffic_kind(cell.traffic)
    print(f"sweep {cell.name}: setup_s={time.perf_counter() - T_PROCESS:.1f}",
          flush=True)

    # per tick: (end time, queue, busy slots, S, seconds)
    ticks = []
    step = eng.step

    def logged_step():
        t = time.perf_counter()
        before = eng.stats()["ticks"]
        n = step()
        if eng.stats()["ticks"] > before:
            now = time.perf_counter()
            ticks.append((now, len(eng.queue),
                          sum(s is not None for s in eng.slots),
                          eng.stats()["prefill_ticks"], now - t))
        return n
    eng.step = logged_step

    every = []          # (request, token stamps) of every rate so far
    for rate in [float(r) for r in args.rates.split(",")]:
        total = args.warm + args.seconds
        tt = gen.schedule(cell.traffic, args.seed, total, cfg.vocab,
                          rate=rate)
        n0 = len(ticks)
        reqs, times, t0, _, _, _ = serve.drive(ctx, eng, tt, total,
                                               drain_s=0.0)
        every += list(zip(reqs, times))
        a, b = t0 + args.warm, t0 + total
        tk = [x for x in ticks[n0:] if a <= x[0] <= b]
        pre = [x for x in ticks if x[0] < a]
        offered = sum(a <= r.t_enqueue_us / 1e6 <= b for r, _ in every)
        finished = sum(r.done and a <= ts[-1] <= b for r, ts in every)
        toks = sum(a <= t <= b for _, ts in every for t in ts)
        itl = [y - x for _, ts in every for x, y in zip(ts, ts[1:])
               if a <= x and y <= b]
        ttft = [ts[0] - r.t_enqueue_us / 1e6 for r, ts in every
                if ts and a <= r.t_enqueue_us / 1e6 <= b]
        chunk = np.diff([pre[-1][3] if pre else 0] + [x[3] for x in tk])
        print(f"rate={rate} offered_rps={offered / args.seconds:.3f} "
              f"finished_rps={finished / args.seconds:.3f} "
              f"tokens_per_s={toks / args.seconds:.1f} "
              f"busy_slots_mean={np.mean([x[2] for x in tk]):.1f} "
              f"queue_start={pre[-1][1] if pre else 0} "
              f"queue_end={tk[-1][1]} "
              f"queue_mean={np.mean([x[1] for x in tk]):.1f} "
              f"chunk_share={np.mean(chunk > 0):.3f} "
              f"tick_ms={1e3 * np.mean([x[4] for x in tk]):.1f} "
              f"ttft_p50_ms={1e3 * np.percentile(ttft, 50):.0f} "
              f"ttft_p90_ms={1e3 * np.percentile(ttft, 90):.0f} "
              f"itl_mean_ms={1e3 * np.mean(itl):.1f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
