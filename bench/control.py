"""Readings that the limits of ``correct`` are set from, on the chip at a
cell's own size: the program on many seeds, and the control and the
planted faults on a few.

    python3 bench/control.py --workload granite-8b-serve.conv \
        --seeds 1,2,3,...,12 --control-seeds 1,2,3 --seconds 20

All in one process, one seed after another.  Per seed it prints one
line per reading: whether the configuration's limits find it correct,
and every number a run compares as ``name=value/limit``:

* ``program``  -- the program as the benchmark runs it;
* ``control``  -- the plain reference computed in float8 put in the
  program's place (serving: at each position the reference scored, the
  token that float8 puts first; training: its losses, gradient and
  update norms);
* ``fault:half_batch`` (training) -- the program with the labels of
  half of each batch's tokens ignored, its loss the mean over the rest.

A step that returns its state unchanged reads 1 on ``update_norm_gap``
by that number's definition and needs no run.  The benchmark's own
runs never run any of this.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_PROCESS = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def serve_readings(ctx, serve, seconds, control: bool):
    import harness as H
    c = ctx.cell.config
    eng, cfg, key = serve.build(ctx)
    tt = H.traffic_kind(ctx.cell.traffic).schedule(
        ctx.cell.traffic, ctx.seed, seconds, cfg.vocab)
    reqs, *_ = serve.drive(ctx, eng, tt, seconds, drain_s=0.0)
    eng.run()
    picked = serve.sample([r for r in reqs if r.done], ctx.seed)
    served = [r.out_tokens for r in picked]
    del eng
    H.free_device_memory()
    ref = H.load_module(f"{H.BENCH}/configs/{c['reference']}.py",
                        "bench_reference_" + c["reference"])
    sets = [[t] for t in served]
    if control:
        low = serve.score(ref, c, key, picked, sets, "fp8")
        sets = [[t, top] for t, (_, _, top) in zip(served, low)]
    want = serve.score(ref, c, key, picked, sets)
    out = {"program": {"max_logit_gap": serve.max_gap(want)}}
    if control:
        out["control"] = {"max_logit_gap": serve.max_gap(want, 1)}
    n = sum(map(len, served))
    return out, f"requests={len(picked)} served_tokens={n}"


def train_readings(ctx, train, control: bool):
    import harness as H

    def program(labels_fault=None):
        bundle, params, opt, feed, key, _ = train.build(ctx, labels_fault)
        _, _, got = train.checked_steps(ctx.cell.config, bundle, params,
                                        opt, feed, key)
        del bundle, params, opt
        H.free_device_memory()
        return got, key

    got, key = program()
    ref = train.reference_readings(ctx, key)
    out = {"program": train.compare(got, ref)}
    if control:
        out["control"] = train.compare(
            train.reference_readings(ctx, key, "fp8"), ref)

        def half(labels):
            labels = labels.copy()
            labels[:, labels.shape[1] // 2:] = -1
            return labels

        out["fault:half_batch"] = train.compare(program(half)[0], ref)
    return out, f"left_out={train.left_out(ref)} ref_losses={ref['losses']}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="serving: length of the open-loop window")
    args = ap.parse_args(argv)

    import harness as H
    cell = H.load_cell(args.workload)
    sys.path.insert(0, H.SRC)
    try:
        devs = H.chips(cell.chips)
    except H.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mode = H.load_module(os.path.join(H.BENCH, "modes",
                                      cell.config["mode"] + ".py"),
                         "bench_mode_" + cell.config["mode"])
    ctl = set(_seeds(args.control_seeds))
    clock = H.CompileClock()
    for seed in sorted(set(_seeds(args.seeds)) | ctl):
        t0 = time.perf_counter()
        ctx = H.RunCtx(cell=cell, seed=seed, seconds=args.seconds,
                       devices=devs, clock=clock,
                       tracer=H.Tracer(False, cell.name),
                       t_process=T_PROCESS,
                       log=lambda m: print(m, file=sys.stderr))
        if cell.config["mode"] == "serve":
            out, note = serve_readings(ctx, mode, args.seconds, seed in ctl)
        else:
            out, note = train_readings(ctx, mode, seed in ctl)
        for kind, nums in out.items():
            checks = H.judge(cell.config, nums)
            print(f"{cell.name} seed={seed} {kind} "
                  f"correct={all(c.ok for c in checks)} "
                  + " ".join(f"{c.name}={c.value!r}/{c.limit!r}"
                             for c in checks), flush=True)
        print(f"{cell.name} seed={seed} {note} "
              f"s={time.perf_counter() - t0:.1f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
