"""Serving benchmark worker: load-generate against the continuous-
batching engine on a dp=2 x tp=2 mesh of 8 forced host devices.

One deterministic request mix (prompt lengths, max_new_tokens) is served
at each offered arrival rate: requests are submitted on an open-loop
schedule (request i arrives at ``i / qps``; ``qps=inf`` enqueues the
whole mix at once) and the engine is stepped until the mix drains.
Reported per QPS level: p50/p99 TTFT, p50/p99 request latency and
generated tokens/sec.

Absolute microseconds are not comparable across hosts, so the gated
keys are dimensionless ratios against a *solo* baseline measured in the
same process right before the sweep -- the same mix served one request
at a time (no batching, no queueing):

  tokens_per_s_ratio   throughput gain of continuous batching (floor)
  p99_ttft_ratio       p99 TTFT / solo mean request latency (ceiling)
  p99_latency_ratio    p99 latency / solo mean request latency (ceiling)

TP decode collectives run on ExecPlan schedules picked by
``autotune.choose()`` (``decode_collectives="plan"``); the payload
records the trace-time picks.  Rows: ``serve,qps=<q>,tokens_per_s=..``.
Writes ``--out`` (default results/serving.json); ``--trace`` saves the
engine.tick Chrome trace + a metrics snapshot next to it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.mesh import make_mesh, parallel_config_for  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.model import init_params  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.obs.log import data, get_logger  # noqa: E402
from repro.obs.metrics import get_metrics  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402

log = get_logger("benchmarks.serve")

CFG = ModelConfig(name="bench", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=160, vocab=256,
                  head_dim=16, act="swiglu")
MAX_LEN = 64
CHUNK = 16
# offered arrival rates (requests/sec); "inf" = the whole mix at once.
# smoke runs the subset marked True -- its labels must stay a subset of
# the full grid so the committed baseline always overlaps in CI.
QPS_GRID = ((1.0, False), (4.0, True), (16.0, False), (float("inf"), True))


def _mix(n_requests: int, max_new: int):
    rng = np.random.default_rng(7)
    return [Request(prompt=rng.integers(0, CFG.vocab,
                                        int(rng.integers(4, 25)))
                    .astype(np.int32), max_new_tokens=max_new)
            for _ in range(n_requests)]


def _engine(pc, mesh, params, batch_slots, bundle=None):
    return Engine(CFG, pc, mesh, params, batch_slots=batch_slots,
                  max_len=MAX_LEN, prefill_chunk=CHUNK, block_size=8,
                  bundle=bundle)


def _percentiles(vals):
    a = np.asarray(vals, np.float64)
    return (float(np.percentile(a, 50)), float(np.percentile(a, 99)))


def run_solo(pc, mesh, params, mix, bundle) -> dict:
    """Baseline: the same mix, one request at a time, no batching."""
    eng = _engine(pc, mesh, params, 1, bundle)
    t0 = time.perf_counter()
    for r in mix:
        req = Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        eng.submit(req)
        eng.run()
        assert req.done
    wall_s = time.perf_counter() - t0
    tokens = sum(r.max_new_tokens for r in mix)
    return {
        "wall_s": wall_s,
        "tokens_per_s": tokens / wall_s,
        "mean_latency_us": wall_s * 1e6 / len(mix),
    }


def run_level(pc, mesh, params, mix, qps, batch_slots, bundle) -> dict:
    """Serve the mix at one offered arrival rate."""
    eng = _engine(pc, mesh, params, batch_slots, bundle)
    reqs = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
            for r in mix]
    period = 0.0 if qps == float("inf") else 1.0 / qps
    t0 = time.perf_counter()
    nxt = 0
    while nxt < len(reqs) or eng.queue or \
            any(s is not None for s in eng.slots):
        now = time.perf_counter() - t0
        while nxt < len(reqs) and now >= nxt * period:
            eng.submit(reqs[nxt])
            nxt += 1
        eng.step()
        if nxt < len(reqs) and not eng.queue and \
                all(s is None for s in eng.slots):
            # idle between arrivals: wait for the next one
            time.sleep(max(0.0, nxt * period - (time.perf_counter() - t0)))
    wall_s = time.perf_counter() - t0
    st = eng.stats()
    assert all(r.done for r in reqs)
    assert st["tokens"] == sum(r.max_new_tokens for r in reqs), st
    for m in eng.kv:
        m.check()
    ttft_p50, ttft_p99 = _percentiles([r.ttft_us for r in reqs])
    lat_p50, lat_p99 = _percentiles([r.latency_us for r in reqs])
    label = "qps=inf" if qps == float("inf") else f"qps={qps:g}"
    return {
        "label": label,
        "bench": "serve",
        "qps": None if qps == float("inf") else qps,
        "n_requests": len(reqs),
        "batch_slots": batch_slots,
        "wall_s": round(wall_s, 3),
        "tokens_per_s": round(st["tokens"] / wall_s, 2),
        "p50_ttft_us": round(ttft_p50, 1),
        "p99_ttft_us": round(ttft_p99, 1),
        "p50_latency_us": round(lat_p50, 1),
        "p99_latency_us": round(lat_p99, 1),
        "ticks": st["ticks"],
        "prefill_ticks": st["prefill_ticks"],
        "peak_blocks_used": max(k["peak_blocks_used"] for k in st["kv"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="results/serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="run only the smoke QPS subset (CI PR gate)")
    ap.add_argument("--trace", action="store_true",
                    help="save the engine.tick Chrome trace next to --out")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    # the mix is the SAME in smoke and full runs -- smoke only trims the
    # QPS grid -- so a smoke row in CI and the committed full-run row
    # with the same label measure the identical workload
    mix = _mix(args.requests, args.max_new)

    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    pc = parallel_config_for(mesh, param_mode="dp")
    params, _ = init_params(CFG, pc, jax.random.PRNGKey(0))

    # warm up every compiled (B, S) shape outside the timed runs
    warm = _engine(pc, mesh, params, args.batch_slots)
    bundle = warm.bundle
    warm.generate([Request(prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens)
                   for r in mix[:4]])
    solo_warm = _engine(pc, mesh, params, 1, bundle)
    solo_warm.generate([Request(prompt=mix[0].prompt, max_new_tokens=2)])

    if args.trace:
        obs_trace.enable(clear=True)
    metrics = get_metrics()

    solo = run_solo(pc, mesh, params, mix, bundle)
    data(f"serve,solo,tokens_per_s={solo['tokens_per_s']:.2f},"
         f"mean_latency_us={solo['mean_latency_us']:.1f}")

    rows = []
    for qps, in_smoke in QPS_GRID:
        if args.smoke and not in_smoke:
            continue
        row = run_level(pc, mesh, params, mix, qps, args.batch_slots,
                        bundle)
        row["solo_tokens_per_s"] = round(solo["tokens_per_s"], 2)
        row["solo_mean_latency_us"] = round(solo["mean_latency_us"], 1)
        # dimensionless, host-normalized: the gated keys.  At a finite
        # offered rate, wall clock is arrival-schedule-bound (fixed
        # seconds) while the solo baseline is host-bound, so the
        # throughput ratio is only meaningful on the saturated
        # (qps=inf) row; the latency ratios compare host-bound
        # quantities on both sides and gate at every level.
        if qps == float("inf"):
            row["tokens_per_s_ratio"] = round(
                row["tokens_per_s"] / solo["tokens_per_s"], 3)
        row["p99_ttft_ratio"] = round(
            row["p99_ttft_us"] / solo["mean_latency_us"], 3)
        row["p99_latency_ratio"] = round(
            row["p99_latency_us"] / solo["mean_latency_us"], 3)
        rows.append(row)
        metrics.histogram("serve_tokens_per_s").record(row["tokens_per_s"])
        data(f"serve,{row['label']},tokens_per_s={row['tokens_per_s']},"
             f"tps_ratio={row.get('tokens_per_s_ratio', '-')},"
             f"p99_ttft_ratio={row['p99_ttft_ratio']},"
             f"p99_latency_ratio={row['p99_latency_ratio']}")

    # trace-time decode collective picks (engines share one choice log)
    choices = warm.decode_choices
    picks = [{"op": op, "nbytes": nb, "kind": c.kind, "r": c.r,
              "n_buckets": c.n_buckets, "source": c.source}
             for op, nb, c in choices]
    if not picks:
        log.error("no_decode_choices")
        return 1

    mode = "smoke" if args.smoke else "full"
    payload = {"benchmark": "serving", "mode": mode,
               "model": CFG.name, "mesh": "dp=2,tp=2",
               "decode_choices": picks, "results": rows}
    out_dir = os.path.dirname(os.path.abspath(args.out)) or "."
    os.makedirs(out_dir, exist_ok=True)
    if args.trace:
        tracer = obs_trace.get_tracer()
        trace_path = tracer.save(
            os.path.join(out_dir, f"trace_serving_{mode}.json"),
            process_name=f"serve-bench-{mode}")
        obs_trace.disable()
        payload["trace_path"] = os.path.basename(trace_path)
        metrics_path = metrics.save(
            os.path.join(out_dir, f"metrics_serving_{mode}.json"),
            extra={"benchmark": "serving", "mode": mode})
        data(f"serve,trace,{os.path.basename(trace_path)},"
             f"{tracer.n_events}")
        data(f"serve,WROTE,{metrics_path}")
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    data(f"serve,WROTE,{args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
