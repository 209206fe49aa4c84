"""Serving: one ``Engine`` replica on one chip, as ``launch/serve.py``
builds it, driven by an open-loop timetable.

Set-up makes the weights on the device from the seed, builds the
engine with the configuration's slots, ``max_len``, prefill chunk and
block size, and serves two warm-up requests that compile both tick
shapes (S = 1 and S = prefill_chunk).  The window then submits every
request at its due time and steps the engine whenever it holds work.
After the window the timetable runs on, for at most ``DRAIN_S``, until
every request due in the window has its first token.

End to end, ``itl_mean_ms``: all gaps between consecutive tokens of a
request that end inside the window, summed, over their number.  The
time to first token, measured from each request's due time, is printed
on standard error only: a window of tens of requests under bursty
arrivals gives a tail that moves with the seed's burst order far more
than any bound could allow.

``correct``: a sample of the finished requests drawn from the seed, the
longest among them, is scored under the plain reference's full forward
pass once the program's state is freed; the widest gap by which a
served token's reference logit lies below the reference's best must
stay under the configuration's limit.
"""
from __future__ import annotations

import time

import numpy as np

import harness as H
import registry

DRAIN_S = 60.0
SAMPLE_MIN_TOKENS = 256      # served tokens the reference scores, at least
SAMPLE_MAX_TOKENS = 32768    # prompt + served tokens it may read, at most
MAX_OUTPUT = 1024            # served tokens of one request, at most


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, float), q))


def sample(done, seed: int):
    """The longest finished request, then others in an order drawn from
    the seed, until the served tokens reach ``SAMPLE_MIN_TOKENS`` or the
    reference's reading would pass ``SAMPLE_MAX_TOKENS``."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out_tokens))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 7])).permutation(len(rest))
    out = [longest]
    served = len(longest.out_tokens)
    read = len(longest.prompt) + served
    for j in order:
        if served >= SAMPLE_MIN_TOKENS:
            break
        r = rest[j]
        n = len(r.prompt) + len(r.out_tokens)
        if read + n > SAMPLE_MAX_TOKENS:
            continue
        out.append(r)
        served += len(r.out_tokens)
        read += n
    return out


def score(ref, c: dict, key, reqs, tokens, precision: str = "float32"):
    """The reference's pass over each request's prompt and served
    tokens, read at the positions that predicted them: per request
    (best logit, logits of the token sets ``tokens[j]``, argmax)."""
    seqs = [np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                 np.int32)]) for r in reqs]
    pos = [len(r.prompt) - 1 + np.arange(len(r.out_tokens)) for r in reqs]
    return ref.serve_scores(c, key, seqs, pos, tokens,
                            seq_len=c["engine"]["max_len"],
                            max_positions=MAX_OUTPUT, precision=precision)


def max_gap(scores, k: int = 0) -> float:
    """The widest gap by which token set ``k`` lies below the best."""
    return float(max((best - picked[k]).max()
                     for best, picked, _ in scores))


def build(ctx):
    """Set-up: weights, engine, warm-up.  Returns (engine, model config,
    seed key)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.serve.engine import Engine, Request

    c = ctx.cell.config
    e = c["engine"]
    cfg = registry.program_config(c)
    mesh = make_mesh((1, 1), ("data", "model"), devices=ctx.devices[:1])
    pc = parallel_config_for(mesh, param_mode="dp")
    key = H.seed_key(ctx.seed)
    params = registry.place_params(c, key, cfg, pc, NamedSharding(mesh, P()))
    eng = Engine(cfg, pc, mesh, params, batch_slots=e["batch_slots"],
                 max_len=e["max_len"], prefill_chunk=e["prefill_chunk"],
                 block_size=e["block_size"], temperature=0.0)
    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 1]))
    eng.generate([Request(prompt=rng.integers(0, cfg.vocab, n,
                                              dtype=np.int32),
                          max_new_tokens=4)
                  for n in (e["prefill_chunk"] + 5, 7)])
    jax.block_until_ready(eng.caches)
    return eng, cfg, key


def drive(ctx, eng, timetable, seconds: float, drain_s: float = DRAIN_S):
    """Submit each request at its due time and step the engine while it
    holds work.  Returns (requests, token times per request, t0, t_end,
    submit lateness of the window's requests, tick kinds traced)."""
    import jax

    from repro.serve.engine import Request

    span, tracer = ctx.tracer.span, ctx.tracer
    lo = 0.25 * seconds
    trace_span = (lo, lo + min(10.0, 0.5 * seconds))
    reqs, times, late, kinds = [], [], [], []
    tracing = traced = False
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < len(timetable) and timetable[i].due_s <= now:
            q = timetable[i]
            stamps = []
            r = Request(prompt=q.prompt, max_new_tokens=q.max_new,
                        stream=lambda _r, _t, s=stamps: s.append(
                            time.perf_counter()),
                        t_enqueue_us=(t0 + q.due_s) * 1e6)
            with span("bench.submit"):
                eng.submit(r)
            reqs.append(r)
            times.append(stamps)
            if q.in_window:
                late.append(now - q.due_s)
            i += 1
        if tracer.on and not traced and not tracing and now >= trace_span[0]:
            jax.block_until_ready(eng.caches)
            tracer.start()
            tracing = True
        if tracing and now >= trace_span[1]:
            jax.block_until_ready(eng.caches)
            tracer.stop()
            tracing, traced = False, True
        if now >= seconds:
            waiting = any(r.t_first_token_us is None
                          for r, q in zip(reqs, timetable) if q.in_window)
            if not waiting or now >= seconds + drain_s:
                break
        if eng.queue or any(s is not None for s in eng.slots):
            before = eng.stats() if tracing else None
            with span("bench.engine_step"):
                eng.step()
            if tracing:
                after = eng.stats()
                if after["ticks"] > before["ticks"]:
                    kinds.append("chunk" if after["prefill_ticks"]
                                 > before["prefill_ticks"] else "decode")
        else:
            nxt = timetable[i].due_s if i < len(timetable) else now + 1e-3
            with span("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt - now, 0.01)))
    if tracing:
        jax.block_until_ready(eng.caches)
        tracer.stop()
    return reqs, times, t0, time.perf_counter(), late, kinds


def latency(reqs, times, timetable, t0: float, t_end: float,
            seconds: float):
    """(ttft list ms, ITL gaps in the window s, failed count) over the
    requests due in the window."""
    ttft, failed = [], 0
    for r, q in zip(reqs, timetable):
        if not q.in_window:
            continue
        if r.t_first_token_us is None:
            failed += 1
            ttft.append((t_end - t0 - q.due_s) * 1e3)
        else:
            ttft.append((r.t_first_token_us - r.t_enqueue_us) / 1e3)
    itl = [b - a for ts in times for a, b in zip(ts, ts[1:])
           if b <= t0 + seconds]
    return ttft, itl, failed


def run(ctx) -> H.Outcome:
    c = ctx.cell.config
    clock = ctx.clock
    eng, cfg, key = build(ctx)
    timetable = H.traffic_kind(ctx.cell.traffic).schedule(
        ctx.cell.traffic, ctx.seed, ctx.seconds, cfg.vocab, tail_s=DRAIN_S)
    setup_s = time.perf_counter() - ctx.t_process
    setup_compile_s, setup_hits = clock.secs, clock.hits

    reqs, times, t0, t_end, late, kinds = drive(ctx, eng, timetable,
                                                ctx.seconds)
    ttft, itl, failed = latency(reqs, times, timetable, t0, t_end,
                                ctx.seconds)
    peak = H.memory_peak_bytes(ctx.devices)
    st = eng.stats()
    n_win = sum(q.in_window for q in timetable)
    done = [r for r, q in zip(reqs, timetable) if q.in_window and r.done]
    notes = [
        f"setup_s={setup_s!r} compile_s={setup_compile_s!r} "
        f"cache_hits={setup_hits}",
        f"window: requests={n_win} finished={len(done)} failed={failed} "
        f"itl_gaps={len(itl)} ticks={st['ticks']} "
        f"prefill_ticks={st['prefill_ticks']} drain_s={t_end - t0 - ctx.seconds!r} "
        f"compiles_in_window={clock.compiles_between(t0, t_end)}",
        f"generator lateness: mean_ms={1e3 * float(np.mean(late))!r} "
        f"max_ms={1e3 * float(np.max(late))!r}",
        f"ttft_ms: p50={_percentile(ttft, 50)!r} p90={_percentile(ttft, 90)!r}"
        f" max={max(ttft)!r}",
        f"memory_peak_bytes={peak}",
    ]
    picked = sample(done, ctx.seed)
    served = [r.out_tokens for r in picked]
    del eng
    H.free_device_memory()
    t_ref = time.perf_counter()
    ref = H.load_module(f"{H.BENCH}/configs/{c['reference']}.py",
                        "bench_reference_" + c["reference"])
    gap = max_gap(score(ref, c, key, picked, [[t] for t in served])) \
        if picked else float("nan")
    longest = max((len(r.prompt) + len(r.out_tokens) for r in picked),
                  default=0)
    notes.append(f"check: requests={len(picked)} served_tokens="
                 f"{sum(map(len, served))} longest={longest} "
                 f"ref_s={time.perf_counter() - t_ref!r}")
    return H.Outcome(
        end_to_end={"setup_s": setup_s,
                    "itl_mean_ms": 1e3 * float(np.sum(itl)) / max(len(itl), 1)},
        attempted=n_win, failed=failed,
        checks=H.judge(c, {"max_logit_gap": gap}),
        memory_peak_bytes=peak, facts={"tick_kinds": kinds}, notes=notes)
