"""Serving a DeepSeek-V2 configuration (latent attention on the paged
cache, held experts): ``modes/serve.py``'s engine, open-loop window,
end-to-end metric and check, with this family's program configuration,
weights (``bench/weights_deepseek_v2.py``) and reference
(``bench/configs/deepseek_v2.py``).

Besides ``max_logit_gap``, standard error gets, per expert layer, the
number of the checked sequences' tokens whose top-k a router with bf16
operands (the program's) picks otherwise than the reference's f32 one
on the reference's own layer input.  The per-layer readers get the
configuration and the device kind (``facts``).
"""
from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

import harness as H
import weights_deepseek_v2 as W

serve = H.load_module(os.path.join(H.BENCH, "modes", "serve.py"),
                      "bench_mode_serve")


def program_config(c: dict):
    """The registry entry ``c["registry"]`` with ``c["program"]``'s
    overrides (``moe`` and ``yarn`` overrides replace fields of those
    groups), after checking that it runs the file's model."""
    from repro.configs import get_config
    cfg = get_config(c["registry"])
    over = dict(c["program"]["overrides"])
    for group in ("moe", "yarn"):
        if group in over:
            fields = {k: tuple(v) if isinstance(v, list) else v
                      for k, v in over.pop(group).items()}
            over[group] = dataclasses.replace(getattr(cfg, group), **fields)
    cfg = dataclasses.replace(cfg, **over)
    m, y, rs = cfg.moe, cfg.yarn, c["rope_scaling"]
    held = c["held_experts"]
    want = {
        "d_model": (cfg.d_model, c["hidden_size"]),
        "n_heads": (cfg.n_heads, c["num_attention_heads"]),
        "vocab": (cfg.vocab, c["vocab_size"]),
        "n_layers": (cfg.n_layers, c["num_hidden_layers"]),
        "norm_eps": (cfg.norm_eps, c["rms_norm_eps"]),
        "rope_theta": (cfg.rope_theta, c["rope_theta"]),
        "kv_lora_rank": (cfg.kv_lora_rank, c["kv_lora_rank"]),
        "qk_nope_head_dim": (cfg.qk_nope_head_dim, c["qk_nope_head_dim"]),
        "qk_rope_head_dim": (cfg.qk_rope_head_dim, c["qk_rope_head_dim"]),
        "v_head_dim": (cfg.v_head_dim, c["v_head_dim"]),
        "query compression": (None, c["q_lora_rank"]),
        "first_dense": (m.first_dense, c["first_k_dense_replace"]),
        "d_first_dense": (m.d_first_dense, c["intermediate_size"]),
        "d_expert": (m.d_expert, c["moe_intermediate_size"]),
        "n_experts": (m.n_experts, c["published"]["n_routed_experts"]),
        "n_held": (m.n_held, c["n_routed_experts"]),
        "held": (m.held, (held["chip"], held["chips"])),
        "top_k": (m.top_k, c["num_experts_per_tok"]),
        "d_shared": (m.d_shared,
                     c["moe_intermediate_size"] * c["n_shared_experts"]),
        "norm_topk": (m.norm_topk, c["norm_topk_prob"]),
        "gate": (("softmax", "greedy", 1.0),
                 (c["scoring_func"], c["topk_method"],
                  c["routed_scaling_factor"])),
        "yarn": ((y.factor, y.beta_fast, y.beta_slow,
                  y.original_max_position_embeddings, y.mscale,
                  y.mscale_all_dim),
                 (rs["factor"], rs["beta_fast"], rs["beta_slow"],
                  rs["original_max_position_embeddings"], rs["mscale"],
                  rs["mscale_all_dim"])),
    }
    differ = {k: v for k, v in want.items() if v[0] != v[1]}
    if differ or cfg.act != "swiglu" or cfg.tie_embeddings \
            or cfg.cycle != ("attn",) or not cfg.mla:
        raise ValueError(f"{c['name']}: the program's {c['registry']} "
                         f"does not run the file's model: {differ}")
    return cfg


def place_params(c: dict, key, cfg, pc, shardings):
    """The weights of ``c`` from ``key``, made on the device in one
    jitted call, in the program's layout and placement."""
    import jax
    from repro.models.model import param_shapes
    want, _ = param_shapes(cfg, pc)
    got = jax.eval_shape(lambda k: W.program_tree(c, k), key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError(f"{c['name']}: seeded weights do not match the "
                         f"program's parameter tree")
    return jax.jit(lambda k: W.program_tree(c, k),
                   out_shardings=shardings)(key)


def build(ctx):
    """Set-up as ``serve.build``: weights, engine, warm-up of both tick
    shapes.  Returns (engine, model config, seed key)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.serve.engine import Engine, Request

    c = ctx.cell.config
    e = c["engine"]
    cfg = program_config(c)
    mesh = make_mesh((1, 1), ("data", "model"), devices=ctx.devices[:1])
    pc = parallel_config_for(mesh, param_mode="dp")
    key = H.seed_key(ctx.seed)
    params = place_params(c, key, cfg, pc, NamedSharding(mesh, P()))
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


def score(ref, c: dict, key, reqs, tokens, precision: str = "float32",
          flips: list = None):
    """``serve.score``, with the reference's router disagreements per
    expert layer appended to ``flips``."""
    seqs = [np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                 np.int32)]) for r in reqs]
    pos = [len(r.prompt) - 1 + np.arange(len(r.out_tokens)) for r in reqs]
    return ref.serve_scores(c, key, seqs, pos, tokens,
                            seq_len=c["engine"]["max_len"],
                            max_positions=serve.MAX_OUTPUT,
                            precision=precision, flips=flips)


def run(ctx) -> H.Outcome:
    c = ctx.cell.config
    clock = ctx.clock
    eng, cfg, key = build(ctx)
    timetable = H.traffic_kind(ctx.cell.traffic).schedule(
        ctx.cell.traffic, ctx.seed, ctx.seconds, cfg.vocab,
        tail_s=serve.DRAIN_S)
    setup_s = time.perf_counter() - ctx.t_process
    setup_compile_s, setup_hits = clock.secs, clock.hits

    reqs, times, t0, t_end, late, kinds = serve.drive(ctx, eng, timetable,
                                                      ctx.seconds)
    ttft, itl, failed = serve.latency(reqs, times, timetable, t0, t_end,
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
        f"prefill_ticks={st['prefill_ticks']} "
        f"drain_s={t_end - t0 - ctx.seconds!r} "
        f"compiles_in_window={clock.compiles_between(t0, t_end)}",
        f"generator lateness: mean_ms={1e3 * float(np.mean(late))!r} "
        f"max_ms={1e3 * float(np.max(late))!r}",
        f"ttft_ms: p50={float(np.percentile(ttft, 50))!r} "
        f"p90={float(np.percentile(ttft, 90))!r} max={max(ttft)!r}",
        f"memory_peak_bytes={peak}",
    ]
    picked = serve.sample(done, ctx.seed)
    served = [r.out_tokens for r in picked]
    del eng
    H.free_device_memory()
    t_ref = time.perf_counter()
    ref = H.load_module(f"{H.BENCH}/configs/{c['reference']}.py",
                        "bench_reference_" + c["reference"])
    flips: list = []
    gap = serve.max_gap(score(ref, c, key, picked, [[t] for t in served],
                              flips=flips)) if picked else float("nan")
    read = sum(len(r.prompt) + len(r.out_tokens) - 1 for r in picked)
    notes.append(f"check: requests={len(picked)} served_tokens="
                 f"{sum(map(len, served))} read_tokens={read} "
                 f"router_top{c['num_experts_per_tok']}_differs={flips} "
                 f"ref_s={time.perf_counter() - t_ref!r}")
    return H.Outcome(
        end_to_end={"setup_s": setup_s,
                    "itl_mean_ms": 1e3 * float(np.sum(itl)) / max(len(itl), 1)},
        attempted=n_win, failed=failed,
        checks=H.judge(c, {"max_logit_gap": gap}),
        memory_peak_bytes=peak,
        facts={"tick_kinds": kinds, "config": c,
               "device_kind": ctx.devices[0].device_kind},
        notes=notes)
