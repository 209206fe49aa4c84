"""The ``deepseek-v2-lite-serve.chat`` cell at test size on the CPU:
its configuration and traffic files with every width, length and rate
scaled down (``bench_tiny.py`` stays the llama cells' own), driven
through ``harness.run_cell`` as on the chip."""
from __future__ import annotations

import copy
import os
import time

import jax
import pytest

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the path)
import harness as H

CPU = jax.devices("cpu")[:1]
NAME = "deepseek-v2-lite-serve.chat"

# every width of the block, its depth, and the held experts: chip 1 of
# 4, so that the share is not the first experts
_WIDTHS = {"hidden_size": 64, "intermediate_size": 192,
           "moe_intermediate_size": 32, "num_attention_heads": 4,
           "num_key_value_heads": 4, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "vocab_size": 256, "num_experts_per_tok": 4,
           "n_routed_experts": 4, "num_hidden_layers": 3}
_PROGRAM = {"d_model": 64, "d_ff": 192, "n_heads": 4, "n_kv_heads": 4,
            "head_dim": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab": 256,
            "n_layers": 3,
            "moe": {"n_experts": 16, "top_k": 4, "d_expert": 32,
                    "d_shared": 64, "d_first_dense": 192, "held": [1, 4]}}


def tiny_cell() -> H.Cell:
    real = H.load_cell(NAME)
    c = copy.deepcopy(real.config)
    c.update(_WIDTHS)
    c["published"]["n_routed_experts"] = 16
    c["held_experts"] = {"chip": 1, "chips": 4}
    c["program"]["overrides"] = copy.deepcopy(_PROGRAM)
    c["engine"].update(batch_slots=4, max_len=256, prefill_chunk=32)
    # the test size's own limit, between its sound runs on the CPU and
    # its float8 control (test_bench_mla_moe_run_is_correct_and_its_
    # control_is_not reads both)
    c["correct"] = {"max_logit_gap": 0.035}
    t = copy.deepcopy(real.traffic)
    t.update(rate_rps=4.0,
             prompt=dict(t["prompt"], median=40, min=8, max=150),
             output=dict(t["output"], median=10, min=2, max=60))
    return H.Cell(name=NAME, config=c, traffic=t, chips=1,
                  end_to_end=real.end_to_end, per_layer=[])


def _ctx(cell, seed):
    return H.RunCtx(cell=cell, seed=seed, seconds=2.0, devices=CPU,
                    clock=H.CompileClock(), tracer=H.Tracer(False, "t"),
                    t_process=time.perf_counter(), log=lambda m: None)


def _mode():
    return H.load_module(os.path.join(H.BENCH, "modes", "serve_mla_moe.py"),
                         "bench_mode_serve_mla_moe")


def test_the_program_config_runs_the_files_model():
    mode = _mode()
    c = tiny_cell().config
    cfg = mode.program_config(c)
    assert cfg.mla and cfg.moe.n_held == 4 and cfg.moe.first_held == 4
    bad = copy.deepcopy(c)
    bad["program"]["overrides"]["moe"]["held"] = [0, 4]
    with pytest.raises(ValueError):
        mode.program_config(bad)


def test_the_cell_is_correct_and_its_control_is_not():
    """Served tokens lie within rounding of the float32 reference's
    best; the tokens that the reference in float8 puts first lie far
    below it."""
    cell = tiny_cell()
    mode, serve = _mode(), H.load_module(
        os.path.join(H.BENCH, "modes", "serve.py"), "bench_mode_serve")
    ctx = _ctx(cell, 2**33 + 21)
    eng, cfg, key = mode.build(ctx)
    tt = H.traffic_kind(cell.traffic).schedule(cell.traffic, ctx.seed, 2.0,
                                               cfg.vocab)
    reqs, *_ = serve.drive(ctx, eng, tt, 2.0, drain_s=0.0)
    eng.run()
    picked = serve.sample([r for r in reqs if r.done], ctx.seed)
    served = [r.out_tokens for r in picked]
    ref = H.load_module(os.path.join(H.BENCH, "configs", "deepseek_v2.py"),
                        "bench_reference_deepseek_v2")
    low = mode.score(ref, cell.config, key, picked, [[t] for t in served],
                     "fp8")
    flips: list = []
    want = mode.score(ref, cell.config, key, picked,
                      [[t, top] for t, (_, _, top) in zip(served, low)],
                      flips=flips)
    prog, ctl = serve.max_gap(want), serve.max_gap(want, 1)
    assert sum(map(len, served)) >= 100
    assert len(flips) == cell.config["num_hidden_layers"] - 1
    judged = [H.judge(cell.config, {"max_logit_gap": x}) for x in (prog, ctl)]
    assert all(c.ok for c in judged[0]), (prog, ctl)
    assert not all(c.ok for c in judged[1]), (prog, ctl)


def test_a_whole_run_is_correct():
    r = H.run_cell(tiny_cell(), 2**33 + 5, 2.0, False, time.perf_counter(),
                   devices=CPU)
    assert r["correct"], r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "itl_mean_ms"}


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import Engine
    sample = Engine._sample
    monkeypatch.setattr(Engine, "_sample", lambda self, row, uid, step:
                        (sample(self, row, uid, step) + 1) % row.shape[-1])
    r = H.run_cell(tiny_cell(), 2**33 + 5, 2.0, False, time.perf_counter(),
                   devices=CPU)
    assert not r["correct"], r


# ---------------------------------------------------------------- readers
MS = 1_000_000          # ns


def _metric(name):
    return H.load_module(os.path.join(H.BENCH, "metrics", name + ".py"),
                         "bench_metric_" + name.replace(".", "_"))


def _chunk_trace():
    """One chip, window [0, 100 ms]: a decode tick (5-20 ms) and a chunk
    tick (30-60 ms) of the serve step, each dispatched by its tick."""
    import xplane as X
    P = H.load_module(os.path.join(H.BENCH, "metrics", "_program.py"),
                      "bench_metric_program")
    mods = [("jit_step(7)", 5 * MS, 20 * MS), ("jit_step(8)", 30 * MS,
                                               60 * MS)]
    ops = [("%fusion.1 = e", 5 * MS, 9 * MS),
           ("%fusion.2 = l", 9 * MS, 20 * MS),
           ("%fusion.3 = e", 30 * MS, 33 * MS),
           ("%ragged-dot-none.4 = k", 33 * MS, 38 * MS),
           ("%convert.5 = c", 38 * MS, 40 * MS),
           ("%fusion.6 = l", 40 * MS, 50 * MS),
           ("%fusion.7 = a", 50 * MS, 60 * MS)]
    body = "jit(step)/while/body/"
    scopes = {"jit_step(7)": {"fusion.1": body + "mlp/experts/take",
                              "fusion.2": body + "attn/latent_attn/dot"},
              "jit_step(8)": {"fusion.3": body + "mlp/experts/take",
                              "ragged-dot-none.4": "ragged-dot-none",
                              "convert.5": body + "mlp/experts/weight_cast/"
                                                  "convert_element_type",
                              "fusion.6": body + "attn/latent_attn/dot",
                              "fusion.7": body + "attn/dot"}}

    def tick(a, b, s, tokens, kv, pairs):
        return [P.Span("engine.tick", a * MS, b * MS,
                       dict(s=s, tokens=tokens, kv_tokens=kv,
                            attn_pairs=pairs)),
                P.Span("engine.dispatch", (a + 1) * MS, (a + 2) * MS)]

    tr = X.Trace(devices=[X.Device(0, ops, mods)],
                 spans=[("bench.traced", 0, 100 * MS)], window=(0, 100 * MS))
    tr.program = P.Program(spans=tick(1, 22, 1, 40, 60_000, 60_000)
                           + tick(27, 62, 32, 300, 90_000, 1_500_000),
                           scopes=scopes)
    return tr


def test_the_expert_and_latent_readers_read_the_chunk_ticks():
    """Under ``experts`` or in a ``ragged-dot`` kernel, less the weight
    cast: 3 + 5 ms of the chunk tick; the roofline shares divide the
    least times of ``_mla_moe`` by the measured ones."""
    c = H.load_cell(NAME).config
    facts = {"config": c, "device_kind": "TPU v5 lite"}
    tr = _chunk_trace()
    assert _metric("expert_ms.serve").reduce(tr, facts) == pytest.approx(8.0)
    m = H.load_module(os.path.join(H.BENCH, "metrics", "_mla_moe.py"),
                      "bench_metric_mla_moe")
    peak = H.peaks("TPU v5 lite")
    # 300 tokens: 225 rows on the held experts, every one picked; 8
    # expert layers of 3 x 2048 x 1408 bf16 weights and the rows' d in
    # and out, memory-bound
    least = m.expert_min_s(c, 300, peak)
    nbytes = (8 * 3 * 2048 * 1408 + 225 * 2 * 2048) * 2
    assert least == pytest.approx(8 * nbytes / 819e9, rel=1e-6)
    assert _metric("expert_roofline.serve").reduce(tr, facts) == \
        pytest.approx(100 * least / 8e-3)
    # one query per live latent (decode rows): 9 layers of 576 bf16
    # values a latent, memory-bound
    assert m.latent_min_s(c, 90_000, 90_000, peak) == \
        pytest.approx(90_000 * 576 * 2 * 9 / 819e9, rel=1e-6)
    # the chunk tick's 1.5 M query-key pairs: 16 heads of a 576-wide
    # score and a 512-wide value product each, compute-bound
    lat = 1_500_000 * 16 * 2 * (576 + 512) * 9 / 197e12
    assert m.latent_min_s(c, 90_000, 1_500_000, peak) == \
        pytest.approx(lat, rel=1e-6)
    assert _metric("latent_attn_roofline.serve").reduce(tr, facts) == \
        pytest.approx(100 * lat / 10e-3)


def test_the_readers_find_nothing_without_chunk_ticks_or_facts():
    c = H.load_cell(NAME).config
    facts = {"config": c, "device_kind": "TPU v5 lite"}
    tr = _chunk_trace()
    for sp in tr.program.spans:
        sp.args["s"] = 1
    for name in ("expert_ms.serve", "expert_roofline.serve",
                 "latent_attn_roofline.serve"):
        assert _metric(name).reduce(tr, facts) is None, name
    # a run whose mode gave no configuration (another mode's cell)
    for name in ("expert_roofline.serve", "latent_attn_roofline.serve"):
        assert _metric(name).reduce(_chunk_trace(), {}) is None, name
