"""What decides ``correct``: the reference, its control, and the planted
faults, at test size on the CPU.  The harness's look for a chip is
skipped; everything else of a run is driven as on the chip."""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import bench_tiny
import harness as H
import weights as W

CPU = jax.devices("cpu")[:1]


def _run(cell, seconds=2.0, devices=CPU):
    return H.run_cell(cell, 2**33 + 5, seconds, False, time.perf_counter(),
                      devices=devices)


def test_layer_by_layer_weights_equal_the_program_tree():
    """Equal to float32 rounding: XLA may contract the sampler's
    arithmetic differently in another program."""
    c = bench_tiny.cell("granite-8b-serve.conv").config
    key = H.seed_key(2**40 + 1)
    tree = W.named(jax.jit(lambda k: W.program_tree(c, k))(key))
    for i in range(c["num_hidden_layers"]):
        one = W.layer(c, key, i)
        for n in W.LAYER:
            np.testing.assert_allclose(tree[n][i, 0], one[n], rtol=1e-6,
                                       atol=1e-7, err_msg=f"{n} {i}")
    top = W.top(c, key)
    for n in W.TOP:
        np.testing.assert_allclose(tree[n], top[n], rtol=1e-6, atol=1e-7)


def test_seeds_beyond_32_bits_give_distinct_keys():
    a, b = H.seed_key(2**31 + 5), H.seed_key(2**33 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(H.seed_key(2**31 + 5)))


@pytest.mark.parametrize("value, ok", [(0.0, True), (0.12, True),
                                       (0.1201, False),
                                       (float("nan"), False),
                                       (float("inf"), False)])
def test_a_reading_is_judged_by_its_limit(value, ok):
    """A number passes while it is at most its limit; one that was not
    read (NaN) never passes."""
    c = H.load_cell("granite-8b-serve.conv").config
    (check,) = H.judge(c, {"max_logit_gap": value})
    assert (check.name, check.limit) == ("max_logit_gap", 0.12)
    assert check.ok is ok


def test_serve_run_is_correct_and_its_control_is_not():
    """The program's served tokens sit within rounding of the float32
    reference's best; the same reference in float8 picks tokens that lie
    far below it."""
    cell = bench_tiny.cell("granite-8b-serve.conv")
    serve = H.load_module(os.path.join(H.BENCH, "modes", "serve.py"),
                          "bench_mode_serve")
    ctx = H.RunCtx(cell=cell, seed=11, seconds=2.0, devices=CPU,
                   clock=H.CompileClock(), tracer=H.Tracer(False, "t"),
                   t_process=time.perf_counter(), log=lambda m: None)
    eng, cfg, key = serve.build(ctx)
    tt = H.traffic_kind(cell.traffic).schedule(cell.traffic, 11, 2.0,
                                               cfg.vocab)
    reqs, *_ = serve.drive(ctx, eng, tt, 2.0, drain_s=0.0)
    eng.run()
    picked = serve.sample([r for r in reqs if r.done], 11)
    ref = H.load_module(os.path.join(H.BENCH, "configs", "llama.py"),
                        "bench_reference_llama")
    served = [r.out_tokens for r in picked]
    low = serve.score(ref, cell.config, key, picked, [[t] for t in served],
                      "fp8")
    want = serve.score(ref, cell.config, key, picked,
                       [[t, top] for t, (_, _, top) in zip(served, low)])
    prog, ctl = serve.max_gap(want), serve.max_gap(want, 1)
    assert sum(len(r.out_tokens) for r in picked) >= 100
    judged = [H.judge(cell.config, {"max_logit_gap": x}) for x in (prog, ctl)]
    assert all(c.ok for c in judged[0]), judged
    assert not all(c.ok for c in judged[1]), judged
    assert ctl > 3 * prog, (prog, ctl)


def test_train_run_is_correct_and_its_control_is_not():
    cell = bench_tiny.cell("granite-8b-train.dp1")
    train = H.load_module(os.path.join(H.BENCH, "modes", "train.py"),
                          "bench_mode_train")
    ctx = H.RunCtx(cell=cell, seed=12, seconds=1.0, devices=CPU,
                   clock=H.CompileClock(), tracer=H.Tracer(False, "t"),
                   t_process=time.perf_counter(), log=lambda m: None)
    bundle, params, opt, feed, key, _ = train.build(ctx)
    _, _, got = train.checked_steps(cell.config, bundle, params, opt, feed,
                                    key)
    ref = train.reference_readings(ctx, key)
    prog = train.compare(got, ref)
    ctl = train.compare(train.reference_readings(ctx, key, "fp8"), ref)
    assert all(c.ok for c in H.judge(cell.config, prog)), prog
    assert not all(c.ok for c in H.judge(cell.config, ctl)), ctl
    assert ctl["grad_norm_gap"] > 3 * prog["grad_norm_gap"], (prog, ctl)


# ---------------------------------------------------------------- faults
def test_sound_runs_are_correct():
    for name in ("granite-8b-serve.conv", "granite-8b-train.dp1"):
        cell = H.load_cell(name)
        r = _run(bench_tiny.cell(name))
        assert r["correct"], r
        assert list(r) == ["correct", "attempted", "failed", "metrics",
                           "device", "checks"]
        assert list(r["checks"]) == list(cell.config["correct"])
        assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert r["attempted"] > 0 and r["failed"] == 0


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import Engine
    sample = Engine._sample
    monkeypatch.setattr(Engine, "_sample", lambda self, row, uid, step:
                        (sample(self, row, uid, step) + 1) % row.shape[-1])
    r = _run(bench_tiny.cell("granite-8b-serve.conv"))
    assert not r["correct"], r


def test_a_step_that_keeps_its_state_is_not_correct(monkeypatch):
    import repro.train.step as step
    monkeypatch.setattr(step, "apply_updates_dp",
                        lambda params, grads, opt, oc, pc: (params, opt))
    r = _run(bench_tiny.cell("granite-8b-train.dp1"))
    assert not r["correct"], r
    assert r["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0,
                                                                 abs=1e-3)


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    import repro.train.step as step
    loss = step.loss_and_metrics

    def half(params, specs, batch, *a, **k):
        lab = batch["labels"]
        lab = lab.at[:, lab.shape[1] // 2:].set(-1)
        return loss(params, specs, dict(batch, labels=lab), *a, **k)

    monkeypatch.setattr(step, "loss_and_metrics", half)
    r = _run(bench_tiny.cell("granite-8b-train.dp1"))
    assert not r["correct"], r


_NO_EXCHANGE = """
import sys, time
sys.path.insert(0, {tests!r})
import bench_tiny, harness as H, jax
import repro.train.step as step
sound = H.run_cell(bench_tiny.cell("granite-8b-train.dp1", dp=2), 5, 1.0,
                   False, time.perf_counter(), devices=jax.devices()[:2])
step.sync_grads_dp = lambda grads, *a, **k: grads
cut = H.run_cell(bench_tiny.cell("granite-8b-train.dp1", dp=2), 5, 1.0,
                 False, time.perf_counter(), devices=jax.devices()[:2])
print("SOUND", sound["correct"], "CUT", cut["correct"])
"""


def test_the_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, "-c", _NO_EXCHANGE.format(
            tests=os.path.dirname(os.path.abspath(__file__)))],
        env=env, capture_output=True, text=True, timeout=600)
    assert "SOUND True CUT False" in out.stdout, out.stderr[-3000:]


def test_no_chip_no_result():
    """On the CPU the command exits non-zero and prints nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(H.BENCH, "run.py"), "--workload",
         "granite-8b-serve.conv", "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
    assert "no result" in out.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    paths has no program to measure."""
    import shutil
    man = H.load_manifest()
    shutil.copy(os.path.join(H.ROOT, "BENCHMARK.json"), tmp_path)
    for p in man["paths"]:
        shutil.copytree(os.path.join(H.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "granite-8b-serve.conv", "--seed", "1", "--seconds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
