"""Substrate tests: data pipeline, checkpointing, optimizer math,
serve engine, elastic runner (single device; multi-device elasticity is
covered by examples/elastic_failover.py and test_parallelism)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import (AsyncCheckpointer, latest_steps,
                                         restore, save)
from repro.data.pipeline import DataConfig, DataLoader, synth_batch
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.parallel.api import ParallelConfig
from repro.train.optimizer import OptConfig, init_opt_state, lr_at

TINY = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=160, vocab=256,
                   head_dim=16, act="swiglu")
# Served tokens are checked against the model's plain full forward, not
# for bit equality across batches (see repro.serve.reference).  Engine
# and reference run the same bf16 model at different shapes, and one
# bf16 rounding moves a unit-scale value by at most 2^-8 ~ 0.004; the
# few that differ over TINY's two layers move its unit-scale logits
# well under 0.05, while a token the reference does not favour sits a
# median ~2.7 below its best.
LOGIT_TOL = 0.05


# ------------------------------------------------------------------ data
def test_data_determinism_and_elastic_resharding():
    dc = DataConfig(seq_len=16, global_batch=8, seed=3)
    full = synth_batch(TINY, dc, step=5)
    lo = synth_batch(TINY, dc, step=5, host_slice=(0, 4))
    hi = synth_batch(TINY, dc, step=5, host_slice=(4, 8))
    np.testing.assert_array_equal(
        np.concatenate([lo["tokens"], hi["tokens"]]), full["tokens"])
    # a different host-count slicing of the SAME step yields the same data
    thirds = [synth_batch(TINY, dc, step=5, host_slice=(i, i + 2))
              for i in range(0, 8, 2)]
    np.testing.assert_array_equal(
        np.concatenate([t["labels"] for t in thirds]), full["labels"])


def test_data_loader_prefetch():
    dc = DataConfig(seq_len=8, global_batch=4)
    dl = DataLoader(TINY, dc, start_step=0, prefetch=2)
    steps = [next(dl)[0] for _ in range(5)]
    dl.close()
    assert steps == [0, 1, 2, 3, 4]


def test_labels_are_shifted_tokens():
    dc = DataConfig(seq_len=12, global_batch=2)
    b = synth_batch(TINY, dc, step=0)
    # labels = next token of the same stream
    assert b["tokens"].shape == b["labels"].shape
    assert not np.array_equal(b["tokens"], b["labels"])


# ------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip(tmp_path):
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(TINY, pc, jax.random.PRNGKey(0))
    opt = init_opt_state(params, pc)
    d = str(tmp_path / "ckpt")
    save(d, 7, {"params": params, "opt": opt}, meta={"dp": 1})
    step, out = restore(d, {"params": params, "opt": opt})
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_and_gc(tmp_path):
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(TINY, pc, jax.random.PRNGKey(0))
    d = str(tmp_path / "ckpt")
    ck = AsyncCheckpointer(d, keep=2)
    for s in [1, 2, 3]:
        ck.save(s, {"params": params})
    ck.wait()
    assert latest_steps(d) == [2, 3]            # gc kept last 2
    # a partial (uncommitted) dir must be ignored
    os.makedirs(os.path.join(d, "step_00000009"))
    assert latest_steps(d) == [2, 3]


def test_restore_incompatible_layout_keeps_fresh(tmp_path):
    """Elastic resize: zero1 flat buffers with a different dp are not
    force-loaded."""
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(TINY, pc, jax.random.PRNGKey(0))
    d = str(tmp_path / "ckpt")
    save(d, 1, {"opt": {"m": np.zeros(10), "v": np.zeros(10)}})
    fresh = {"opt": {"m": np.ones(6), "v": np.ones(6)}}
    _, out = restore(d, fresh)
    np.testing.assert_array_equal(out["opt"]["m"], np.ones(6))


# --------------------------------------------- checkpoint integrity
def _tiny_tree(fill):
    return {"params": {"w": np.full((4, 3), fill, np.float32),
                       "b": np.arange(5, dtype=np.float32) * fill}}


def _leaf_file(d, step):
    sd = os.path.join(d, f"step_{step:08d}")
    import json
    with open(os.path.join(sd, "manifest.json")) as f:
        man = json.load(f)
    fn = man["trees"]["params"]["w"]["file"]
    return sd, os.path.join(sd, fn)


def test_restore_falls_back_past_torn_checkpoint(tmp_path):
    """A leaf file truncated AFTER commit (torn disk write) must not be
    restored: the damaged step is quarantined and restore falls back to
    the newest earlier step that verifies."""
    from repro.checkpoint.checkpoint import validate_checkpoint
    d = str(tmp_path / "ckpt")
    save(d, 1, _tiny_tree(1.0))
    save(d, 2, _tiny_tree(2.0))
    sd2, leaf = _leaf_file(d, 2)
    with open(leaf, "r+b") as f:
        f.truncate(os.path.getsize(leaf) // 2)
    assert not validate_checkpoint(sd2)
    assert latest_steps(d, validate=True) == [1]
    step, out = restore(d, _tiny_tree(0.0))
    assert step == 1
    np.testing.assert_array_equal(out["params"]["w"],
                                  _tiny_tree(1.0)["params"]["w"])
    assert os.path.isdir(sd2 + ".corrupt") and not os.path.isdir(sd2)
    assert latest_steps(d) == [1]  # the quarantined dir stops being listed


def test_restore_explicit_damaged_step_raises(tmp_path):
    """Silent bit rot (same-size content change) is caught by the leaf
    checksums; asking for the damaged step explicitly raises instead of
    quarantining."""
    d = str(tmp_path / "ckpt")
    save(d, 3, _tiny_tree(3.0))
    sd, leaf = _leaf_file(d, 3)
    blob = bytearray(open(leaf, "rb").read())
    blob[-1] ^= 0xFF
    with open(leaf, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(ValueError, match="failed validation"):
        restore(d, _tiny_tree(0.0), step=3)
    assert os.path.isdir(sd)  # explicit requests never quarantine


def test_restore_garbage_manifest_and_all_damaged(tmp_path):
    """A garbage manifest fails validation (the commit marker pins its
    digest); with every step damaged, restore raises rather than loading
    corrupt state."""
    d = str(tmp_path / "ckpt")
    save(d, 1, _tiny_tree(1.0))
    sd = os.path.join(d, "step_00000001")
    with open(os.path.join(sd, "manifest.json"), "w") as f:
        f.write('{"trees": {')
    with pytest.raises(FileNotFoundError, match="passed validation"):
        restore(d, _tiny_tree(0.0))


def test_legacy_checkpoint_without_checksums_restores(tmp_path):
    """Checkpoints written before checksums existed ("ok" marker, no
    sha256 entries) still validate by file presence and restore."""
    import json
    from repro.checkpoint.checkpoint import validate_checkpoint
    d = str(tmp_path / "ckpt")
    save(d, 4, _tiny_tree(4.0))
    sd = os.path.join(d, "step_00000004")
    mpath = os.path.join(sd, "manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    for leaves in man["trees"].values():
        for ent in leaves.values():
            ent.pop("sha256", None)
    with open(mpath, "w") as f:
        json.dump(man, f)
    with open(os.path.join(sd, "_COMMITTED"), "w") as f:
        f.write("ok")
    assert validate_checkpoint(sd)
    step, out = restore(d, _tiny_tree(0.0))
    assert step == 4
    np.testing.assert_array_equal(out["params"]["b"],
                                  _tiny_tree(4.0)["params"]["b"])


# -------------------------------------------------------------- optimizer
def test_lr_schedule():
    oc = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100,
                   min_lr_ratio=0.1)
    assert float(lr_at(oc, jnp.int32(0))) == 0.0
    assert float(lr_at(oc, jnp.int32(10))) == pytest.approx(1e-3, rel=1e-5)
    assert float(lr_at(oc, jnp.int32(100))) == pytest.approx(1e-4, rel=1e-4)


def test_adamw_decreases_loss_quadratic():
    """AdamW on a quadratic: sanity for the update math."""
    from repro.train.optimizer import apply_updates_dp
    pc = ParallelConfig(dp=1, tp=1)
    oc = OptConfig(lr=0.1, warmup_steps=0, total_steps=100,
                   weight_decay=0.0, grad_clip=None)
    params = {"x": jnp.array([3.0, -2.0])}
    opt = init_opt_state(params, pc)
    for _ in range(60):
        grads = {"x": 2 * params["x"]}
        params, opt = apply_updates_dp(params, grads, opt, oc, pc)
    assert float(jnp.abs(params["x"]).max()) < 0.5


# ------------------------------------------------------------ serve engine
def test_engine_continuous_batching():
    from repro.serve.engine import Engine, Request
    mesh = make_mesh((1, 1), ("data", "model"))
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(TINY, pc, jax.random.PRNGKey(0))
    eng = Engine(TINY, pc, mesh, params, batch_slots=2, max_len=48,
                 prefill_chunk=8)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, TINY.vocab, 5).astype(np.int32),
                    max_new_tokens=4) for _ in range(5)]
    eng.generate(reqs)
    for r in reqs:
        assert len(r.out_tokens) == 4
        assert all(0 <= t < TINY.vocab for t in r.out_tokens)
    for m in eng.kv:
        m.check()
    st = eng.stats()
    assert st["requests"] == 5 and st["tokens"] == 20
    assert st["live"] == 0 and st["queued"] == 0


def test_engine_greedy_matches_decode_step():
    """Greedy engine output == manual teacher-forced argmax decode."""
    from repro.serve.engine import Engine, Request
    from repro.models.model import decode_step, init_caches, param_shapes
    mesh = make_mesh((1, 1), ("data", "model"))
    pc = ParallelConfig(dp=1, tp=1)
    params, specs = init_params(TINY, pc, jax.random.PRNGKey(4))
    prompt = np.arange(6, dtype=np.int32) + 3
    eng = Engine(TINY, pc, mesh, params, batch_slots=1, max_len=32,
                 prefill_chunk=8, temperature=0.0)
    req = Request(prompt=prompt, max_new_tokens=3)
    eng.generate([req])

    caches = init_caches(TINY, pc, 1, 32)
    lg, caches = decode_step(params, specs, jnp.asarray(prompt[None]),
                             caches, jnp.int32(0), TINY, pc)
    toks = []
    pos = len(prompt)
    for _ in range(3):
        t = int(np.asarray(lg[0, -1]).argmax())
        toks.append(t)
        lg, caches = decode_step(params, specs,
                                 jnp.full((1, 1), t, jnp.int32),
                                 caches, jnp.int32(pos), TINY, pc)
        pos += 1
    assert toks == req.out_tokens


def test_engine_mixed_length_prompts_match_solo():
    """Regression: the retired wave engine left-padded prompts, feeding
    pad tokens through the model at wrong positions -- shorter prompts
    in a mixed-length batch decoded differently from a solo run.  The
    paged engine gives every row its own positions/lengths, so batched
    greedy output must equal each request's B=1 sequential run."""
    from repro.serve.engine import Engine, Request
    mesh = make_mesh((1, 1), ("data", "model"))
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(TINY, pc, jax.random.PRNGKey(7))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, TINY.vocab, n).astype(np.int32)
               for n in (3, 11, 6, 17)]
    eng = Engine(TINY, pc, mesh, params, batch_slots=4, max_len=48,
                 prefill_chunk=8)
    batched = [Request(prompt=p, max_new_tokens=5) for p in prompts]
    eng.generate(batched)
    solo = Engine(TINY, pc, mesh, params, batch_slots=1, max_len=48,
                  prefill_chunk=8, bundle=eng.bundle)
    for r in batched:
        ref = Request(prompt=r.prompt, max_new_tokens=5)
        solo.generate([ref])
        assert ref.out_tokens == r.out_tokens, \
            (len(r.prompt), r.out_tokens, ref.out_tokens)


def test_engine_sampling_deterministic_per_request():
    """Gumbel-max sampling is keyed by (seed, uid, step), never by slot,
    admission order or batch mates: under two slot counts and submit
    orders, every sampled token scores within LOGIT_TOL of the best
    under the full-forward reference plus that same keyed draw."""
    from repro.serve.engine import Engine, Request
    from repro.serve.reference import served_token_margins
    mesh = make_mesh((1, 1), ("data", "model"))
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(TINY, pc, jax.random.PRNGKey(0))

    def serve(slots, order):
        eng = Engine(TINY, pc, mesh, params, batch_slots=slots, max_len=48,
                     prefill_chunk=8, temperature=0.7, seed=11)
        reqs = [Request(prompt=np.arange(4, dtype=np.int32) + i,
                        max_new_tokens=4, uid=i) for i in range(4)]
        eng.generate([reqs[i] for i in order])
        return reqs

    for reqs in (serve(2, [0, 1, 2, 3]),
                 serve(3, [2, 0, 3, 1])):  # different slots AND order
        for r in reqs:
            margins = served_token_margins(TINY, params, r.prompt,
                                           r.out_tokens, temperature=0.7,
                                           seed=11, uid=r.uid)
            assert margins.max() <= LOGIT_TOL, (r.uid, r.out_tokens,
                                                margins)
        # distinct requests don't all sample identically by accident
        assert len({tuple(r.out_tokens) for r in reqs}) > 1


# ------------------------------------------------------------ elastic
def test_elastic_runner_single_device(tmp_path):
    from repro.runtime.elastic import ElasticConfig, ElasticRunner
    from repro.data.pipeline import DataConfig
    runner = ElasticRunner(
        TINY, OptConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        ElasticConfig(ckpt_dir=str(tmp_path / "ck"), ckpt_every=5),
        DataConfig(seq_len=16, global_batch=4),
        mesh_shape=(1, 1))
    logs = runner.run(12)
    assert logs[-1]["loss"] < logs[0]["loss"] + 0.2
    runner.ckpt.wait()
    assert latest_steps(str(tmp_path / "ck")) == [5, 10]
    step = runner.restore_latest()
    assert step == 10
    logs2 = runner.run(3)
    assert np.isfinite(logs2[-1]["loss"])


def test_straggler_watch_not_masked_by_prior_outlier():
    """The straggler EWMA must not be contaminated by the outlier it just
    alerted on: folding the raw spike in inflates the baseline so the
    NEXT straggler sails under the threshold."""
    from repro.runtime.elastic import StragglerWatch
    w = StragglerWatch(factor=3.0, decay=0.9)
    assert not any(w.observe(0.1) for _ in range(5))
    base = w.value
    assert w.observe(2.0)               # 20x: alert
    assert w.value < base * 1.25        # clamped fold, not raw 2.0
    assert w.observe(0.8)               # 8x original pace: still alerts
    # a persistent regime change converges instead of alerting forever
    alerts = [w.observe(1.0) for _ in range(40)]
    assert not any(alerts[-10:])
    assert abs(w.value - 1.0) < 0.1


def test_straggler_watch_warmup_and_runner_alerts(tmp_path):
    from repro.runtime.elastic import StragglerWatch
    w = StragglerWatch(factor=3.0, decay=0.9, warmup=3)
    assert not w.observe(0.1)           # seeds the baseline
    assert not w.observe(1.0)           # 10x, but still warming up
    w2 = StragglerWatch(factor=3.0)
    [w2.observe(0.1) for _ in range(4)]
    assert w2.observe(1.0)              # past warmup: alerts

    # runner plumbing: an alert carries (step, dt, baseline)
    from repro.data.pipeline import DataConfig
    from repro.runtime.elastic import ElasticConfig, ElasticRunner
    runner = ElasticRunner(
        TINY, OptConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        ElasticConfig(ckpt_dir=str(tmp_path / "ck"), ckpt_every=50),
        DataConfig(seq_len=8, global_batch=2), mesh_shape=(1, 1))
    for dt in [0.1] * 5:
        runner._watch_straggler(dt)
        runner.step += 1
    runner._watch_straggler(5.0)
    [(step, dt, baseline)] = runner.alerts
    assert step == 5 and dt == 5.0 and baseline == pytest.approx(0.1)
    assert runner.step_time_ewma < 0.5  # clamped: spike didn't poison it


_WORKER = os.path.join(os.path.dirname(__file__), "_multidevice_worker.py")


@pytest.mark.slow
@pytest.mark.xdist_group("subprocess")
def test_elastic_resize_prime_counts_8dev():
    """resize() through prime dp counts 8 -> 7 -> 5 with zero1 opt-state
    reset and restore_latest across layout changes; runs in a subprocess
    with 8 forced host devices (see _multidevice_worker.py)."""
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, _WORKER, "elastic_resize"],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, f"worker failed:\n{res.stdout}\n{res.stderr}"
    assert "ok elastic_resize 8->7->5" in res.stdout, res.stdout


@pytest.mark.slow
@pytest.mark.xdist_group("subprocess")
def test_serve_engine_tp_dp_8dev():
    """Continuous-batching engine on dp=2 x tp=2 (of 8 forced host
    devices): batched paged decode bit-identical to the single-request
    path, and TP decode collectives picked by autotune.choose() from a
    measured tuning table (source="measured"); see check_serve in
    _multidevice_worker.py."""
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_TUNING_CACHE", None)
    res = subprocess.run([sys.executable, _WORKER, "serve"],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, f"worker failed:\n{res.stdout}\n{res.stderr}"
    assert "ok serve" in res.stdout, res.stdout


# ------------------------------------------------------------ compile cache
@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir_is_fixed_or_from_env(monkeypatch, tmp_path,
                                                from_env):
    """Without JAX_COMPILATION_CACHE_DIR the cache sits at one fixed,
    git-ignored path of the checkout; with it, JAX's own setting stands
    and the helper sets no other directory."""
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        else:
            monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
            got = compile_cache.enable_compile_cache()
            root = os.path.dirname(os.path.dirname(__file__))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            with open(os.path.join(root, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
