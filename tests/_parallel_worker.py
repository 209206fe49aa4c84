"""Multi-device parallelism checks (subprocess; 8 host devices).

Verifies the manual-SPMD model stack end to end: a train step under
(dp, tp) sharding with each param mode must produce the same loss and the
same updated parameters as the single-device reference.
"""
import os
import sys

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.launch.mesh import make_mesh, parallel_config_for
from repro.models.model import init_caches, init_params
from repro.parallel.api import ParallelConfig
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.step import make_serve_step, make_train_step

OC = OptConfig(lr=1e-3, warmup_steps=0, total_steps=100, grad_clip=None)

# Per-arch loss tolerance vs the single-device reference.  xlstm's mLSTM
# recurrence chains bf16 matmul outputs through an exponential-gated
# cumulative scan, so the dp=2 batch split (different device boundaries
# -> different reassociation of the same bf16 sums) compounds through the
# sequence dimension instead of averaging out; on XLA's CPU backend the
# resulting loss drift has been measured at ~0.11 (absolute, at loss ~6.05)
# while every attention arch stays < 5e-3.  The updated-parameter check
# below stays at the tight default -- it would catch a genuine gradient
# sync bug that a loss-level gate this loose could hide.
_LOSS_TOL = {"xlstm_1_3b": 0.2}
_DEFAULT_LOSS_TOL = 5e-2


def _batch(cfg, B, S, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.frontend == "audio":
        return {
            "embeds": jnp.asarray(
                rng.standard_normal((B, S, cfg.d_model)), jnp.bfloat16),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        }
    if cfg.frontend == "vision":
        s_text = max(S - cfg.n_patches, 8)
        return {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab, (B, s_text)), jnp.int32),
            "patch_embeds": jnp.asarray(
                rng.standard_normal((B, cfg.n_patches, cfg.d_model)),
                jnp.bfloat16),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab, (B, s_text)), jnp.int32),
        }
    return {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
    }


def _reference(cfg, batch):
    mesh = make_mesh((1, 1), ("data", "model"))
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(cfg, pc, jax.random.PRNGKey(0))
    opt = init_opt_state(params, pc)
    b = make_train_step(cfg, pc, mesh, OC, donate=False)
    p1, o1, m1 = b.train_step(params, opt, batch)
    return params, p1, float(m1["loss"])


def check_mode(arch: str, mode: str, mesh_shape, seed=0):
    cfg = get_reduced(arch)
    B, S = 4, 32
    batch = _batch(cfg, B, S, seed)
    params0, p_ref, loss_ref = _reference(cfg, batch)

    mesh = make_mesh(mesh_shape, ("data", "model"))
    pc = parallel_config_for(mesh, param_mode=mode)
    b = make_train_step(cfg, pc, mesh, OC, donate=False)
    # identical initial params: reuse the single-device init (global arrays)
    opt = init_opt_state(params0, pc, b.specs)
    p1, o1, m1 = b.train_step(params0, opt, batch)
    loss = float(m1["loss"])
    tol = _LOSS_TOL.get(arch, _DEFAULT_LOSS_TOL)
    assert abs(loss - loss_ref) < tol, (arch, mode, loss, loss_ref)
    # updated params must match the reference update
    err = max(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b_, np.float32)))
              for a, b_ in zip(jax.tree.leaves(jax.device_get(p_ref)),
                               jax.tree.leaves(jax.device_get(p1))))
    assert err < 5e-2, (arch, mode, err)
    print(f"ok {arch} {mode} mesh={mesh_shape} loss={loss:.4f} "
          f"ref={loss_ref:.4f} param_err={err:.2e}")


def check_decode_tp(arch: str, mesh_shape):
    cfg = get_reduced(arch)
    mesh1 = make_mesh((1, 1), ("data", "model"))
    pc1 = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(cfg, pc1, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    B = 2
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 8)), jnp.int32)

    def run(mesh, pc):
        bundle = make_serve_step(cfg, pc, mesh)
        # cache arrays are GLOBAL (batch dim sharded over dp by in_specs)
        caches = init_caches(cfg, pc, B, 32)
        lg, caches = bundle.serve_step(params, toks, caches, jnp.int32(0))
        lg2, _ = bundle.serve_step(
            params, jnp.argmax(lg[:, -1:], -1).astype(jnp.int32), caches,
            jnp.int32(8))
        return np.asarray(lg, np.float32), np.asarray(lg2, np.float32)

    a1, a2 = run(mesh1, pc1)
    mesh2 = make_mesh(mesh_shape, ("data", "model"))
    pc2 = parallel_config_for(mesh2, param_mode="dp")
    b1, b2 = run(mesh2, pc2)
    # scale-aware: bf16 accumulation-order changes across TP shards scale
    # with the logit magnitude (recurrentgemma's tied-embed logits ~ +-15)
    for a, b in [(a1, b1), (a2, b2)]:
        scale = max(np.abs(a).max(), 1.0)
        assert np.abs(a - b).max() / scale < 3e-2, (arch, np.abs(a-b).max())
    print(f"ok decode {arch} mesh={mesh_shape}")


def check_multipod():
    """(pod, data, model) = (2, 2, 2): hierarchical DP over (pod, data)."""
    arch = "granite_8b"
    cfg = get_reduced(arch)
    B, S = 4, 32
    batch = _batch(cfg, B, S)
    params0, p_ref, loss_ref = _reference(cfg, batch)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pc = parallel_config_for(mesh, param_mode="zero1")
    b = make_train_step(cfg, pc, mesh, OC, donate=False)
    opt = init_opt_state(params0, pc, b.specs)
    p1, _, m1 = b.train_step(params0, opt, batch)
    assert abs(float(m1["loss"]) - loss_ref) < 5e-2
    err = max(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(c, np.float32)))
              for a, c in zip(jax.tree.leaves(jax.device_get(p_ref)),
                              jax.tree.leaves(jax.device_get(p1))))
    assert err < 5e-2, err
    print(f"ok multipod zero1 loss={float(m1['loss']):.4f} err={err:.2e}")


def check_group_collectives():
    """Training with the paper's schedule executors at the TP boundary
    (collective_impl="group") must match the XLA-native collectives."""
    from dataclasses import replace
    arch = "granite_8b"
    cfg = get_reduced(arch)
    B, S = 4, 32
    batch = _batch(cfg, B, S)
    params0, p_ref, loss_ref = _reference(cfg, batch)
    mesh = make_mesh((2, 4), ("data", "model"))
    pc = replace(parallel_config_for(mesh, param_mode="dp"),
                 collective_impl="group")
    b = make_train_step(cfg, pc, mesh, OC, donate=False)
    opt = init_opt_state(params0, pc, b.specs)
    p1, _, m1 = b.train_step(params0, opt, batch)
    assert abs(float(m1["loss"]) - loss_ref) < 5e-2
    err = max(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(c, np.float32)))
              for a, c in zip(jax.tree.leaves(jax.device_get(p_ref)),
                              jax.tree.leaves(jax.device_get(p1))))
    assert err < 5e-2, err
    print(f"ok group_collectives loss={float(m1['loss']):.4f} err={err:.2e}")


def check_seq_shard_decode():
    """TP-sequence-sharded KV cache (flash-decoding LSE merge) must match
    the replicated-cache single-device decode (MQA arch)."""
    cfg = get_reduced("granite_34b")        # MQA: kv=1
    pc1 = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(cfg, pc1, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    B = 2
    toks = rng.integers(0, cfg.vocab, (B, 6)).astype(np.int32)

    def run(mesh, pc, seq_shard):
        bundle = make_serve_step(cfg, pc, mesh, seq_shard=seq_shard)
        caches = init_caches(cfg, pc, B, 32, seq_shard=seq_shard)
        pos, outs = 0, []
        for t in range(6):
            lg, caches = bundle.serve_step(
                params, jnp.asarray(toks[:, t:t+1]), caches,
                jnp.int32(pos))
            pos += 1
            outs.append(np.asarray(lg, np.float32))
        return np.concatenate(outs, axis=1)

    ref = run(make_mesh((1, 1), ("data", "model")), pc1, False)
    mesh2 = make_mesh((2, 4), ("data", "model"))
    got = run(mesh2, parallel_config_for(mesh2, param_mode="dp"), True)
    scale = max(np.abs(ref).max(), 1.0)
    assert np.abs(ref - got).max() / scale < 3e-2
    print("ok seq_shard_decode")


if __name__ == "__main__":
    which = sys.argv[1]
    if which == "modes":
        for mode in ["dp", "zero1", "fsdp"]:
            check_mode("granite_8b", mode, (2, 4))
    elif which == "archs_tp":
        for arch in ["granite_34b", "mixtral_8x7b", "recurrentgemma_2b",
                     "xlstm_1_3b", "hubert_xlarge", "pixtral_12b",
                     "deepseek_moe_16b", "command_r_plus_104b"]:
            check_mode(arch, "dp", (2, 2))
    elif which == "decode":
        for arch in ["granite_8b", "recurrentgemma_2b"]:
            check_decode_tp(arch, (2, 4))
    elif which == "multipod":
        check_multipod()
    elif which == "seqshard":
        check_seq_shard_decode()
    elif which == "groupcoll":
        check_group_collectives()
    else:
        raise SystemExit(f"unknown {which}")
    print("ALL-OK")
