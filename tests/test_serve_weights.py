"""The serving engine's weights: ``Engine`` serves from
:func:`~repro.models.model.serve_weights` of the caller's fp32 master
tree, made once when it is built.  The attention, MLP (experts and
router included), embedding and head leaves are bf16 there, every other
leaf keeps its dtype, each keeps its placement, the caller's tree is
left as it was and not held, and a serve step computes the same logits
from the prepared tree as from the fp32 one."""
import dataclasses
import gc
import os
import subprocess
import sys
import textwrap
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch.mesh import make_mesh
from repro.models.attention import PageCtx
from repro.models.config import ModelConfig
from repro.models.model import (init_paged_caches, init_params,
                                serve_weights)
from repro.parallel.api import ParallelConfig
from repro.serve.engine import Engine, Request
from repro.train.step import make_paged_serve_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

TINY = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=160, vocab=256,
                   head_dim=16, act="swiglu")
PC = ParallelConfig(dp=1, tp=1)


def _mla_moe():
    """DeepSeek-V2-Lite at the registry's reduced size, holding the
    experts of chip 1 of 4, as ``tests/test_mla_moe.py`` serves it."""
    r = get_reduced("deepseek_v2_lite")
    return dataclasses.replace(r, moe=dataclasses.replace(r.moe,
                                                          held=(1, 4)))


CONFIGS = {"tiny": lambda: TINY, "mla_moe": _mla_moe}


def _cast(path) -> bool:
    """Whether the leaf at ``path`` is one ``serve_weights`` casts."""
    keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    return keys[0] in ("embed", "head") or (
        keys[0] in ("prefix", "cycles") and keys[2] in ("attn", "mlp"))


def _engine(cfg, params, **kw):
    mesh = make_mesh((1, 1), ("data", "model"))
    return Engine(cfg, PC, mesh, params, batch_slots=2, max_len=48,
                  prefill_chunk=8, block_size=4, temperature=0.0, **kw)


def _requests(cfg):
    rng = np.random.default_rng(5)
    return [Request(prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
                    max_new_tokens=4) for n in (3, 11, 6)]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_engine_params_are_bf16_where_the_step_casts(name):
    cfg = CONFIGS[name]()
    params, _ = init_params(cfg, PC, jax.random.PRNGKey(1))
    eng = _engine(cfg, params)
    got = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    want = jax.tree.leaves(params)
    assert len(got) == len(want)
    kinds = set()
    for (path, g), w in zip(got, want):
        cast = _cast(path)
        kinds.add(cast)
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        assert g.dtype == (jnp.bfloat16 if cast else w.dtype), \
            (jax.tree_util.keystr(path), g.dtype)
    assert kinds == {True, False}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_callers_tree_is_kept_as_it_was_and_serves_again(name):
    cfg = CONFIGS[name]()
    params, _ = init_params(cfg, PC, jax.random.PRNGKey(2))
    before = [np.asarray(a) for a in jax.tree.leaves(params)]
    first = _engine(cfg, params)
    a = first.generate(_requests(cfg))
    for x, y in zip(jax.tree.leaves(params), before):
        assert x.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(x), y)
    again = _engine(cfg, params, bundle=first.bundle)
    b = again.generate(_requests(cfg))
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]


def test_the_engine_holds_no_reference_to_the_callers_tree():
    params, _ = init_params(TINY, PC, jax.random.PRNGKey(3))
    refs = [weakref.ref(a) for a in jax.tree.leaves(params)]
    eng = _engine(TINY, params)
    del params
    gc.collect()
    assert all(r() is None for r in refs)
    [r] = eng.generate(_requests(TINY)[:1])
    assert len(r.out_tokens) == 4


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_paged_step_is_the_same_on_the_fp32_and_the_prepared_tree(name):
    """Prefill two rows at once, then three greedy decode ticks, from
    the fp32 tree (cast inside the step) and from ``serve_weights`` of
    it (cast before): the same logits, so the same tokens."""
    cfg = CONFIGS[name]()
    params, _ = init_params(cfg, PC, jax.random.PRNGKey(4))
    mesh = make_mesh((1, 1), ("data", "model"))
    bundle = make_paged_serve_step(cfg, PC, mesh)
    B, S, nb, bs = 2, 8, 4, 4
    table = jnp.asarray(1 + np.arange(B * nb, dtype=np.int32)
                        .reshape(B, nb))
    rng = np.random.default_rng(6)
    n0 = np.array([8, 5], np.int32)
    prompt = rng.integers(0, cfg.vocab, (B, S), dtype=np.int32)

    def serve(p):
        caches = init_paged_caches(cfg, PC, B, 1 + B * nb, bs)
        toks, lengths, n_new = jnp.asarray(prompt), np.zeros(B, np.int32), n0
        logits, picks = [], []
        for t in range(4):
            ctx = PageCtx(block_table=table, lengths=jnp.asarray(lengths),
                          n_new=jnp.asarray(n_new),
                          reset=jnp.full((B,), t == 0))
            lg, caches = bundle.serve_step(p, toks, caches, ctx)
            lg = np.asarray(lg[:, 0], np.float32)
            logits.append(lg)
            picks.append(lg.argmax(-1).astype(np.int32))
            lengths = lengths + n_new
            n_new = np.ones(B, np.int32)
            toks = jnp.asarray(picks[-1][:, None])
        return np.stack(logits), np.stack(picks)

    prepared = jax.jit(serve_weights)(params)
    lg32, t32 = serve(params)
    lg16, t16 = serve(prepared)
    np.testing.assert_array_equal(lg16, lg32)
    np.testing.assert_array_equal(t16, t32)


_SHARDED = textwrap.dedent("""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.models.config import ModelConfig
    from repro.models.model import init_params
    from repro.serve.engine import Engine, Request
    from repro.train.step import param_pspecs

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=160, vocab=256,
                      head_dim=16, act="swiglu")
    for shape, mode in (((1, 2), "dp"), ((2, 1), "fsdp")):
        mesh = make_mesh(shape, ("data", "model"), devices=jax.devices()[:2])
        pc = parallel_config_for(mesh, param_mode=mode)
        params, specs = init_params(cfg, pc, jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, param_pspecs(params, specs, pc))
        eng = Engine(cfg, pc, mesh, params, batch_slots=1, max_len=32,
                     prefill_chunk=8, block_size=4)
        src, got = jax.tree.leaves(params), jax.tree.leaves(eng.params)
        assert len(src) == len(got)
        for a, b in zip(src, got):
            assert b.sharding == a.sharding, (mode, a.sharding, b.sharding)
        split = sum(not a.sharding.is_fully_replicated for a in src)
        reqs = eng.generate([Request(prompt=np.arange(5, dtype=np.int32)
                                     + 1, max_new_tokens=3)])
        print(mode, split, len(reqs[0].out_tokens))
""")


def test_prepared_leaves_keep_their_placement_on_a_two_device_mesh():
    """Tensor-parallel (1 x 2) and FSDP (2 x 1) placements of the
    caller's leaves are the prepared leaves' placements, and the engine
    serves from them."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", _SHARDED],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines())
    assert set(lines) == {"dp", "fsdp"}
    for mode, rest in lines.items():
        split, served = map(int, rest.split())
        assert split > 0 and served == 3, (mode, rest)
