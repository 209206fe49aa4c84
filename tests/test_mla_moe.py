"""DeepSeek-V2-Lite's block against the benchmark's plain float32
reference (``bench/configs/deepseek_v2.py``), at the registry's
``REDUCED`` size on seeded weights: latent attention on the paged cache
(absorbed) and in the published form, and the dropless layer of held
experts."""
import copy
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import harness as H  # noqa: E402
from repro.configs import get_config, get_reduced  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models.attention import (LatentCache, PageCtx,  # noqa: E402
                                    PagedLatent, mla_block)
from repro.models.config import MoEConfig  # noqa: E402
from repro.models.layers import attention_scale  # noqa: E402
from repro.models.model import init_params  # noqa: E402
from repro.parallel.api import ParallelConfig  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402

PC = ParallelConfig(dp=1, tp=1)
REF = H.load_module(os.path.join(H.BENCH, "configs", "deepseek_v2.py"),
                    "bench_reference_deepseek_v2")
MODE = H.load_module(os.path.join(H.BENCH, "modes", "serve_mla_moe.py"),
                     "bench_mode_serve_mla_moe")
W = REF.W


def reduced_file(chip: int = 1, chips: int = 4) -> dict:
    """The benchmark configuration file of ``REDUCED``, holding experts
    of chip ``chip`` of ``chips``."""
    r = get_reduced("deepseek_v2_lite")
    m, y = r.moe, r.yarn
    c = copy.deepcopy(H.load_json(os.path.join(
        H.BENCH, "configs", "deepseek-v2-lite-serve.json")))
    c.update(hidden_size=r.d_model, intermediate_size=m.d_first_dense,
             moe_intermediate_size=m.d_expert,
             n_shared_experts=m.d_shared // m.d_expert,
             num_attention_heads=r.n_heads, num_key_value_heads=r.n_heads,
             kv_lora_rank=r.kv_lora_rank,
             qk_nope_head_dim=r.qk_nope_head_dim,
             qk_rope_head_dim=r.qk_rope_head_dim, v_head_dim=r.v_head_dim,
             vocab_size=r.vocab, num_experts_per_tok=m.top_k,
             n_routed_experts=m.n_experts // chips,
             num_hidden_layers=r.n_layers, rms_norm_eps=r.norm_eps)
    c["rope_scaling"]["original_max_position_embeddings"] = \
        y.original_max_position_embeddings
    c["published"]["n_routed_experts"] = m.n_experts
    c["held_experts"] = {"chip": chip, "chips": chips}
    return c


def program(c: dict):
    cfg = dataclasses.replace(
        get_reduced("deepseek_v2_lite"),
        moe=dataclasses.replace(get_reduced("deepseek_v2_lite").moe,
                                held=(c["held_experts"]["chip"],
                                      c["held_experts"]["chips"])))
    params = jax.jit(lambda k: W.program_tree(c, k))(KEY)
    return cfg, params


KEY = H.seed_key(2**34 + 7)


def _serve(cfg, params, prompts, max_new, slots=4):
    """Serve ``prompts`` through ``Engine``; per request, the logits
    each of its tokens was sampled from."""
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = Engine(cfg, PC, mesh, params, batch_slots=slots, max_len=128,
                 prefill_chunk=8, block_size=4)
    seen = {}
    sample = Engine._sample

    def record(self, row, uid, step):
        seen[(uid, step)] = np.array(row, np.float32)
        return sample(self, row, uid, step)

    eng._sample = record.__get__(eng)
    reqs = eng.generate([Request(prompt=p, max_new_tokens=max_new)
                         for p in prompts])
    return reqs, [np.stack([seen[(r.uid, j)] for j in range(max_new)])
                  for r in reqs]


def _reference_logits(c, reqs, precision="float32"):
    """The reference's full forward logits at the positions that
    predicted each served token."""
    embed, dense_layer, moe_layer, _ = REF._serve_fns(REF._frozen(c),
                                                      precision)
    out = []
    for r in reqs:
        seq = np.concatenate([r.prompt, r.out_tokens[:-1]]).astype(np.int32)
        x = embed(KEY, seq[None])
        for i in range(c["num_hidden_layers"]):
            f = dense_layer if i < c["first_k_dense_replace"] else moe_layer
            x, _ = f(KEY, i, x, np.ones((1, len(seq)), bool))
        t = W.top(c, KEY)
        h = REF.rmsnorm(x[0], t["final_norm"], c["rms_norm_eps"])
        lg = REF.mm(h, t["head"], precision)
        pos = len(r.prompt) - 1 + np.arange(len(r.out_tokens))
        out.append(np.asarray(lg)[pos])
    return out


def test_engine_logits_match_the_reference_forward():
    """Prefill in chunks, then paged decode, against the full forward.
    The program computes in bf16 (activations, products, the latent
    cache): its logits, of magnitude up to 4, lie within 0.1 of the
    float32 reference's (at most 0.056 over three prompt seeds: the
    rounding of ~10 bf16 products in series).  The same reference in
    float8, the precision below the one stated, lies at least 0.28 off
    on the same seeds, so it fails the tolerance."""
    c = reduced_file()
    cfg, params = program(c)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 19, 30)]
    reqs, got = _serve(cfg, params, prompts, max_new=6)
    want = _reference_logits(c, reqs)
    low = _reference_logits(c, reqs, "fp8")
    tol = 0.1
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=tol)
    assert min(float(np.abs(lw - w).max()) for lw, w in zip(low, want)) \
        > tol


def _mla_inputs(cfg, S=11, B=2, seed=0):
    params, _ = init_params(cfg, PC, jax.random.PRNGKey(seed))
    p = params["prefix"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.d_model),
                          jnp.float32)
    return p, x


def test_absorbed_decode_equals_the_published_form():
    """The cached paths (absorbed, over the latent) against the
    cache-free path (keys and values expanded), all in float32: equal to
    f32 rounding of the reassociated products."""
    cfg = get_reduced("deepseek_v2_lite")
    p, x = _mla_inputs(cfg)
    B, S, _ = x.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    want, _ = mla_block(p, x, cfg, PC, positions=pos)
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    # dense latent cache: a prompt of 7, then one token at a time
    cache = LatentCache(jnp.zeros((B, 1, 16, width), jnp.float32),
                        jnp.int32(0))
    got = []
    for lo, hi in [(0, 7)] + [(t, t + 1) for t in range(7, S)]:
        o, cache = mla_block(p, x[:, lo:hi], cfg, PC, positions=pos[lo:hi],
                             cache=cache)
        got.append(o)
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, rtol=1e-4,
                               atol=1e-5)
    # paged latent pool, blocks of 4 in a shuffled order
    bs, nb = 4, 1 + B * 4
    table = jnp.asarray(1 + np.random.default_rng(0).permutation(
        B * 4).reshape(B, 4), jnp.int32)
    pool = PagedLatent(jnp.zeros((nb, 1, bs, width), jnp.float32))
    got, length = [], 0
    for lo, hi in [(0, 7)] + [(t, t + 1) for t in range(7, S)]:
        ctx = PageCtx(table, jnp.full((B,), length, jnp.int32),
                      jnp.full((B,), hi - lo, jnp.int32),
                      jnp.zeros((B,), bool))
        ppos = length + jnp.arange(hi - lo, dtype=jnp.int32)[None]
        o, pool = mla_block(p, x[:, lo:hi], cfg, PC,
                            positions=jnp.broadcast_to(ppos, (B, hi - lo)),
                            cache=pool, paged=ctx)
        got.append(o)
        length = hi
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, rtol=1e-4,
                               atol=1e-5)


def _moe_params(cfg, held):
    m = dataclasses.replace(cfg.moe, held=held)
    c = dataclasses.replace(cfg, moe=m)
    params, _ = init_params(c, PC, jax.random.PRNGKey(5))
    return c, params["cycles"]["g0"]["mlp"]


def test_the_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips' held experts computed alone (each chip sharing
    the router), plus the shared expert once, equal the layer that holds
    all 16 experts, in float32."""
    cfg = get_reduced("deepseek_v2_lite")
    full_cfg, full = _moe_params(cfg, (0, 1))
    full = jax.tree.map(lambda a: a[0, 0], full)
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 10, cfg.d_model),
                          jnp.float32)
    want, _ = moe_lib.moe_apply(full, x, full_cfg, PC)
    n = 8
    per = cfg.moe.n_experts // n
    parts = []
    for i in range(n):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                             held=(i, n)))
        share = dict(full, experts={k: v[i * per:(i + 1) * per]
                                    for k, v in full["experts"].items()})
        y, _ = moe_lib.held_experts_apply(share, x.reshape(-1, cfg.d_model),
                                          None, c)
        parts.append(y)
    shared = moe_lib.mlp_apply(full["shared"], x, cfg, PC)
    got = sum(parts).reshape(x.shape) + shared
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_a_request_is_served_alike_alone_and_beside_seven_others():
    """Dropless: beside 7 requests of the same prompt, which route every
    token to the same experts (so that a capacity queue of the same
    layer overflows), a request's logits are those it gets alone, to f32
    rounding (the batch's shapes are the same)."""
    c = reduced_file()
    cfg, params = program(c)
    prompt = np.random.default_rng(4).integers(0, cfg.vocab, 21,
                                               dtype=np.int32)
    _, alone = _serve(cfg, params, [prompt], 5, slots=8)
    _, crowd = _serve(cfg, params, [prompt] * 8, 5, slots=8)
    for lg in crowd:
        np.testing.assert_allclose(lg, alone[0], rtol=1e-5, atol=1e-5)
    # a capacity queue of this layer overflows on a chunk tick's rows
    # (8 slots x 8) when they all route alike
    T = 8 * 8
    router = {"w": params["cycles"]["g0"]["mlp"]["router"]["w"][0, 0]}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, cfg.d_model))
    top_e, _, _ = moe_lib.route(router, jnp.repeat(x, T, 0), cfg.moe)
    _, _, keep = moe_lib.dispatch_indices(top_e, cfg.moe, T)
    assert not bool(jnp.all(keep))


@pytest.mark.parametrize("norm", [True, False])
def test_the_gate_renormalises_only_where_asked(norm):
    m = MoEConfig(n_experts=16, top_k=4, d_expert=8, norm_topk=norm)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((20, 12)), jnp.float32)
    w = {"w": jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)}
    top_e, top_p, _ = moe_lib.route(w, x, m)
    probs = jax.nn.softmax(x @ w["w"], axis=-1)
    picked = jnp.take_along_axis(probs, top_e, -1)
    if norm:
        np.testing.assert_allclose(top_p, picked / picked.sum(-1,
                                                              keepdims=True),
                                   rtol=1e-5)
    else:
        np.testing.assert_allclose(top_p, picked, rtol=1e-5)
        assert float(top_p.sum(-1).max()) < 1.0


def test_published_gates_and_scale():
    """DeepSeek's published gates do not renormalise; the attention
    scale is 192 ** -0.5 times YaRN's m ** 2, m = 0.1 * 0.707 * ln 40 +
    1."""
    for arch in ("deepseek_v2_lite", "deepseek_moe_16b"):
        assert get_config(arch).moe.norm_topk is False
    assert get_config("mixtral_8x7b").moe.norm_topk is True
    m = 0.1 * 0.707 * np.log(40) + 1
    assert attention_scale(get_config("deepseek_v2_lite")) == \
        pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert REF.softmax_scale(H.load_json(os.path.join(
        H.BENCH, "configs", "deepseek-v2-lite-serve.json"))) == \
        pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
