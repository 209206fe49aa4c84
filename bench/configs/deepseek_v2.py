"""Plain reference of the DeepSeek-V2 decoder (arXiv:2405.04434, the
published ``modeling_deepseek.py``), no query compression: pre-norm
blocks of RMSNorm, multi-head latent attention, and a SwiGLU MLP (the
first ``first_k_dense_replace`` layers) or a routed expert layer with
shared experts; a final RMSNorm and an untied head.

Attention in the published, non-absorbed form: ``q = x W_q`` split into
``[q_nope, q_pe]`` per head; ``[c, k_pe] = x W_kva``, ``c`` normalised;
``[k_nope, v] = c W_kvb`` per head; ``q_pe`` and the one ``k_pe`` roped
as the published code does (each rope block viewed as interleaved
pairs, de-interleaved, then ``x cos + rotate_half(x) sin`` with YaRN's
frequencies and cos/sin factor); logits ``[q_nope, q_pe] . [k_nope,
k_pe]`` times ``qk_head_dim ** -0.5 * m ** 2``; softmax; ``v``; ``W_o``.

The expert layer is a chip's share (``held_experts`` in the
configuration): the gate is a softmax over all published experts, the
top ``num_experts_per_tok`` taken greedily and not renormalised
(``norm_topk_prob`` false), times ``routed_scaling_factor``; the layer
adds the held experts' gate-weighted outputs, each expert run on every
token and weighted by zero where it was not picked, and the shared
experts (one SwiGLU of ``n_shared_experts`` widths) once.

Straight ``jax.numpy`` in float32, every product at ``HIGHEST``, no
cache and no batching tricks; it imports nothing of the program and
makes its weights itself from the seed (``bench/weights_deepseek_v2.py``),
one layer at a time.  ``precision="fp8"`` is the control: both operands
of every product rounded to float8 (``llama.py``'s rounding).
"""
from __future__ import annotations

import math
import os
from functools import lru_cache
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import harness
import weights_deepseek_v2 as W

_llama = harness.load_module(os.path.join(harness.BENCH, "configs",
                                          "llama.py"),
                             "bench_reference_llama")
ein, mm, rmsnorm = _llama.ein, _llama.mm, _llama.rmsnorm


# ---------------------------------------------------------------- rope
def _yarn_m(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(c: dict, pos):
    """cos and sin (S, rope_dim) at positions ``pos`` (S,), as the
    published ``DeepseekV2YarnRotaryEmbedding`` caches them."""
    dim, base = c["qk_rope_head_dim"], c["rope_theta"]
    rs = c["rope_scaling"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = freq_inter * (1 - mask) + freq_extra * mask
    ms = _yarn_m(factor, rs["mscale"]) / _yarn_m(factor, rs["mscale_all_dim"])
    freqs = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], -1)
    return jnp.cos(emb) * ms, jnp.sin(emb) * ms


def apply_rope(x, cos, sin):
    """x (B, S, H, dim); cos/sin (S, dim)."""
    B, S, H, d = x.shape
    x = x.reshape(B, S, H, d // 2, 2).swapaxes(-1, -2).reshape(B, S, H, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def softmax_scale(c: dict) -> float:
    s = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    rs = c["rope_scaling"]
    if rs and rs.get("mscale_all_dim"):
        s *= _yarn_m(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


# ---------------------------------------------------------------- block
def attention(q, k, v, scale: float, precision: str, q_chunk: int = 256):
    """Causal attention, q/k (B, S, H, dq), v (B, S, H, dv), in query
    blocks of ``q_chunk`` rows."""
    B, S, H, _ = q.shape
    q_chunk = min(q_chunk, S)
    n = S // q_chunk
    kpos = jnp.arange(S)

    def one(args):
        qc, start = args
        s = ein("bqhd,bkhd->bhqk", qc, k, precision) * scale
        qpos = start + jnp.arange(q_chunk)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision)

    qs = q.reshape(B, n, q_chunk, H, -1).swapaxes(0, 1)
    out = jax.lax.map(one, (qs, jnp.arange(n) * q_chunk))
    return out.swapaxes(0, 1).reshape(B, S, H, v.shape[-1])


def mla(x, w, c: dict, precision: str):
    B, S, _ = x.shape
    n = W.dims(c)
    H, nope, rd, r = n["h"], n["nope"], n["rope"], n["r"]
    q = mm(x, w["wq"], precision).reshape(B, S, H, nope + rd)
    kva = mm(x, w["wkva"], precision)
    ckv = rmsnorm(kva[..., :r], w["kv_norm"], c["rms_norm_eps"])
    kv = mm(ckv, w["wkvb"], precision).reshape(B, S, H, nope + n["vd"])
    cos, sin = rope_tables(c, jnp.arange(S))
    q_pe = apply_rope(q[..., nope:], cos, sin)
    k_pe = apply_rope(kva[..., None, r:], cos, sin)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    kf = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, (B, S, H, rd))], -1)
    o = attention(qf, kf, kv[..., nope:], softmax_scale(c), precision)
    return mm(o.reshape(B, S, -1), w["wo"], precision)


def swiglu(h, w1, w3, w2, precision: str):
    g = jax.nn.silu(mm(h, w1, precision)) * mm(h, w3, precision)
    return mm(g, w2, precision)


def gate(h, w, c: dict, precision: str):
    """The gate's weight of every published expert for each token: its
    softmax probability where it is among the greedy top k, else 0; and
    the top-k expert ids."""
    probs = jax.nn.softmax(mm(h, w["router"], precision), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * c["routed_scaling_factor"]
    E = probs.shape[-1]
    weights = jnp.sum(jax.nn.one_hot(top_e, E) * top_p[..., None], -2)
    return weights, top_e


def experts(h, w, c: dict, precision: str):
    """The held experts' part of the expert layer, plus the shared
    experts; and the gate's top-k expert ids."""
    n = W.dims(c)
    weights, top_e = gate(h, w, c, precision)
    held = jax.lax.dynamic_slice_in_dim(weights, n["first"], n["held"], -1)

    def one(acc, e):
        y = swiglu(h, w["e_w1"][e], w["e_w3"][e], w["e_w2"][e], precision)
        return acc + held[..., e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n["held"]))
    return out + swiglu(h, w["s_w1"], w["s_w3"], w["s_w2"], precision), top_e


def block(x, w, c: dict, precision: str, dense: bool):
    """One decoder layer over x (B, S, d), positions 0..S-1.  Returns
    its output, the normalised input of its MLP, and the gate's top-k
    expert ids (None in a dense layer)."""
    eps = c["rms_norm_eps"]
    x = x + mla(rmsnorm(x, w["ln1"], eps), w, c, precision)
    h = rmsnorm(x, w["ln2"], eps)
    if dense:
        return x + swiglu(h, w["w1"], w["w3"], w["w2"], precision), h, None
    y, top_e = experts(h, w, c, precision)
    return x + y, h, top_e


def _frozen(c: dict) -> str:
    import json
    return json.dumps(c, sort_keys=True)


# ---------------------------------------------------------------- serving
@lru_cache(maxsize=None)
def _serve_fns(cf: str, precision: str):
    import json
    c = json.loads(cf)

    @jax.jit
    def embed(key, tokens):
        return W.top(c, key)["embed"][tokens]

    def run_layer(dense):
        @jax.jit
        def f(key, i, x, valid):
            w = W.layer(c, key, i, dense)
            x, h, mine = block(x, w, c, precision, dense)
            if dense:
                return x, jnp.int32(0)
            # tokens whose top-k set a router with bf16 operands and f32
            # accumulation would pick otherwise
            low = jnp.einsum("...k,kn->...n", h.astype(jnp.bfloat16),
                             w["router"].astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
            _, other = jax.lax.top_k(low, c["num_experts_per_tok"])
            same = jnp.all(jnp.sort(mine, -1) == jnp.sort(other, -1), -1)
            return x, jnp.sum(~same & valid)
        return f

    @jax.jit
    def score(key, x, rows, cols, tokens):
        t = W.top(c, key)
        h = rmsnorm(x[rows, cols], t["final_norm"], c["rms_norm_eps"])
        lg = mm(h, t["head"], precision)                   # (n, vocab)
        picked = jnp.take_along_axis(lg[None], tokens[..., None], -1)[..., 0]
        return lg.max(-1), picked, lg.argmax(-1)

    return embed, run_layer(True), run_layer(False), score


def serve_scores(c: dict, key, seqs: Sequence[np.ndarray],
                 positions: Sequence[np.ndarray],
                 tokens: Sequence[np.ndarray], *, seq_len: int,
                 max_positions: int, precision: str = "float32",
                 group: int = 2, flips: list = None
                 ) -> List[Tuple[np.ndarray, ...]]:
    """As ``llama.serve_scores``: full, cache-free passes over ``seqs``;
    for sequence ``j``, at each of ``positions[j]``, the best next-token
    logit, the logits of the token sets ``tokens[j]``, and the argmax.
    ``flips``, a list, receives per expert layer the number of tokens
    (of every sequence, padding left out) whose top-k a bf16 router
    would pick otherwise."""
    embed, dense_layer, moe_layer, score = _serve_fns(_frozen(c), precision)
    n = len(seqs)
    groups, valid = [], []
    for g in range(0, n, group):
        toks = np.zeros((group, seq_len), np.int32)
        ok = np.zeros((group, seq_len), bool)
        for j, s in enumerate(seqs[g:g + group]):
            toks[j, :len(s)] = s
            ok[j, :len(s)] = True
        groups.append(embed(key, toks))
        valid.append(ok)
    for i in range(c["num_hidden_layers"]):
        f = dense_layer if i < c["first_k_dense_replace"] else moe_layer
        outs = [f(key, i, x, ok) for x, ok in zip(groups, valid)]
        groups = [x for x, _ in outs]
        if flips is not None and f is moe_layer:
            flips.append(int(sum(int(k) for _, k in outs)))
    out = []
    for j in range(n):
        m = len(positions[j])
        tk = np.asarray(tokens[j], np.int32).reshape(-1, m)
        pos = np.zeros(max_positions, np.int32)
        pos[:m] = positions[j]
        sets = np.zeros((tk.shape[0], max_positions), np.int32)
        sets[:, :m] = tk
        best, picked, top = score(key, groups[j // group],
                                  np.full(max_positions, j % group, np.int32),
                                  pos, sets)
        out.append((np.asarray(best)[:m], np.asarray(picked)[:, :m],
                    np.asarray(top)[:m]))
    return out
