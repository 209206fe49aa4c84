"""Shared layers: norms, embeddings, MLPs, vocab-parallel cross-entropy.

Everything here runs *inside* shard_map (manual SPMD).  Parameter arrays
are the device-local shards; the companion ``ParamSpec`` tree (built in
:mod:`repro.models.model`) records which global dim each shard came from.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import ops as kops
from repro.parallel.api import (ParallelConfig, seq_all_gather,
                                seq_reduce_scatter, tp_psum, tp_rank)

COMPUTE_DTYPE = jnp.bfloat16


def norm_apply(p, x, *, kind: str = "rmsnorm", eps: float = 1e-5,
               impl: str = "xla"):
    if kind == "rmsnorm":
        return kops.norm(x, p["w"], eps=eps, impl=impl)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * p["w"] + p["b"]).astype(x.dtype)


def cast_weight(w, dtype):
    """``w`` in ``dtype``, under the ``weight_cast`` named scope, so a
    profile can tell the per-call cast of fp32 master weights from the
    matmul that reads them."""
    with jax.named_scope("weight_cast"):
        return w.astype(dtype)


def dense(x, w):
    """Local matmul in compute dtype.

    Output stays in the compute dtype (bf16): the MXU accumulates fp32
    internally for bf16 operands, and a fp32 output tensor would double
    both the live-buffer footprint and the bytes of any TP partial-sum
    reduce that follows."""
    return jax.lax.dot_general(
        x, cast_weight(w, x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=x.dtype)


# ---------------------------------------------------------------------------
#  vocab-parallel embedding
# ---------------------------------------------------------------------------

def embed_tokens(p, tokens, cfg, pc: ParallelConfig, *, sp: bool):
    """tokens (B, S) replicated -> activations.

    The embedding table is sharded over the vocab dim on the TP axis;
    each device embeds only tokens inside its shard, then the partial
    activations are summed and (with SP) scattered over the sequence.
    Output: (B, S/tp, d) if sp else (B, S, d).
    """
    # cast the (V/tp, d) table once; gathering from the fp32 master would
    # materialize a fp32 (B, S, d) tensor
    table = cast_weight(p["w"], COMPUTE_DTYPE)       # (V/tp, d) local
    vshard = table.shape[0]
    if vshard == cfg.vocab:
        # replicated table (vocab % tp != 0): full values, slice for SP
        out = jnp.take(table, tokens, axis=0)        # (B, S, d)
        if sp and pc.tp > 1:
            n = out.shape[1] // pc.tp
            out = lax.dynamic_slice_in_dim(out, tp_rank(pc) * n, n, 1)
        return out
    r = tp_rank(pc)
    lo = r * vshard
    idx = tokens - lo
    inside = (idx >= 0) & (idx < vshard)
    idx = jnp.clip(idx, 0, vshard - 1)
    out = jnp.take(table, idx, axis=0)               # (B, S, d) bf16
    out = jnp.where(inside[..., None], out, jnp.zeros((), COMPUTE_DTYPE))
    if pc.tp == 1:
        return out
    if sp:
        return seq_reduce_scatter(out, pc, axis=1)
    return tp_psum(out, pc)


def lm_head_logits(p, x, cfg, pc: ParallelConfig):
    """x (B, S, d) full-seq -> vocab-shard logits (B, S, V/tp) in fp32."""
    return jax.lax.dot_general(
        x, p["w"].astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def vocab_parallel_ce(p_head, x, labels, cfg, pc: ParallelConfig, *,
                      chunk: int = 512, sp: bool = False):
    """Chunked vocab-parallel cross entropy.

    x       (B, S, d) full sequence -- or, with ``sp=True``, the
            sequence-parallel shard (B, S/tp, d): each chunk is then
            all-gathered over TP *inside* the loop, so the full (B, S, d)
            hidden state never materializes (saves ~1.6 GB/device on the
            104B config) and the gather overlaps the head matmuls.
    labels  (B, S) int32 (always global); -1 = ignore
    Returns (sum_loss, n_valid) -- psum over DP by the caller for a
    global mean.

    Never materializes (B, S, V): only a (B, chunk, V/tp) logits shard
    exists per step; max/logsumexp/label-pick reduce over TP with psums.
    """
    B = x.shape[0]
    d = x.shape[-1]
    S = labels.shape[1]
    vshard = p_head["w"].shape[1]
    if vshard == cfg.vocab and pc.tp > 1 and sp:
        # replicated head (vocab % tp != 0): partition over the SEQUENCE
        # instead -- each device scores its own seq shard against the full
        # vocab, partial sums reduce over TP (grads of the replicated head
        # stay exact under the TP psum).
        r_ = tp_rank(pc)
        s_local = x.shape[1]
        lab = lax.dynamic_slice_in_dim(
            labels.reshape(B, pc.tp, s_local), r_, 1, 1)[:, 0]
        total, count = vocab_parallel_ce(
            p_head, x, lab, cfg,
            ParallelConfig(dp_axes=pc.dp_axes, dp=pc.dp, tp=1),
            chunk=chunk, sp=False)
        total = lax.psum(total, pc.tp_axis)
        count = lax.psum(count, pc.tp_axis)
        return total, count
    r = tp_rank(pc)
    lo = r * vshard
    if sp and pc.tp > 1:
        s_local = x.shape[1]
        lchunk = max(chunk // pc.tp, 1)
        n_chunks = -(-s_local // lchunk)
        pad = n_chunks * lchunk - s_local
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        xs = x.reshape(B, n_chunks, lchunk, d).swapaxes(0, 1)
        # global labels arranged device-major to match all_gather order,
        # padded per-device then chunked
        lab = labels.reshape(B, pc.tp, s_local)
        if pad:
            lab = jnp.pad(lab, ((0, 0), (0, 0), (0, pad)),
                          constant_values=-1)
        lab = lab.reshape(B, pc.tp, n_chunks, lchunk)
        ls = lab.transpose(2, 0, 1, 3).reshape(n_chunks, B,
                                               pc.tp * lchunk)
    else:
        n_chunks = -(-S // chunk)
        pad = n_chunks * chunk - S
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)),
                             constant_values=-1)
        xs = x.reshape(B, n_chunks, chunk, d).swapaxes(0, 1)   # (C,B,c,d)
        ls = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    def body(carry, inp):
        xc, lc = inp
        if sp and pc.tp > 1:
            xc = seq_all_gather(xc, pc, axis=1)                # (B, c, d)
        logits = lm_head_logits(p_head, xc, cfg, pc)           # (B, c, V/tp) f32
        # numerical stabilizer: mathematically gradient-free (cancels in
        # lse - picked), so stop_gradient keeps pmax out of the VJP.
        m = jnp.max(lax.stop_gradient(logits), axis=-1)
        if pc.tp > 1:
            m = lax.pmax(m, pc.tp_axis)
        m = lax.stop_gradient(m)
        z = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        z = tp_psum(z, pc)
        lse = m + jnp.log(z)
        li = lc - lo
        inside = (li >= 0) & (li < vshard)
        li = jnp.clip(li, 0, vshard - 1)
        picked = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        picked = tp_psum(jnp.where(inside, picked, 0.0), pc)
        valid = lc >= 0
        loss = jnp.where(valid, lse - picked, 0.0)
        s, n = carry
        return (s + jnp.sum(loss), n + jnp.sum(valid)), None

    # remat each chunk: the backward recomputes the (B, chunk, V/tp)
    # logits tile instead of stacking one per chunk (saves ~4 GB on the
    # 256k-vocab configs)
    body = jax.checkpoint(body, prevent_cse=False)
    (total, count), _ = lax.scan(body, (jnp.float32(0.0), jnp.int32(0)),
                                 (xs, ls))
    return total, count


# ---------------------------------------------------------------------------
#  MLPs
# ---------------------------------------------------------------------------

def mlp_apply(p, x, cfg, pc: ParallelConfig, *, act: Optional[str] = None):
    """Gated/plain MLP with d_ff sharded over TP.

    x (B, S, d) full-seq; returns (B, S, d) *partial* sums over TP --
    the caller reduce-scatters / psums at the block boundary.
    """
    act = act or cfg.act
    if act in ("swiglu", "geglu"):
        g = dense(x, p["w1"])
        u = dense(x, p["w3"])
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    else:
        h = jax.nn.gelu(dense(x, p["w1"]))
    return jax.lax.dot_general(
        h, cast_weight(p["w2"], h.dtype), (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=h.dtype)


# ---------------------------------------------------------------------------
#  rotary embeddings
# ---------------------------------------------------------------------------

def rope(q, k, positions, *, theta: float):
    """q,k: (B, H, S, D); positions (S,) or (B, S) absolute indices."""
    D = q.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
        ang = ang[None, None]
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[:, None]                                             # (B,1,S,half)
    # angles in fp32 (large theta), but the applied sin/cos drop to the
    # compute dtype: a bf16*f32 promotion here would send fp32 cotangents
    # back through the QKV projections (3 GB transients on the 104B cfg)
    sin = jnp.sin(ang).astype(q.dtype)
    cos = jnp.cos(ang).astype(q.dtype)

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _yarn_m(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(dim: int, theta: float, yarn=None):
    """Inverse frequencies of a ``dim``-wide rope and the factor on its
    cos/sin.  With :class:`~repro.models.config.YarnRope`, DeepSeek-V2's
    YaRN: frequencies interpolated by ``factor`` below the ``beta_fast``
    correction dimension, kept above ``beta_slow``'s, and a linear ramp
    between them."""
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return base.astype(np.float32), 1.0

    def corr(rot):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp               # 1: the original frequency
    inv = base / yarn.factor * (1.0 - keep) + base * keep
    scale = (_yarn_m(yarn.factor, yarn.mscale)
             / _yarn_m(yarn.factor, yarn.mscale_all_dim))
    return inv.astype(np.float32), scale


def attention_scale(cfg) -> float:
    """Softmax scale of attention logits: ``head_dim ** -0.5``, times
    YaRN's ``m(mscale_all_dim) ** 2`` where the config scales its rope."""
    s = cfg.hd ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        s *= _yarn_m(y.factor, y.mscale_all_dim) ** 2
    return s


def rope_pairs(x, positions, *, theta: float, yarn=None):
    """Rotary embedding of ``x`` (B, S, ..., D) at ``positions`` (S,) or
    (B, S), in DeepSeek-V2's pairing: the columns are read as interleaved
    pairs ``(2i, 2i + 1)`` and come out de-interleaved, first members
    then second, each pair rotated by frequency ``i``
    (:func:`rope_frequencies`).  Angles in fp32, the rotation in x's
    dtype, as :func:`rope`."""
    D = x.shape[-1]
    half = D // 2
    inv, scale = rope_frequencies(D, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * inv
    if positions.ndim == 1:
        ang = ang[None]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos = (jnp.cos(ang) * scale).astype(x.dtype)
    sin = (jnp.sin(ang) * scale).astype(x.dtype)
    x = x.reshape(x.shape[:-1] + (half, 2)).swapaxes(-1, -2).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)
