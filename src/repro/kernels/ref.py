"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def fused_combine_ref(a: jnp.ndarray, b: jnp.ndarray,
                      accum_dtype=jnp.float32) -> jnp.ndarray:
    """Elementwise combine (the allreduce reduction op) with fp32 accum."""
    return (a.astype(accum_dtype) + b.astype(accum_dtype)).astype(a.dtype)


def combine_n_ref(stack: jnp.ndarray, accum_dtype=jnp.float32) -> jnp.ndarray:
    """Sum K rows: stack (K, n) -> (n,). fp32 accumulation."""
    return jnp.sum(stack.astype(accum_dtype), axis=0).astype(stack.dtype)


def rmsnorm_ref(x: jnp.ndarray, w: jnp.ndarray,
                eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def chunked_attention_ref(q, k, v, *, causal=True, window=None, scale=None,
                          kv_valid=None, q_positions=None,
                          q_chunk: int = 256):
    """Memory-bounded XLA attention: lax.map over query chunks, so only a
    (B, H, q_chunk, Skv) logits tile is ever live.  Same math/masking as
    :func:`flash_attention_ref`; used for long sequences where the full
    (Sq, Skv) logits tensor would not fit (the dry-run path -- the Pallas
    flash kernel is the on-hardware equivalent)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = D ** -0.5
    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32) + (Skv - Sq)
    q_chunk = min(q_chunk, Sq)
    pad = (-Sq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad))
    n = q.shape[2] // q_chunk
    qs = q.reshape(B, Hq, n, q_chunk, D).transpose(2, 0, 1, 3, 4)
    ps = q_positions.reshape(n, q_chunk)
    kpos = jnp.arange(Skv, dtype=jnp.int32)[None, :]

    def one(args):
        qc, pc_ = args
        logits = jnp.einsum("bhqd,bhkd->bhqk", qc.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        qpos = pc_[:, None]
        mask = jnp.ones((q_chunk, Skv), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kv_valid is not None:
            mask &= kpos < kv_valid
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        m = jnp.max(logits, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(logits - m)
        p = jnp.where(mask[None, None], p, 0.0)
        den = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        return (jnp.einsum("bhqk,bhkd->bhqd", p / den,
                           v.astype(jnp.float32))).astype(q.dtype)

    # flash-style backward: recompute each chunk's logits/probabilities
    # instead of saving the (B, H, q_chunk, Skv) tiles across all chunks
    one = jax.checkpoint(one, prevent_cse=False)
    out = jax.lax.map(one, (qs, ps))
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, n * q_chunk, D)
    return out[:, :, :Sq]


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        kv_valid=None,
                        q_positions=None,
                        return_lse: bool = False):
    """Reference attention.  q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D).

    GQA: Hq must be a multiple of Hkv; kv heads are repeated.
    ``window``: sliding-window attention -- query i attends to keys in
    (i_abs - window, i_abs] where i_abs = i + (Skv - Sq) (decode offset).
    ``kv_valid``: traced scalar or per-row ``(B,)`` vector -- keys at
    index >= kv_valid are masked (KV-cache decode over a fixed-size
    buffer; the vector form serves continuous batching, where every
    batch row sits at its own sequence length).
    ``q_positions``: (Sq,) or per-row (B, Sq) absolute query positions
    overriding the tail-alignment default (cache decode / prefill into
    a larger buffer).

    Training (with its backward), the dense-cache and the seq-sharded
    decode paths attend with this function.  The paged serve path does
    not: :func:`repro.models.attention.paged_attention` reads the
    gathered blocks without the repeat and the f32 copies, and this
    function over :func:`repro.models.attention.paged_view` is its
    oracle.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    # mask shape: (Sq, Skv) shared, or (B, Sq, Skv) when any constraint
    # is per-row (vector kv_valid / 2-D q_positions)
    if q_positions is None:
        qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)
    else:
        qpos = q_positions.astype(jnp.int32)[..., :, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    if kv_valid is not None:
        kv_valid = jnp.asarray(kv_valid)
        if kv_valid.ndim == 1:
            mask = mask & (kpos[None] < kv_valid[:, None, None])
        else:
            mask = mask & (kpos < kv_valid)
    if mask.ndim == 2:
        mask = mask[None, None]
    else:
        mask = mask[:, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    if return_lse:
        m = jnp.max(logits, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        den = jnp.sum(p, axis=-1)
        lse = jnp.where(den > 0, m_safe + jnp.log(jnp.maximum(den, 1e-30)),
                        -jnp.inf)
        o = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(den, 1e-30)[..., None],
                       v.astype(jnp.float32)).astype(q.dtype)
        return o, lse
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully masked rows (can't happen causally)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
