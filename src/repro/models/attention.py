"""GQA/MQA/SWA and latent attention with Megatron-style TP sharding +
KV caches.

Sharding: query heads are sharded over the TP axis.  KV projections are
sharded over KV heads when n_kv_heads >= tp; otherwise (GQA groups wider
than one device, or MQA) the KV projection is *replicated* and each device
dynamically slices the KV head(s) its query heads attend to.  Replicated
KV grads are exact under a TP psum because each device's grad carries only
its own query heads' contribution (disjoint slices of the true gradient).

Latent attention (MLA, DeepSeek-V2; ``cfg.mla``) is :func:`mla_block`.
Its caches hold one latent per token, ``[c, k_pe]`` of ``kv_lora_rank +
qk_rope_head_dim`` values, the same on every TP rank
(:class:`PagedLatent`, :class:`LatentCache`); query heads shard over TP.
Training attends in the published form, keys and values expanded from
the latent; every cached path attends in the absorbed form over the
latent itself, through :func:`paged_attention`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops as kops
from repro.models.layers import (COMPUTE_DTYPE, attention_scale,
                                 cast_weight, dense, norm_apply, rope,
                                 rope_pairs)
from repro.parallel.api import ParallelConfig, tp_rank


class KVCache(NamedTuple):
    """KV cache; ``rolling`` (ring-buffer mode) is passed statically to the
    apply functions rather than stored, so the cache stacks cleanly as a
    scan-able pytree."""

    k: jnp.ndarray          # (B, Hkv_local, S_max_or_window, hd)
    v: jnp.ndarray
    pos: jnp.ndarray        # scalar int32: tokens already in cache


class PagedKV(NamedTuple):
    """Blockwise (paged) KV cache: one shared physical pool per layer.

    ``k``/``v`` are ``(n_blocks, Hkv_local, block_size, hd)`` pools.
    Which physical block backs logical block ``j`` of batch slot ``b``
    lives OUTSIDE the cache, in the per-step :class:`PageCtx` block
    table (host-managed by :class:`repro.serve.kv.KVBlockManager`).
    Physical block 0 is the *garbage block*: unallocated table entries
    and padding-token writes land there and are never read back (per-row
    ``kv_valid`` masks everything past each slot's written length).

    No ``pos`` scalar: continuous batching needs per-row positions,
    which the engine tracks host-side and passes via ``PageCtx``.
    """

    k: jnp.ndarray
    v: jnp.ndarray


class PagedLatent(NamedTuple):
    """Paged cache of latent attention: one ``(n_blocks, 1, block_size,
    kv_lora_rank + qk_rope_head_dim)`` pool per layer holding ``[c,
    k_pe]`` of each token (the normalised latent, then the roped shared
    key), with :class:`PagedKV`'s block table, garbage block and
    per-row lengths."""

    kv: jnp.ndarray


class LatentCache(NamedTuple):
    """Dense cache of latent attention: ``(B, 1, S_max, kv_lora_rank +
    qk_rope_head_dim)`` and, as :class:`KVCache`, the tokens written."""

    kv: jnp.ndarray
    pos: jnp.ndarray


class PageCtx(NamedTuple):
    """Per-step paged-decode context (all leaves are arrays, so the ctx
    crosses ``shard_map`` as an ordinary pytree).

    block_table: (B, nb_max) int32 -- physical block of each logical
                 block per slot (0 = garbage block for unallocated).
    lengths:     (B,) int32 -- tokens already in each slot's cache.
    n_new:       (B,) int32 -- valid new tokens this step per slot
                 (0 = row inactive this tick; tokens past ``n_new`` are
                 right-padding whose cache writes are dropped).
    reset:       (B,) bool -- slots freshly admitted this step whose
                 recurrent state must restart from the initial state.
    """

    block_table: jnp.ndarray
    lengths: jnp.ndarray
    n_new: jnp.ndarray
    reset: jnp.ndarray


def attn_replicated(cfg, pc: ParallelConfig) -> bool:
    """True when the query-head count does not divide TP (e.g.
    recurrentgemma's 10 heads on a 16-way model axis).  Attention then
    computes all heads on every TP device and the block boundary *slices*
    the sequence-parallel shard instead of reducing -- the same rule as
    sLSTM.  Wasteful but exact; the natural production mesh for such small
    models is DP-dominant anyway (documented in DESIGN.md)."""
    return pc.tp > 1 and cfg.n_heads % pc.tp != 0


def local_kv_heads(cfg, pc: ParallelConfig) -> int:
    if attn_replicated(cfg, pc):
        return cfg.n_kv_heads
    return max(cfg.n_kv_heads // pc.tp, 1)


def local_q_heads(cfg, pc: ParallelConfig) -> int:
    if attn_replicated(cfg, pc):
        return cfg.n_heads
    assert cfg.n_heads % pc.tp == 0, (cfg.name, cfg.n_heads, pc.tp)
    return cfg.n_heads // pc.tp


def kv_replicated(cfg, pc: ParallelConfig) -> bool:
    return cfg.n_kv_heads < pc.tp and not attn_replicated(cfg, pc)


def _slice_kv(kv, cfg, pc: ParallelConfig):
    """From a replicated (B, S, Hkv*hd) projection, slice the single KV
    head this device's query heads map to."""
    hd = cfg.hd
    B, S = kv.shape[:2]
    kv = kv.reshape(B, S, cfg.n_kv_heads, hd)
    dev_per_kv = pc.tp // cfg.n_kv_heads
    h = tp_rank(pc) // dev_per_kv
    kv = lax.dynamic_slice_in_dim(kv, h, 1, axis=2)
    return kv  # (B, S, 1, hd)


def qkv_project(p, xg, cfg, pc: ParallelConfig):
    """xg (B, S, d) full-seq -> q (B, Hl, S, hd), k/v (B, Hkv_l, S, hd)."""
    B, S, _ = xg.shape
    hd = cfg.hd
    hl = local_q_heads(cfg, pc)
    q = dense(xg, p["wq"]).reshape(B, S, hl, hd).swapaxes(1, 2)
    k = dense(xg, p["wk"])
    v = dense(xg, p["wv"])
    if kv_replicated(cfg, pc) and pc.tp > 1:
        k = _slice_kv(k, cfg, pc).swapaxes(1, 2)
        v = _slice_kv(v, cfg, pc).swapaxes(1, 2)
    else:
        hkl = local_kv_heads(cfg, pc)
        k = k.reshape(B, S, hkl, hd).swapaxes(1, 2)
        v = v.reshape(B, S, hkl, hd).swapaxes(1, 2)
    return q, k, v


def attention_block(p, xg, cfg, pc: ParallelConfig, *,
                    window: Optional[int], positions: jnp.ndarray,
                    cache: Optional[KVCache] = None,
                    rolling: bool = False, seq_shard: bool = False,
                    paged: Optional[PageCtx] = None,
                    attn_impl: str = "xla"
                    ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Temporal mixing via attention.

    xg: (B, S, d) gathered full sequence (S=1 for decode).
    Returns (B, S, d) **partial over TP** output (caller reduces), and the
    updated cache (decode path).
    """
    B, S, _ = xg.shape
    if cfg.mla:
        assert not (rolling or seq_shard or window), \
            "latent attention has no window, rolling or seq-sharded cache"
        return mla_block(p, xg, cfg, pc, positions=positions, cache=cache,
                         paged=paged, attn_impl=attn_impl)
    if isinstance(cache, PagedKV):
        assert paged is not None, "PagedKV caches need a PageCtx"
        q, k, v = qkv_project(p, xg, cfg, pc)
        q, k = rope(q, k, positions, theta=cfg.rope_theta)   # (B, S) pos
        cache = paged_cache_update(cache, k, v, paged)
        o = paged_attention(q, cache.k, cache.v, paged.block_table,
                            paged.lengths + paged.n_new,     # per row
                            positions,                       # (B, S)
                            window, causal=cfg.causal)
        o = o.swapaxes(1, 2).reshape(B, S, -1)
        out = jax.lax.dot_general(
            o, cast_weight(p["wo"], o.dtype), (((2,), (0,)), ((), ())),
            preferred_element_type=o.dtype)
        return out, cache
    if cache is not None and seq_shard:
        o_full, cache = seq_shard_decode(p, xg, cfg, pc,
                                         positions=positions, cache=cache,
                                         attn_impl=attn_impl)
        # slice this device's query heads for the sharded out-projection
        span = local_q_heads(cfg, pc) * cfg.hd
        o = lax.dynamic_slice_in_dim(o_full, tp_rank(pc) * span, span, 2)
        out = jax.lax.dot_general(
            o, cast_weight(p["wo"], o.dtype), (((2,), (0,)), ((), ())),
            preferred_element_type=o.dtype)
        return out, cache
    q, k, v = qkv_project(p, xg, cfg, pc)
    q, k = rope(q, k, positions, theta=cfg.rope_theta)

    if cache is None:
        o = kops.attention(q, k, v, causal=cfg.causal, window=window,
                           impl=attn_impl)
    else:
        if rolling:
            assert S == 1, "rolling (windowed) caches support decode only"
        k, v, cache, kv_valid = _cache_update(cache, k, v, window,
                                              rolling=rolling)
        o = kops.attention(
            q, k, v,
            # prefill into a cache still needs causality among new tokens
            causal=cfg.causal and S > 1,
            # rolling buffers hold only in-window keys by construction
            window=None if rolling else window,
            kv_valid=kv_valid,
            q_positions=None if rolling else positions.reshape(-1),
            impl=attn_impl)
    o = o.swapaxes(1, 2).reshape(B, S, -1)           # (B, S, Hl*hd)
    out = jax.lax.dot_general(
        o, cast_weight(p["wo"], o.dtype), (((2,), (0,)), ((), ())),
        preferred_element_type=o.dtype)
    return out, cache


def _cache_update(cache: KVCache, k_new, v_new, window, *, rolling: bool):
    """Insert the new token(s) into the cache; return full K/V to attend
    over plus the traced valid length."""
    B, H, S_new, hd = k_new.shape
    if rolling:
        W = cache.k.shape[2]
        slot = cache.pos % W
        k = lax.dynamic_update_slice(cache.k, k_new, (0, 0, slot, 0))
        v = lax.dynamic_update_slice(cache.v, v_new, (0, 0, slot, 0))
        new = KVCache(k, v, cache.pos + S_new)
        valid = jnp.minimum(cache.pos + S_new, W)
        return k, v, new, valid
    k = lax.dynamic_update_slice(cache.k, k_new, (0, 0, cache.pos, 0))
    v = lax.dynamic_update_slice(cache.v, v_new, (0, 0, cache.pos, 0))
    new = KVCache(k, v, cache.pos + S_new)
    return k, v, new, cache.pos + S_new


def _pool_heads(cfg, pc: ParallelConfig) -> int:
    """KV-head count of one device's cache pool (same rule as the dense
    :func:`init_cache` without seq-sharding)."""
    if attn_replicated(cfg, pc):
        return cfg.n_kv_heads
    if kv_replicated(cfg, pc) and pc.tp > 1:
        return 1
    return local_kv_heads(cfg, pc)


def init_paged_pool(cfg, pc: ParallelConfig, n_blocks: int,
                    block_size: int, dtype=COMPUTE_DTYPE) -> PagedKV:
    """One layer's physical KV block pool (block 0 = garbage block): K
    and V pools of the layer's KV heads, or for latent attention one
    latent pool (:class:`PagedLatent`)."""
    if cfg.mla:
        return PagedLatent(jnp.zeros((n_blocks, 1, block_size,
                                      _latent_width(cfg)), dtype))
    shape = (n_blocks, _pool_heads(cfg, pc), block_size, cfg.hd)
    return PagedKV(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _page_slots(ctx: PageCtx, S: int, pool_shape):
    """Physical ``(block, offset)`` of token ``t`` of each row, ``(B,
    S)`` each; padding tokens get the out-of-range block ``n_blocks``."""
    nb, _, bs, _ = pool_shape
    pos = ctx.lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = jnp.arange(S)[None, :] < ctx.n_new[:, None]          # (B, S)
    logical = jnp.clip(pos // bs, 0, ctx.block_table.shape[1] - 1)
    blk = jnp.take_along_axis(ctx.block_table, logical, axis=1)  # (B, S)
    blk = jnp.where(valid, blk, nb)          # OOB sentinel: dropped
    return blk, pos % bs


def paged_cache_update(cache: PagedKV, k_new, v_new, ctx: PageCtx):
    """Scatter the new token(s) of every slot into the shared pool.

    Token ``t`` of row ``b`` lands at logical position ``lengths[b] +
    t``, i.e. physical ``(block_table[b, pos // bs], :, pos % bs)``.
    Padding tokens (``t >= n_new[b]``) are routed to an out-of-range
    block index and dropped by the scatter -- they neither advance any
    slot nor scribble on another slot's blocks.
    """
    blk, off = _page_slots(ctx, k_new.shape[2], cache.k.shape)
    kk = jnp.swapaxes(k_new, 1, 2).astype(cache.k.dtype)   # (B, S, H, hd)
    vv = jnp.swapaxes(v_new, 1, 2).astype(cache.v.dtype)
    k = cache.k.at[blk, :, off].set(kk, mode="drop")
    v = cache.v.at[blk, :, off].set(vv, mode="drop")
    return PagedKV(k, v)


def paged_attention(q, pool_k, pool_v, block_table, kv_valid, q_positions,
                    window: Optional[int] = None, *, causal: bool = True,
                    scale: Optional[float] = None,
                    v_dim: Optional[int] = None):
    """Attention of every slot's new tokens over its paged cache.

    q: ``(B, Hq, S, hd)``; pools ``(n_blocks, Hkv, bs, hd)`` and
    ``(n_blocks, Hkv, bs, dv)``; ``block_table`` ``(B, nb_max)``;
    ``kv_valid`` ``(B,)`` written length of each slot; ``q_positions``
    ``(B, S)`` absolute positions.  Returns ``(B, Hq, S, dv)`` in q's
    dtype.  ``scale`` defaults to ``hd ** -0.5``; ``v_dim`` takes the
    values as the first ``v_dim`` columns of ``pool_v`` (latent
    attention passes its one latent pool as both, ``Hkv = 1``).

    The same masks as :func:`repro.kernels.ref.flash_attention_ref`
    over :func:`paged_view` (its oracle in the tests), and the products
    the TPU runs for that oracle's f32 einsums at default precision
    (bf16 operands, f32 accumulation), without its copies: the query
    heads of one KV head form a group ``G = Hq // Hkv`` that contracts
    against the gathered ``(B, nb_max, Hkv, bs, hd)`` view with the KV
    head as a batch dimension, so K/V are never repeated nor copied to
    f32; the softmax runs in f32.  A row with nothing to attend to
    (``kv_valid`` 0) gives zeros.  Forward only: the serve path has no
    backward.
    """
    B, Hq, S, hd = q.shape
    _, Hkv, bs, _ = pool_k.shape
    nbm = block_table.shape[1]
    k = pool_k[block_table]                       # (B, nbm, Hkv, bs, hd)
    v = pool_v[block_table]
    if v_dim is not None:
        v = v[..., :v_dim]
    qg = q.astype(k.dtype).reshape(B, Hkv, Hq // Hkv, S, hd)
    logits = jnp.einsum("bkgsd,bnktd->bkgsnt", qg, k,
                        preferred_element_type=jnp.float32) * (
        hd ** -0.5 if scale is None else scale)
    kpos = jnp.arange(nbm * bs, dtype=jnp.int32).reshape(nbm, bs)
    qpos = q_positions.astype(jnp.int32)[:, :, None, None]   # (B, S, 1, 1)
    mask = kpos < kv_valid.astype(jnp.int32)[:, None, None, None]
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    mask = mask[:, None, None]                    # (B, 1, 1, S|1, nbm, bs)
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=(-2, -1), keepdims=True)
    p = jnp.exp(logits - jnp.where(jnp.isfinite(m), m, 0.0))
    den = jnp.sum(p, axis=(-2, -1))               # (B, Hkv, G, S)
    o = jnp.einsum("bkgsnt,bnktd->bkgsd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(den, 1e-30)[..., None]
    return o.reshape(B, Hq, S, v.shape[-1]).astype(q.dtype)


def paged_view(cache: PagedKV, block_table):
    """Gather each slot's logical cache view from the pool.

    Returns ``(B, H, nb_max * bs, hd)`` K/V where row ``b``'s sequence
    axis is its own logical positions (garbage past ``kv_valid``).
    The serve path attends with :func:`paged_attention`, which reads
    the gathered blocks without this view's copy; the view is what its
    oracle attends over.
    """
    B, nbm = block_table.shape
    _, H, bs, hd = cache.k.shape
    kv = []
    for pool in (cache.k, cache.v):
        view = pool[block_table]                  # (B, nbm, H, bs, hd)
        view = jnp.moveaxis(view, 2, 1).reshape(B, H, nbm * bs, hd)
        kv.append(view)
    return kv[0], kv[1]


def init_cache(cfg, pc: ParallelConfig, batch_local: int, max_len: int,
               *, rolling_window: Optional[int] = None,
               seq_shard: bool = False, dtype=COMPUTE_DTYPE) -> KVCache:
    if cfg.mla:
        assert rolling_window is None and not seq_shard
        return LatentCache(jnp.zeros((batch_local, 1, max_len,
                                      _latent_width(cfg)), dtype),
                           jnp.int32(0))
    if attn_replicated(cfg, pc):
        H = cfg.n_kv_heads
    elif kv_replicated(cfg, pc) and pc.tp > 1:
        H = 1 if not seq_shard else cfg.n_kv_heads
    else:
        H = local_kv_heads(cfg, pc)
    L = rolling_window if rolling_window else max_len
    if seq_shard:
        assert pc.tp > 1 and rolling_window is None
        assert L % pc.tp == 0
        # KV heads stay whole (replicated-KV archs); the SEQUENCE dim of
        # the (GLOBAL) cache shards over TP via the in_specs -- inside
        # shard_map each device sees its L/tp slice (flash-decoding).
        H = cfg.n_kv_heads
    shape = (batch_local, H, L, cfg.hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.int32(0))


def seq_shard_decode(p, xg, cfg, pc: ParallelConfig, *,
                     positions, cache: KVCache, attn_impl: str = "xla"):
    """Decode attention against a TP-sequence-sharded KV cache.

    Motivation: MQA/low-kv-head archs cannot shard the cache over heads,
    so a 32k x batch-128 cache replicates ~11 GB per device.  Here device
    r owns cache slots [r*Ls, (r+1)*Ls); each device scores *all* query
    heads (q gathered over TP -- trivial at S_new=1) against its slice and
    the partial outputs merge with a log-sum-exp-weighted psum
    (flash-decoding across the model axis).  Cache memory drops by tp.

    Returns ((B, 1, Hq*hd) full-head attention output replicated over TP,
    new cache).  The caller slices its local heads for the out-projection.
    """
    from jax import lax as _lax
    B, S, _ = xg.shape
    assert S == 1, "seq-sharded caches are a decode-path feature"
    hd = cfg.hd
    hl = local_q_heads(cfg, pc)
    q = dense(xg, p["wq"]).reshape(B, S, hl, hd).swapaxes(1, 2)
    # KV projections are replicated for these archs: keep ALL kv heads
    k_new = dense(xg, p["wk"]).reshape(B, S, cfg.n_kv_heads, hd) \
        .swapaxes(1, 2)
    v_new = dense(xg, p["wv"]).reshape(B, S, cfg.n_kv_heads, hd) \
        .swapaxes(1, 2)
    # gather all query heads (tiny at S_new=1)
    if pc.tp > 1:
        q = _lax.all_gather(q, pc.tp_axis, axis=1, tiled=True)
    q, k_new = rope(q, k_new, positions, theta=cfg.rope_theta)

    Ls = cache.k.shape[2]
    r = tp_rank(pc)
    pos = cache.pos
    local_slot = pos - r * Ls
    owner = (local_slot >= 0) & (local_slot < Ls)
    ins = jnp.clip(local_slot, 0, Ls - 1)
    k_upd = _lax.dynamic_update_slice(cache.k, k_new, (0, 0, ins, 0))
    v_upd = _lax.dynamic_update_slice(cache.v, v_new, (0, 0, ins, 0))
    k_c = jnp.where(owner, k_upd, cache.k)
    v_c = jnp.where(owner, v_upd, cache.v)
    new_cache = KVCache(k_c, v_c, pos + 1)

    valid_local = jnp.clip(pos + 1 - r * Ls, 0, Ls)
    o, lse = kops.attention(q, k_c, v_c, causal=False, window=None,
                            kv_valid=valid_local, impl=attn_impl,
                            return_lse=True)
    # LSE merge across the TP slices
    m = _lax.pmax(lse, pc.tp_axis)                        # (B, Hq, 1)
    w = jnp.exp(lse - jnp.where(jnp.isfinite(m), m, 0.0))
    w = jnp.where(jnp.isfinite(lse), w, 0.0)
    num = _lax.psum(o.astype(jnp.float32) * w[..., None], pc.tp_axis)
    den = _lax.psum(w, pc.tp_axis)
    o = (num / jnp.maximum(den, 1e-30)[..., None]).astype(xg.dtype)
    o = o.swapaxes(1, 2).reshape(B, S, -1)                # (B, 1, Hq*hd)
    return o, new_cache


# ---------------------------------------------------------------- latent
def _latent_width(cfg) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def mla_block(p, xg, cfg, pc: ParallelConfig, *, positions, cache=None,
              paged: Optional[PageCtx] = None, attn_impl: str = "xla"):
    """Multi-head latent attention (DeepSeek-V2, no query compression).

    Per token: ``q = x W_q`` -> heads of ``[q_nope, q_pe]``; ``[c, k_pe]
    = x W_kva``, ``c`` RMS-normalised (``kv_norm``); ``[k_nope, v] = c
    W_kvb`` per head; ``q_pe`` and the one shared ``k_pe`` are roped
    (:func:`~repro.models.layers.rope_pairs`, with YaRN); the logits of
    ``[q_nope, q_pe] . [k_nope, k_pe]`` scale by
    :func:`~repro.models.layers.attention_scale`; ``o = softmax . v``,
    then ``o W_o``.

    Without a cache (training) that is computed as written.  With one,
    the cache holds ``[c, k_pe]`` per token and attention runs absorbed:
    queries ``[q_nope W_UK^T, q_pe]`` attend over the latent itself, the
    values are ``c``, and ``W_UV`` (``W_kvb``'s value columns) is applied
    to the result.  Returns the (B, S, d) output, partial over TP, and
    the updated cache."""
    B, S, _ = xg.shape
    hl = local_q_heads(cfg, pc)
    nope, rd, r, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                       cfg.kv_lora_rank, cfg.v_head_dim)
    scale = attention_scale(cfg)
    q = dense(xg, p["wq"]).reshape(B, S, hl, nope + rd)
    kva = dense(xg, p["wkva"])                               # (B, S, r+rd)
    c = norm_apply(p["kv_norm"], kva[..., :r], eps=cfg.norm_eps)
    q_pe = rope_pairs(q[..., nope:], positions, theta=cfg.rope_theta,
                      yarn=cfg.yarn)
    k_pe = rope_pairs(kva[..., r:], positions, theta=cfg.rope_theta,
                      yarn=cfg.yarn)
    wkvb = cast_weight(p["wkvb"], xg.dtype).reshape(r, hl, nope + vd)
    if cache is None:
        kv = jnp.einsum("bsr,rhe->bshe", c, wkvb,
                        preferred_element_type=xg.dtype)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None], (B, S, hl, rd))], -1)
        qf = jnp.concatenate([q[..., :nope], q_pe], -1)
        o = kops.attention(qf.swapaxes(1, 2), k.swapaxes(1, 2),
                           kv[..., nope:].swapaxes(1, 2), causal=cfg.causal,
                           scale=scale, impl=attn_impl)
        o = o.swapaxes(1, 2)                                 # (B, S, hl, vd)
    else:
        q_lat = jnp.einsum("bshn,rhn->bhsr", q[..., :nope], wkvb[..., :nope],
                           preferred_element_type=jnp.float32)
        q_abs = jnp.concatenate([q_lat.astype(xg.dtype),
                                 q_pe.swapaxes(1, 2)], -1)  # (B, hl, S, r+rd)
        new = jnp.concatenate([c, k_pe], -1)                 # (B, S, r+rd)
        if isinstance(cache, PagedLatent):
            assert paged is not None, "PagedLatent caches need a PageCtx"
            blk, off = _page_slots(paged, S, cache.kv.shape)
            pool = cache.kv.at[blk, 0, off].set(
                new.astype(cache.kv.dtype), mode="drop")
            cache = PagedLatent(pool)
            table, valid = paged.block_table, paged.lengths + paged.n_new
            qpos = positions
        else:
            pool = lax.dynamic_update_slice(
                cache.kv, new[:, None].astype(cache.kv.dtype),
                (0, 0, cache.pos, 0))
            cache = LatentCache(pool, cache.pos + S)
            table = jnp.arange(B, dtype=jnp.int32)[:, None]
            valid = jnp.full((B,), cache.pos, jnp.int32)
            qpos = jnp.broadcast_to(positions, (B, S))
        with jax.named_scope("latent_attn"):
            o_lat = paged_attention(q_abs, pool, pool, table, valid, qpos,
                                    causal=cfg.causal, scale=scale, v_dim=r)
        o = jnp.einsum("bhsr,rhv->bshv", o_lat, wkvb[..., nope:],
                       preferred_element_type=xg.dtype)
    o = o.reshape(B, S, hl * vd)
    out = jax.lax.dot_general(
        o, cast_weight(p["wo"], o.dtype), (((2,), (0,)), ((), ())),
        preferred_element_type=o.dtype)
    return out, cache
