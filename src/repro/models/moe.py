"""Mixture-of-Experts FFN: a dropless layer of held experts, and a
capacity-based all-to-all dispatch.

Where the layer runs without an exchange (:func:`ep_group_size` 1: in
serving and in single-device training, and under the "tp" dispatch),
it is dropless (:func:`held_experts_apply`): every token is routed over
all ``n_experts``, and the layer computes the part of the result that
the experts it holds (``MoEConfig.held``) give, for every token routed
to them, with grouped products over the rows sorted by expert
(``jax.lax.ragged_dot``).  No token is dropped, so a request's output
does not depend on what else shares its batch.  Rows marked invalid
(chunk padding, idle slots) are routed nowhere.

Parallelization: experts' ffn widths are sharded over the TP axis exactly
like a dense MLP (mixtral: 14336/16 = 896 per device; deepseek-moe:
1408/16 = 88).  Tokens are already gathered to the full sequence at the
block boundary (sequence-parallel residual), so routing is computed
redundantly-but-identically on every TP device and the expert outputs are
partial sums that the block boundary reduce-scatters -- the exact same
collective pattern as a dense block.

A token-dropping all-to-all expert-parallel dispatch (GShard style) is
available behind ``ParallelConfig.moe_dispatch``: tokens stay sharded
over the DP axis, experts are partitioned into ``dp`` groups, and two
all-to-alls move each rank's expert queues to the group owner and the
expert outputs back (``_experts_apply_ep``).  The exchange itself runs
either through stock ``lax.all_to_all`` ("gshard" -- the oracle) or
through the permutation-group schedule tables of
:func:`repro.core.allreduce.all_to_all_flat` ("schedule"); the two are
bit-identical because an all-to-all is a pure permutation.  The default
("tp") keeps the TP-sharded form, which for the expert counts in the
assigned pool (8/64 with tp=16) needs no extra collectives at all,
which the dry-run roofline confirms (see DESIGN.md §MoE).

The all-to-all dispatch keeps the capacity form, because its exchange
needs blocks of one size: per expert a queue of C = ceil(T * k / E *
capacity_factor) slots; overflowing tokens drop (their residual passes
through).  It holds every expert.  Aux losses (both paths):
load-balance + router z-loss.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.allreduce import all_to_all_flat
from repro.models.layers import cast_weight, dense, mlp_apply
from repro.parallel.api import ParallelConfig


def capacity(tokens: int, cfg_moe) -> int:
    c = math.ceil(tokens * cfg_moe.top_k / cfg_moe.n_experts
                  * cfg_moe.capacity_factor)
    return max(8, -(-c // 8) * 8)  # pad to 8 for TPU-friendly shapes


def route(p_router, x, cfg_moe):
    """x (T, d) -> top-k experts, probs and aux losses.  The gate is a
    softmax in f32 over all experts, its top-k taken greedily, and
    renormalised only where ``cfg_moe.norm_topk``.

    Returns (expert_idx (T,k), probs (T,k), aux_loss scalar).
    """
    logits = jax.lax.dot_general(
        x, cast_weight(p_router["w"], x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (T, E) f32
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, cfg_moe.top_k)   # (T, k)
    if cfg_moe.norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balance loss (Switch/GShard): E * sum_e f_e * m_e
    E = cfg_moe.n_experts
    me = jnp.mean(probs, axis=0)                                  # (E,)
    fe = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    lb = E * jnp.sum(fe * me)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = cfg_moe.aux_loss_weight * lb + cfg_moe.z_loss_weight * z
    return top_e, top_p, aux


def dispatch_indices(top_e, cfg_moe, T: int):
    """Compute (E, C) token indices (T = sentinel for empty slots) and the
    (T, k) in-queue positions, without materializing (T, k, E) one-hots."""
    E = cfg_moe.n_experts
    k = cfg_moe.top_k
    C = capacity(T, cfg_moe)
    counts = jnp.zeros((E,), jnp.int32)
    slot_pos = []
    for j in range(k):
        oh = jax.nn.one_hot(top_e[:, j], E, dtype=jnp.int32)       # (T, E)
        pos_in_slot = jnp.cumsum(oh, axis=0) - oh                  # (T, E)
        pos = jnp.sum(oh * pos_in_slot, axis=-1) + counts[top_e[:, j]]
        slot_pos.append(pos)
        counts = counts + jnp.sum(oh, axis=0)
    pos = jnp.stack(slot_pos, axis=1)                              # (T, k)
    keep = pos < C
    # scatter token ids into the expert queues
    tok = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], pos.shape)
    eq = jnp.full((E, C), T, dtype=jnp.int32)                      # sentinel T
    e_flat = top_e.reshape(-1)
    p_flat = jnp.where(keep, pos, C).reshape(-1)   # C = out of bounds -> drop
    eq = eq.at[e_flat, p_flat].set(tok.reshape(-1), mode="drop")
    return eq, pos, keep


def experts_apply(p, xq, cfg, act: str):
    """xq (E, C, d) -> (E, C, d) partial over TP (w2 rows sharded).

    Expert weights are stacked: w1/w3 (E, d, ff/tp), w2 (E, ff/tp, d).
    """
    def one(x_e, w1, w3, w2):
        g = dense(x_e, w1)
        u = dense(x_e, w3)
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
        return jax.lax.dot_general(
            h, cast_weight(w2, h.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=h.dtype)
    return jax.vmap(one)(xq, p["w1"], p["w3"], p["w2"])


def ep_group_size(pc: ParallelConfig, n_experts: int) -> int:
    """Expert-parallel group size of the all-to-all dispatch (1 = the
    dispatch is disabled and every rank applies every expert locally).

    The dispatch activates when ``pc.moe_dispatch`` asks for it, the DP
    axis is a single named axis with more than one rank, and the expert
    count splits evenly across the ranks."""
    if pc.moe_dispatch not in ("gshard", "schedule"):
        return 1
    if pc.dp <= 1 or len(pc.dp_axes) != 1:
        return 1
    return pc.dp if n_experts % pc.dp == 0 else 1


def _experts_apply_ep(pe, xq, cfg, pc: ParallelConfig, ep: int):
    """Expert-parallel experts: all-to-all dispatch + local apply + return.

    ``xq`` (E, C, d) holds this rank's queues for *all* experts; rank
    ``s`` owns expert group ``s`` (experts ``s*E/ep .. (s+1)*E/ep-1``).
    Exchange 1 sends each group's queues to its owner (after it, entry
    ``s`` of the received (ep, E/ep, C, d) block is rank ``s``'s queues
    for *my* group); the owner applies its expert slice to every rank's
    tokens at once; exchange 2 is the inverse permutation, so the
    returned (E, C, d) buffer is laid out exactly like the local path's
    -- the combine below never knows which rank ran the experts.

    With ``pc.moe_dispatch == "schedule"`` both exchanges run the
    compiled permutation-group step tables
    (:func:`repro.core.allreduce.all_to_all_flat`, Bruck or direct by
    message size); "gshard" runs stock ``lax.all_to_all``.  Both are
    pure permutations of identical blocks, hence bit-identical.
    """
    axis = pc.dp_axes[0]
    E, C, d = xq.shape
    El = E // ep

    def exchange(buf):
        # buf (ep, El, C, d), entry s destined for rank s; returns the
        # same shape with entry s = the block received from rank s
        if pc.moe_dispatch == "schedule":
            return all_to_all_flat(buf.reshape(-1), axis).reshape(buf.shape)
        return lax.all_to_all(buf, axis, split_axis=0, concat_axis=0)

    recv = exchange(xq.reshape(ep, El, C, d))            # [s] = s's queues
    rk = lax.axis_index(axis)
    loc = {k: lax.dynamic_slice_in_dim(v, rk * El, El, 0)
           for k, v in pe.items()}
    xq_l = jnp.moveaxis(recv, 0, 1).reshape(El, ep * C, d)
    yq_l = experts_apply(loc, xq_l, cfg, cfg.act)        # (El, ep*C, d)
    back = jnp.moveaxis(yq_l.reshape(El, ep, C, d), 1, 0)
    return exchange(back).reshape(E, C, d)


_MOE_TOKEN_CHUNK = 8192


def held_experts_apply(p, x, valid, cfg):
    """The held experts' part of the layer for x (T, d): each token's
    gate-weighted sum over those of its top-k experts that this layer
    holds, dropless.  ``valid`` (T,) bool or None; an invalid row is
    routed nowhere.  Returns ((T, d) partial over TP, aux_loss)."""
    m = cfg.moe
    T, d = x.shape
    k, El, lo = m.top_k, m.n_held, m.first_held
    with jax.named_scope("router"):
        top_e, top_p, aux = route(p["router"], x, m)
        local = top_e - lo
        held = (local >= 0) & (local < El)
        if valid is not None:
            held = held & valid[:, None]
        # assignments sorted by held expert; the rest (key El) go last
        key = jnp.where(held, local, El).reshape(-1)             # (T*k,)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, El, dtype=jnp.int32), axis=0)
        rows = order // k                                        # token
        gate = jnp.where(held, top_p, 0.0).reshape(-1)[order]
        live = key[order] < El
    with jax.named_scope("experts"):
        pe = p["experts"]
        xs = jnp.take(x, rows, axis=0)                           # (T*k, d)

        def grouped(a, w):
            return lax.ragged_dot(a, cast_weight(w, a.dtype), sizes,
                                  preferred_element_type=a.dtype)

        g = grouped(xs, pe["w1"])
        u = grouped(xs, pe["w3"])
        h = (jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)) * u
        y = grouped(h, pe["w2"]).astype(jnp.float32)
        # rows past the held groups are left unwritten by the product
        y = jnp.where(live[:, None], y * gate[:, None], 0.0)
        out = jnp.zeros((T, d), jnp.float32).at[rows].add(y)
    return out.astype(x.dtype), aux


def moe_apply(p, xg, cfg, pc: ParallelConfig, valid=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """xg (B, S, d) full-seq -> ((B, S, d) partial-over-TP, aux_loss).
    ``valid`` (B, S) bool marks the rows that hold a token (None: all).

    Without an exchange the layer is :func:`held_experts_apply`, plus
    the shared expert once.  With the all-to-all dispatch, tokens are
    processed in chunks of ~8k (scanned, rematted): the (E, C, d)
    dispatch buffers for a 64-expert layer at 64k tokens would otherwise
    hold multiple GB live across the backward pass.  Capacity is
    per-chunk, which also bounds worst-case token dropping locality.
    """
    B, S, d = xg.shape
    T = B * S
    x = xg.reshape(T, d)
    if ep_group_size(pc, cfg.moe.n_experts) == 1:
        out, aux = held_experts_apply(
            p, x, None if valid is None else valid.reshape(T), cfg)
        return _add_shared(p, x, out, cfg, pc).reshape(B, S, d), aux
    assert cfg.moe.held == (0, 1), "the dispatch holds every expert"
    if T > _MOE_TOKEN_CHUNK:
        nc = -(-T // _MOE_TOKEN_CHUNK)
        pad = nc * _MOE_TOKEN_CHUNK - T
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
        xs = x.reshape(nc, -1, d)

        def body(aux_c, xc):
            yc, a = _moe_tokens(p, xc, cfg, pc)
            return aux_c + a / nc, yc

        aux, ys = lax.scan(jax.checkpoint(body, prevent_cse=False),
                           jnp.float32(0.0), xs)
        out = ys.reshape(-1, d)[:T]
        return out.reshape(B, S, d), aux
    out, aux = _moe_tokens(p, x, cfg, pc)
    return out.reshape(B, S, d), aux


def _moe_tokens(p, x, cfg, pc: ParallelConfig):
    """Route + all-to-all dispatch + experts + combine for a flat (T, d)
    token set."""
    m = cfg.moe
    T, d = x.shape
    top_e, top_p, aux = route(p["router"], x, m)
    eq, pos, keep = dispatch_indices(top_e, m, T)

    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])        # sentinel row
    xq = jnp.take(xpad, eq, axis=0)                                # (E, C, d)
    yq = _experts_apply_ep(p["experts"], xq, cfg, pc,
                           ep_group_size(pc, m.n_experts))         # (E, C, d)

    # combine: token t gets sum_j prob_j * yq[e_j, pos_j]
    C = yq.shape[1]
    ypad = jnp.concatenate([yq.reshape(-1, d),
                            jnp.zeros((1, d), yq.dtype)])
    flat_idx = jnp.where(keep, top_e * C + jnp.clip(pos, 0, C - 1),
                         ypad.shape[0] - 1)                        # (T, k)
    gathered = jnp.take(ypad, flat_idx.reshape(-1), axis=0)
    gathered = gathered.reshape(T, m.top_k, d)
    out = jnp.sum(gathered * top_p[..., None].astype(gathered.dtype), axis=1)

    return _add_shared(p, x, out, cfg, pc), aux


def _add_shared(p, x, out, cfg, pc: ParallelConfig):
    """``out`` plus the shared expert of x (T, d), where there is one."""
    if not cfg.moe.n_shared:
        return out
    with jax.named_scope("shared_expert"):
        return out + mlp_apply(p["shared"], x, cfg, pc).reshape(out.shape)
