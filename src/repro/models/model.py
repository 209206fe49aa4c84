"""Model assembly: parameter init (+ partition specs), forward passes.

The model is one function family usable three ways:

* ``loss_and_metrics``  -- training forward (full seq, SP residuals)
* ``prefill``           -- fill KV caches / recurrent states from a prompt
* ``decode_step``       -- one-token step against the caches

All run inside ``jax.shard_map`` (manual mode).  Layers are grouped into
the config's block *cycle* and scanned with stacked parameters, so compile
time and HLO size are O(cycle) not O(n_layers); remat wraps the cycle
body.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import moe as moe_lib
from repro.models import recurrent as rec
from repro.models.attention import (PageCtx, attention_block,
                                    attn_replicated, init_cache,
                                    init_paged_pool, kv_replicated)
from repro.models.config import ModelConfig
from repro.models.layers import (COMPUTE_DTYPE, cast_weight, embed_tokens,
                                 mlp_apply, norm_apply, vocab_parallel_ce)
from repro.parallel.api import (ParallelConfig, ParamSpec, choose_fsdp_dim,
                                fsdp_gather_tree, seq_all_gather,
                                seq_reduce_scatter, tp_decode_all_gather,
                                tp_decode_psum, tp_psum, tp_rank)

PARAM_DTYPE = jnp.float32      # master copy; cast to bf16 at use


# ===========================================================================
#  parameter initialization (GLOBAL shapes) + partition specs
# ===========================================================================

class _Init:
    """Accumulates (params, specs) trees with matching structure.

    ``abstract=True`` builds ShapeDtypeStruct leaves instead of arrays --
    used by the multi-pod dry-run, which must never allocate."""

    def __init__(self, cfg: ModelConfig, pc: ParallelConfig, rng,
                 abstract: bool = False):
        self.cfg, self.pc = cfg, pc
        self.rng = rng
        self.abstract = abstract

    def take(self):
        if self.abstract:
            return None
        self.rng, r = jax.random.split(self.rng)
        return r

    def _spec(self, shape, tp_dim, stacked):
        return ParamSpec(tp_dim=tp_dim,
                         fsdp_dim=choose_fsdp_dim(shape, self.pc.dp,
                                                  avoid=tp_dim)
                         if self.pc.param_mode == "fsdp" else None,
                         stacked=stacked)

    def w(self, shape, tp_dim=None, scale=None, stacked=False):
        spec = self._spec(shape, tp_dim, stacked)
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, PARAM_DTYPE), spec
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = scale if scale is not None else fan_in ** -0.5
        arr = (jax.random.normal(self.take(), shape, PARAM_DTYPE) * scale)
        return arr, spec

    def zeros(self, shape, tp_dim=None, stacked=False):
        spec = self._spec(shape, tp_dim, stacked)
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, PARAM_DTYPE), spec
        return jnp.zeros(shape, PARAM_DTYPE), spec

    def ones(self, shape, tp_dim=None, stacked=False):
        arr, spec = self.zeros(shape, tp_dim, stacked)
        if self.abstract:
            return arr, spec
        return arr + 1.0, spec


def _norm_init(ii: _Init):
    cfg = ii.cfg
    p, s = {}, {}
    p["w"], s["w"] = ii.ones((cfg.d_model,))
    if cfg.norm == "layernorm":
        p["b"], s["b"] = ii.zeros((cfg.d_model,))
    return p, s


def _mlp_init(ii: _Init, d_ff: int):
    cfg = ii.cfg
    p, s = {}, {}
    d = cfg.d_model
    if cfg.act in ("swiglu", "geglu"):
        p["w1"], s["w1"] = ii.w((d, d_ff), tp_dim=1)
        p["w3"], s["w3"] = ii.w((d, d_ff), tp_dim=1)
    else:
        p["w1"], s["w1"] = ii.w((d, d_ff), tp_dim=1)
    p["w2"], s["w2"] = ii.w((d_ff, d), tp_dim=0)
    return p, s


def _mla_init(ii: _Init):
    """Latent attention (see :func:`repro.models.attention.mla_block`):
    query heads shard over TP, the latent projection and its norm are
    replicated."""
    cfg = ii.cfg
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    p, s = {}, {}
    p["wq"], s["wq"] = ii.w((d, H * cfg.hd), tp_dim=1)
    p["wkva"], s["wkva"] = ii.w((d, r + cfg.qk_rope_head_dim))
    p["kv_norm"], s["kv_norm"] = {}, {}
    p["kv_norm"]["w"], s["kv_norm"]["w"] = ii.ones((r,))
    p["wkvb"], s["wkvb"] = ii.w(
        (r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), tp_dim=1)
    p["wo"], s["wo"] = ii.w((cfg.q_dim, d), tp_dim=0,
                            scale=(cfg.q_dim ** -0.5) / math.sqrt(
                                2 * cfg.n_layers))
    return p, s


def _attn_init(ii: _Init):
    cfg, pc = ii.cfg, ii.pc
    if cfg.mla:
        return _mla_init(ii)
    d = cfg.d_model
    p, s = {}, {}
    repl = attn_replicated(cfg, pc)
    p["wq"], s["wq"] = ii.w((d, cfg.q_dim), tp_dim=None if repl else 1)
    kv_tp = None if (repl or kv_replicated(cfg, pc)) else 1
    p["wk"], s["wk"] = ii.w((d, cfg.kv_dim), tp_dim=kv_tp)
    p["wv"], s["wv"] = ii.w((d, cfg.kv_dim), tp_dim=kv_tp)
    p["wo"], s["wo"] = ii.w((cfg.q_dim, d), tp_dim=None if repl else 0,
                            scale=(cfg.q_dim ** -0.5) / math.sqrt(
                                2 * cfg.n_layers))
    return p, s


def _moe_init(ii: _Init):
    cfg = ii.cfg
    m = cfg.moe
    d = cfg.d_model
    p, s = {"router": {}, "experts": {}}, {"router": {}, "experts": {}}
    p["router"]["w"], s["router"]["w"] = ii.w((d, m.n_experts), tp_dim=None)
    E = m.n_held                  # the experts this layer holds
    p["experts"]["w1"], s["experts"]["w1"] = ii.w((E, d, m.d_expert), tp_dim=2)
    p["experts"]["w3"], s["experts"]["w3"] = ii.w((E, d, m.d_expert), tp_dim=2)
    p["experts"]["w2"], s["experts"]["w2"] = ii.w((E, m.d_expert, d), tp_dim=1)
    if m.n_shared:
        p["shared"], s["shared"] = _mlp_init(ii, m.d_shared)
    return p, s


def _rglru_init(ii: _Init):
    cfg = ii.cfg
    d = cfg.d_model
    w = cfg.rnn_width or d
    p, s = {}, {}
    for name in ("w_gate", "w_x", "w_rg", "w_ig"):
        p[name], s[name] = ii.w((d, w), tp_dim=1)
    p["conv_w"], s["conv_w"] = ii.w((cfg.conv_width, w), tp_dim=1,
                                    scale=cfg.conv_width ** -0.5)
    p["conv_b"], s["conv_b"] = ii.zeros((w,), tp_dim=0)
    # Lambda init so a = sigma(L)^c spreads over (0.9, 0.999)
    if ii.abstract:
        p["a_log"] = jax.ShapeDtypeStruct((w,), PARAM_DTYPE)
    else:
        lam = jnp.log(jnp.expm1(
            -jnp.log(jnp.linspace(0.9, 0.999, w)) / rec._C_RGLRU))
        p["a_log"] = lam.astype(PARAM_DTYPE)
    s["a_log"] = ParamSpec(tp_dim=0, fsdp_dim=None)
    p["w_out"], s["w_out"] = ii.w((w, d), tp_dim=0)
    return p, s


def _mlstm_init(ii: _Init):
    cfg = ii.cfg
    d = cfg.d_model
    w = int(d * cfg.mlstm_proj_factor)
    H = cfg.n_heads
    p, s = {}, {}
    p["w_q"], s["w_q"] = ii.w((d, w), tp_dim=None)      # replicated (see DESIGN)
    p["w_k"], s["w_k"] = ii.w((d, w), tp_dim=None)
    p["w_v"], s["w_v"] = ii.w((d, w), tp_dim=1)
    p["w_g"], s["w_g"] = ii.w((d, w), tp_dim=1)
    p["w_i"], s["w_i"] = ii.w((d, H), tp_dim=None, scale=0.02)
    p["w_f"], s["w_f"] = ii.w((d, H), tp_dim=None, scale=0.02)
    p["w_out"], s["w_out"] = ii.w((w, d), tp_dim=0)
    return p, s


def _slstm_init(ii: _Init):
    cfg = ii.cfg
    d = cfg.d_model
    H = cfg.n_heads
    hd = d // H
    p, s = {}, {}
    for name in ("w_z", "w_i", "w_f", "w_o"):
        p[name], s[name] = ii.w((d, d), tp_dim=None)
    for name in ("r_z", "r_i", "r_f", "r_o"):
        p[name], s[name] = ii.w((H, hd, hd), tp_dim=None, scale=hd ** -0.5)
    p["w_out"], s["w_out"] = ii.w((d, d), tp_dim=None)
    return p, s


def _block_has_mlp(cfg: ModelConfig, kind: str) -> bool:
    return kind in ("attn", "local_attn", "rglru") and (
        cfg.d_ff > 0 or cfg.moe is not None)


def _block_init(ii: _Init, kind: str, *, moe_layer: bool, d_ff_dense: int = 0):
    cfg = ii.cfg
    p, s = {}, {}
    p["ln1"], s["ln1"] = _norm_init(ii)
    if kind in ("attn", "local_attn"):
        p["attn"], s["attn"] = _attn_init(ii)
    elif kind == "rglru":
        p["rnn"], s["rnn"] = _rglru_init(ii)
    elif kind == "mlstm":
        p["mix"], s["mix"] = _mlstm_init(ii)
    elif kind == "slstm":
        p["mix"], s["mix"] = _slstm_init(ii)
    else:
        raise ValueError(kind)
    if _block_has_mlp(cfg, kind):
        p["ln2"], s["ln2"] = _norm_init(ii)
        if moe_layer and cfg.moe is not None:
            p["mlp"], s["mlp"] = _moe_init(ii)
        else:
            p["mlp"], s["mlp"] = _mlp_init(ii, d_ff_dense or cfg.d_ff)
    return p, s


def init_params(cfg: ModelConfig, pc: ParallelConfig, rng, *,
                abstract: bool = False) -> Tuple[Dict, Dict]:
    """Build GLOBAL parameters + the matching ParamSpec tree."""
    ii = _Init(cfg, pc, rng, abstract=abstract)
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    # vocab-parallel embedding/head only when the vocab divides TP
    # (hubert's 504 classes stay replicated; CE then partitions over the
    # sequence instead -- see vocab_parallel_ce)
    v_tp = cfg.vocab % pc.tp == 0
    p["embed"], s["embed"] = {}, {}
    p["embed"]["w"], s["embed"]["w"] = ii.w(
        (cfg.vocab, cfg.d_model), tp_dim=0 if v_tp else None, scale=1.0)
    if not cfg.tie_embeddings:
        p["head"], s["head"] = {}, {}
        p["head"]["w"], s["head"]["w"] = ii.w(
            (cfg.d_model, cfg.vocab), tp_dim=1 if v_tp else None)
    p["final_norm"], s["final_norm"] = _norm_init(ii)

    # prefix (unscanned) layers -- DeepSeek-MoE's leading dense layer,
    # recurrentgemma's two leading recurrent blocks
    pfx = cfg.prefix_kinds
    p["prefix"], s["prefix"] = [], []
    for i, kind in enumerate(pfx):
        bp, bs = _block_init(
            ii, kind, moe_layer=False,
            d_ff_dense=cfg.moe.d_first_dense if cfg.moe else 0)
        p["prefix"].append(bp)
        s["prefix"].append(bs)

    # scanned cycles; consecutive identical kinds stack into group scans
    n_cyc_layers = cfg.n_layers - len(pfx)
    cyc = cfg.cycle
    assert n_cyc_layers % len(cyc) == 0, (cfg.name, n_cyc_layers, cyc)
    n_cycles = n_cyc_layers // len(cyc)
    groups = cfg.cycle_groups

    def one_block_of(kind):
        def f(r):
            sub = _Init(cfg, pc, r, abstract=abstract)
            return _block_init(sub, kind, moe_layer=True)
        return f

    cyc_p, cyc_s = {}, {}
    for gi, (kind, cnt) in enumerate(groups):
        bf = one_block_of(kind)
        if abstract:
            bp, bs = bf(None)
            stacked = jax.tree.map(
                lambda sd: jax.ShapeDtypeStruct(
                    (n_cycles, cnt) + sd.shape, sd.dtype), bp)
        else:
            rngs = jax.random.split(ii.take(), n_cycles * cnt)
            rngs = rngs.reshape((n_cycles, cnt) + rngs.shape[1:])
            stacked = jax.vmap(jax.vmap(lambda r: bf(r)[0]))(rngs)
            _, bs = bf(ii.take())
        bs = jax.tree.map(
            lambda sp: ParamSpec(
                tp_dim=None if sp.tp_dim is None else sp.tp_dim + 2,
                fsdp_dim=None if sp.fsdp_dim is None else sp.fsdp_dim + 2,
                stacked=2),
            bs)
        cyc_p[f"g{gi}"], cyc_s[f"g{gi}"] = stacked, bs
    p["cycles"], s["cycles"] = cyc_p, cyc_s
    return p, s


def param_shapes(cfg: ModelConfig, pc: ParallelConfig):
    """ShapeDtypeStruct tree (no allocation) + specs -- for the dry-run."""
    return init_params(cfg, pc, None, abstract=True)


# ===========================================================================
#  forward
# ===========================================================================

def _shard_slice(x, pc: ParallelConfig, axis: int = 1):
    """Take this TP rank's sequence shard of a replicated full value."""
    if pc.tp == 1:
        return x
    n = x.shape[axis] // pc.tp
    return lax.dynamic_slice_in_dim(x, tp_rank(pc) * n, n, axis)


def _row_mask(mask, ndim):
    """(B,) bool -> (B, 1, ..., 1) broadcastable over an ndim array."""
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def _fresh_state(kind: str, cfg: ModelConfig, pc: ParallelConfig, B: int):
    if kind == "rglru":
        return rec.init_rglru_state(cfg, pc, B)
    if kind == "mlstm":
        return rec.init_mlstm_state(cfg, pc, B)
    if kind == "slstm":
        return rec.init_slstm_state(cfg, pc, B)
    raise ValueError(kind)


def block_apply(kind: str, p, x, cfg: ModelConfig, pc: ParallelConfig, *,
                sp: bool, positions, cache=None, rolling: bool = False,
                seq_shard: bool = False, paged=None,
                moe_layer: bool, attn_impl: str = "xla"):
    """One residual block.  x: (B, S/tp, d) if sp else (B, S, d)."""
    aux = jnp.float32(0.0)
    h = norm_apply(p["ln1"], x, kind=cfg.norm, eps=cfg.norm_eps)
    hg = seq_all_gather(h, pc) if sp else h

    window = cfg.window if (kind == "local_attn" or cfg.window) else None
    new_cache = cache
    recurrent = kind in ("rglru", "mlstm", "slstm")
    if paged is not None and recurrent and cache is not None:
        # continuous batching: a freshly admitted slot restarts its
        # recurrent state; a row with no valid tokens this tick must
        # keep its state frozen (its input is padding).  Rows with
        # 0 < n_new < S are the engine's responsibility to avoid for
        # recurrent archs (aligned chunking -- see serve/engine.py).
        B = hg.shape[0]
        fresh = _fresh_state(kind, cfg, pc, B)
        cache = jax.tree.map(
            lambda old, f: jnp.where(_row_mask(paged.reset, old.ndim),
                                     f, old), cache, fresh)
    if kind in ("attn", "local_attn"):
        with jax.named_scope("attn"):
            mix, new_cache = attention_block(
                p["attn"], hg, cfg, pc, window=window, positions=positions,
                cache=cache, rolling=rolling, seq_shard=seq_shard,
                paged=paged, attn_impl=attn_impl)
    elif kind == "rglru":
        mix, new_cache = rec.rglru_block(p["rnn"], hg, cfg, pc, state=cache)
    elif kind == "mlstm":
        mix, new_cache = rec.mlstm_block(p["mix"], hg, cfg, pc, state=cache)
    elif kind == "slstm":
        mix, new_cache = rec.slstm_block(p["mix"], hg, cfg, pc, state=cache)
    else:
        raise ValueError(kind)
    if paged is not None and recurrent and cache is not None:
        active = paged.n_new > 0
        new_cache = jax.tree.map(
            lambda old, new: jnp.where(_row_mask(active, new.ndim),
                                       new, old), cache, new_cache)

    # decode-path psums route through the autotuned ExecPlan collectives
    # when the serving ParallelConfig asks for them
    _psum = tp_decode_psum if paged is not None else tp_psum

    full_value = (kind == "slstm"
                  or (kind in ("attn", "local_attn")
                      and attn_replicated(cfg, pc)))
    if full_value:
        # replicated full value: slice the SP shard instead of reducing
        out = _shard_slice(mix, pc) if sp else mix
    else:
        out = seq_reduce_scatter(mix, pc) if sp else _psum(mix, pc)

    def mlp(h):
        with jax.named_scope("mlp"):
            if moe_layer and cfg.moe is not None:
                # rows that carry no token this tick are routed nowhere
                valid = None if paged is None else (
                    jnp.arange(h.shape[1])[None, :] < paged.n_new[:, None])
                return moe_lib.moe_apply(p["mlp"], h, cfg, pc, valid)
            return mlp_apply(p["mlp"], h, cfg, pc), jnp.float32(0.0)

    if cfg.parallel_residual and _block_has_mlp(cfg, kind):
        mo, aux = mlp(hg)
        mo = seq_reduce_scatter(mo, pc) if sp else _psum(mo, pc)
        return x + out + mo, new_cache, aux

    x = x + out
    if _block_has_mlp(cfg, kind):
        h2 = norm_apply(p["ln2"], x, kind=cfg.norm, eps=cfg.norm_eps)
        hg2 = seq_all_gather(h2, pc) if sp else h2
        mo, aux = mlp(hg2)
        x = x + (seq_reduce_scatter(mo, pc) if sp else _psum(mo, pc))
    return x, new_cache, aux


def _embed_inputs(params, batch, cfg: ModelConfig, pc: ParallelConfig):
    """Return the FULL-sequence activations (B, S, d) in compute dtype."""
    if cfg.frontend == "audio":
        return batch["embeds"].astype(COMPUTE_DTYPE)
    emb = embed_tokens(params["embed"], batch["tokens"], cfg, pc, sp=False)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        emb = jnp.concatenate(
            [batch["patch_embeds"].astype(COMPUTE_DTYPE), emb], axis=1)
    return emb


def forward(params, specs, batch, cfg: ModelConfig, pc: ParallelConfig, *,
            sp: bool, caches=None, pos0=None, rolling: bool = False,
            seq_shard: bool = False, paged: PageCtx = None,
            attn_impl: str = "xla"):
    """Shared trunk.  Returns (hidden_full (B,S,d), new_caches, aux)."""
    if cfg.frontend is None:
        # vocab-parallel embed scatters straight to the SP shard: the full
        # (B, S, d) activations never materialize on one device
        x = embed_tokens(params["embed"], batch["tokens"], cfg, pc, sp=sp)
        S = batch["tokens"].shape[1]
    else:
        x_full = _embed_inputs(params, batch, cfg, pc)
        S = x_full.shape[1]
        x = _shard_slice(x_full, pc) if sp else x_full
    if paged is not None:
        # continuous batching: every row sits at its own sequence offset
        positions = (paged.lengths[:, None]
                     + jnp.arange(S, dtype=jnp.int32)[None, :])   # (B, S)
    elif pos0 is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    else:
        positions = pos0 + jnp.arange(S, dtype=jnp.int32)

    new_prefix_caches = []
    for i, bp in enumerate(params["prefix"]):
        c = caches["prefix"][i] if caches is not None else None
        x, nc, _ = block_apply(cfg.block_kind(i), bp, x, cfg, pc, sp=sp,
                               positions=positions, cache=c, rolling=rolling,
                               seq_shard=seq_shard, paged=paged,
                               moe_layer=False, attn_impl=attn_impl)
        new_prefix_caches.append(nc)

    groups = cfg.cycle_groups
    cyc_specs = specs["cycles"]

    def one_block(kind, gi):
        def f(bp, xc, c):
            # per-block FSDP gather: only this block's parameters are
            # materialized at a time (VJP = ZeRO-3 reduce-scatter)
            bp = fsdp_gather_tree(bp, cyc_specs[f"g{gi}"], pc, sliced=True)
            return block_apply(kind, bp, xc, cfg, pc, sp=sp,
                               positions=positions, cache=c,
                               rolling=rolling, seq_shard=seq_shard,
                               paged=paged, moe_layer=True,
                               attn_impl=attn_impl)
        if pc.remat:
            # per-BLOCK remat: the scans then save only each block's input
            # residual (B, S/tp, d); one block's internals are
            # rematerialized at a time during the backward sweep.
            f = jax.checkpoint(
                f, prevent_cse=True,
                policy=jax.checkpoint_policies.nothing_saveable)
        return f

    block_fns = {gi: one_block(kind, gi)
                 for gi, (kind, _) in enumerate(groups)}

    def cycle_body(carry, xs):
        xc, aux = carry
        if caches is not None:
            cyc_params, cyc_caches = xs
        else:
            cyc_params, cyc_caches = xs, None
        new_caches_c = {}
        for gi, (kind, cnt) in enumerate(groups):
            gp = cyc_params[f"g{gi}"]                     # (cnt, ...)
            gc = cyc_caches[f"g{gi}"] if cyc_caches is not None else None

            if cnt == 1:
                # no inner scan: a length-1 scan would checkpoint the
                # residual stream a second time (one stack per nesting)
                bp = jax.tree.map(lambda a: a[0], gp)
                bc = jax.tree.map(lambda a: a[0], gc) if gc is not None \
                    else None
                xc, nc, a = block_fns[gi](bp, xc, bc)
                aux = aux + a
                new_caches_c[f"g{gi}"] = (
                    jax.tree.map(lambda a_: a_[None], nc)
                    if gc is not None else None)
                continue

            def group_body(carry2, xs2, gi=gi, gc=gc):
                xcc, aux2 = carry2
                if gc is not None:
                    bp, bc = xs2
                else:
                    bp, bc = xs2, None
                xcc, nc, a = block_fns[gi](bp, xcc, bc)
                return (xcc, aux2 + a), nc

            xs2 = (gp, gc) if gc is not None else gp
            (xc, aux), new_gc = lax.scan(group_body, (xc, aux), xs2)
            new_caches_c[f"g{gi}"] = new_gc
        out = new_caches_c if caches is not None else None
        return (xc, aux), out

    xs = (params["cycles"], caches["cycles"]) if caches is not None \
        else params["cycles"]
    (x, aux), cyc_out = lax.scan(cycle_body, (x, jnp.float32(0.0)), xs)

    x = norm_apply(params["final_norm"], x, kind=cfg.norm, eps=cfg.norm_eps)
    # NOTE: with sp=True the returned hidden state is the SP shard
    # (B, S/tp, d); the CE path gathers it chunk-by-chunk.
    new_caches = None
    if caches is not None:
        new_caches = {"prefix": new_prefix_caches, "cycles": cyc_out}
    return x, new_caches, aux


# ---------------------------------------------------------------- training
def loss_and_metrics(params, specs, batch, cfg: ModelConfig,
                     pc: ParallelConfig, *, attn_impl: str = "xla"):
    """Next-token (or masked-frame) CE loss.  Returns (loss_mean_local,
    (sum, count, aux)); the caller averages over DP."""
    # gather fsdp-sharded non-scanned params once
    top = {k: v for k, v in params.items() if k != "cycles"}
    top_specs = {k: v for k, v in specs.items() if k != "cycles"}
    top = fsdp_gather_tree(top, top_specs, pc)
    params = dict(top, cycles=params["cycles"])

    hidden, _, aux = forward(params, specs, batch, cfg, pc, sp=True,
                             attn_impl=attn_impl)
    labels = batch["labels"]
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        npatch = batch["patch_embeds"].shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], npatch), -1, labels.dtype), labels],
            axis=1)
    head = params["head"] if not cfg.tie_embeddings else {
        "w": params["embed"]["w"].T}
    with jax.named_scope("head"):
        total, count = vocab_parallel_ce(head, hidden, labels, cfg, pc,
                                         sp=True)
    loss = total / jnp.maximum(count, 1) + aux
    return loss, (total, count, aux)


# ---------------------------------------------------------------- serving
def serve_weights(params):
    """The parameter tree a serve step computes with: every leaf of each
    block's ``attn`` and ``mlp`` subtrees, the embedding table and the
    head in the compute dtype, every other leaf (norms, front ends) as
    given.  Each use casts those leaves so anyway
    (:func:`~repro.models.layers.cast_weight`), so the values are the
    same.  On a tree already in that dtype every cast is a no-op that
    emits nothing, so a caller that serves many steps makes this tree
    once (:class:`~repro.serve.engine.Engine` does)."""
    def cast(tree):
        return jax.tree.map(lambda w: cast_weight(w, COMPUTE_DTYPE), tree)

    def block(bp):
        return {k: cast(v) if k in ("attn", "mlp") else v
                for k, v in bp.items()}

    out = dict(params, prefix=[block(bp) for bp in params["prefix"]],
               cycles={g: block(bp) for g, bp in params["cycles"].items()})
    for k in ("embed", "head"):
        if k in params:
            out[k] = cast(params[k])
    return out


def _serve_params(params, specs, pc: ParallelConfig):
    """A serve step's parameters: :func:`serve_weights` of ``params``
    under the ``weight_cast`` scope, then the non-scanned ones
    FSDP-gathered once.  Cast here, before the layer loop, the cast
    keeps its scope in the compiled program, where XLA would hoist the
    per-layer casts out of the loop itself and drop their metadata."""
    params = serve_weights(params)
    top = {k: v for k, v in params.items() if k != "cycles"}
    top_specs = {k: v for k, v in specs.items() if k != "cycles"}
    return dict(fsdp_gather_tree(top, top_specs, pc),
                cycles=params["cycles"])


def init_caches(cfg: ModelConfig, pc: ParallelConfig, batch_local: int,
                max_len: int, *, rolling: bool = False,
                seq_shard: bool = False):
    """Build the stacked cache pytree matching the scan structure."""
    def cache_for(kind):
        if kind in ("attn", "local_attn"):
            rw = cfg.window if (rolling and cfg.window) else None
            return init_cache(cfg, pc, batch_local, max_len,
                              rolling_window=rw, seq_shard=seq_shard)
        if kind == "rglru":
            return rec.init_rglru_state(cfg, pc, batch_local)
        if kind == "mlstm":
            return rec.init_mlstm_state(cfg, pc, batch_local)
        if kind == "slstm":
            return rec.init_slstm_state(cfg, pc, batch_local)
        raise ValueError(kind)

    n_prefix = len(cfg.prefix_kinds)
    prefix = [cache_for(cfg.block_kind(i)) for i in range(n_prefix)]
    n_cycles = (cfg.n_layers - n_prefix) // len(cfg.cycle)
    cycles = {}
    for gi, (kind, cnt) in enumerate(cfg.cycle_groups):
        one = cache_for(kind)
        cycles[f"g{gi}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a[None, None], (n_cycles, cnt) + a.shape).copy(), one)
    return {"prefix": prefix, "cycles": cycles}


def init_paged_caches(cfg: ModelConfig, pc: ParallelConfig,
                      batch_local: int, n_blocks: int, block_size: int):
    """Stacked cache pytree for continuous batching: attention layers get
    a paged KV pool (``n_blocks`` fixed-size blocks indexed per-row via
    the block table in :class:`PageCtx`; block 0 is the shared garbage
    block backing unallocated table entries), recurrent layers keep
    their dense per-slot states.  One pool per layer -- the scan
    broadcast below stacks (n_cycles, cnt) independent pools -- while
    all layers share a single block-table geometry."""
    def cache_for(kind):
        if kind in ("attn", "local_attn"):
            return init_paged_pool(cfg, pc, n_blocks, block_size)
        if kind == "rglru":
            return rec.init_rglru_state(cfg, pc, batch_local)
        if kind == "mlstm":
            return rec.init_mlstm_state(cfg, pc, batch_local)
        if kind == "slstm":
            return rec.init_slstm_state(cfg, pc, batch_local)
        raise ValueError(kind)

    n_prefix = len(cfg.prefix_kinds)
    prefix = [cache_for(cfg.block_kind(i)) for i in range(n_prefix)]
    n_cycles = (cfg.n_layers - n_prefix) // len(cfg.cycle)
    cycles = {}
    for gi, (kind, cnt) in enumerate(cfg.cycle_groups):
        one = cache_for(kind)
        cycles[f"g{gi}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a[None, None], (n_cycles, cnt) + a.shape).copy(), one)
    return {"prefix": prefix, "cycles": cycles}


def paged_decode_step(params, specs, tokens, caches, paged: PageCtx,
                      cfg: ModelConfig, pc: ParallelConfig, *,
                      attn_impl: str = "xla"):
    """One continuous-batching tick: tokens (B, S) where row b carries
    ``paged.n_new[b]`` valid new tokens (decode rows S_new=1, prefill
    rows up to the chunk, idle rows 0).  Returns (logits (B, 1, V) at
    each row's LAST valid position, new caches).

    Unlike :func:`decode_step` there is no shared ``pos0``: positions,
    KV writes and attention masks are all per-row via ``paged``; the
    final vocab gather runs on the decode-path collectives
    (:func:`repro.parallel.api.tp_decode_all_gather`)."""
    params = _serve_params(params, specs, pc)

    hidden, new_caches, _ = forward(params, specs, {"tokens": tokens}, cfg,
                                    pc, sp=False, caches=caches, paged=paged,
                                    attn_impl=attn_impl)
    head = params["head"] if not cfg.tie_embeddings else {
        "w": params["embed"]["w"].T}
    with jax.named_scope("head"):
        # row b's next-token logits live at its last valid position
        last = jnp.clip(paged.n_new - 1, 0, hidden.shape[1] - 1)
        hidden = jnp.take_along_axis(hidden, last[:, None, None], axis=1)
        logits = jax.lax.dot_general(
            hidden, cast_weight(head["w"], hidden.dtype),
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (B, 1, V/tp)
        if pc.tp > 1 and logits.shape[-1] != cfg.vocab:
            logits = tp_decode_all_gather(logits, pc, axis=2)
    return logits, new_caches


def decode_step(params, specs, tokens, caches, pos0, cfg: ModelConfig,
                pc: ParallelConfig, *, rolling: bool = False,
                seq_shard: bool = False,
                attn_impl: str = "xla", logits_len: int = 1):
    """tokens (B, S_new) -> (logits (B, min(S_new, logits_len), V),
    new caches).

    S_new == 1 for decode; larger for (chunked) prefill, where only the
    tail ``logits_len`` positions are scored -- scoring all 32k prefill
    positions against a 256k vocab would materialize a 67 GB logits
    tensor nobody reads.
    """
    params = _serve_params(params, specs, pc)

    batch = {"tokens": tokens}
    hidden, new_caches, _ = forward(params, specs, batch, cfg, pc, sp=False,
                                    caches=caches, pos0=pos0,
                                    rolling=rolling, seq_shard=seq_shard,
                                    attn_impl=attn_impl)
    head = params["head"] if not cfg.tie_embeddings else {
        "w": params["embed"]["w"].T}
    with jax.named_scope("head"):
        if hidden.shape[1] > logits_len:
            hidden = hidden[:, -logits_len:, :]
        logits = jax.lax.dot_general(
            hidden, cast_weight(head["w"], hidden.dtype),
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (B, L, V/tp)
        if pc.tp > 1 and logits.shape[-1] != cfg.vocab:
            logits = lax.all_gather(logits, pc.tp_axis, axis=2, tiled=True)
    return logits, new_caches
