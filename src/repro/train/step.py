"""Train / serve step builders: the shard_map programs.

This is where the paper's collective meets the training loop:

* ``dp`` mode    -- per-bucket generalized allreduce of the gradients over
                    the DP axes, step count r autotuned from the fabric
                    parameters via the paper's eq (37) / exact search.
* ``zero1`` mode -- reduction phase only (= any-P reduce-scatter in
                    ceil(lg P) steps); the distribution phase re-broadcasts
                    updated parameters inside the optimizer.
* ``fsdp`` mode  -- parameters sharded over DP; the forward all-gather's
                    VJP reduce-scatters gradients automatically; leftover
                    DP-replicated leaves still sync through the paper's
                    allreduce.

Gradients of TP-replicated parameters (norms, replicated KV, routers,
q/k of mLSTM, all of sLSTM) are partial under sequence-parallelism and get
an exact ``psum`` over the TP axis first.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core.allreduce import tree_reduce_scatter
from repro.core.cost_model import Fabric, TPU_V5E_ICI
from repro.models.config import ModelConfig
from repro.models.model import (decode_step, init_caches, init_paged_caches,
                                loss_and_metrics, paged_decode_step,
                                param_shapes)
from repro.parallel.api import (ParallelConfig, ParamSpec,
                                attach_overlap_sync, bucketed_grad_sync,
                                dp_grad_allreduce, reverse_layer_buckets)
from repro.train.optimizer import (OptConfig, apply_updates_dp,
                                   apply_updates_zero1, clip_by_global_norm,
                                   init_opt_state)


# ---------------------------------------------------------------------------
#  PartitionSpec derivation
# ---------------------------------------------------------------------------

def pspec_for(spec: ParamSpec, ndim: int, pc: ParallelConfig) -> P:
    dims: list = [None] * ndim
    if spec.tp_dim is not None and pc.tp > 1:
        dims[spec.tp_dim] = pc.tp_axis
    if spec.fsdp_dim is not None and pc.param_mode == "fsdp" and pc.dp > 1:
        dims[spec.fsdp_dim] = pc.dp_axes if len(pc.dp_axes) > 1 \
            else pc.dp_axes[0]
    return P(*dims)


def param_pspecs(params_shapes, specs, pc: ParallelConfig):
    return jax.tree.map(
        lambda sd, sp: pspec_for(sp, len(sd.shape), pc), params_shapes, specs)


def batch_pspecs(batch_shapes, pc: ParallelConfig):
    dp = pc.dp_axes if len(pc.dp_axes) > 1 else pc.dp_axes[0]
    return jax.tree.map(
        lambda sd: P(*([dp] + [None] * (len(sd.shape) - 1))), batch_shapes)


def opt_pspecs(opt_shapes, param_specs_tree, pc: ParallelConfig):
    if pc.param_mode in ("dp", "fsdp"):
        mv = param_pspecs(opt_shapes["m"], param_specs_tree, pc)
        return {"m": mv, "v": jax.tree.map(lambda x: x, mv),
                "step": P()}
    dp = pc.dp_axes if len(pc.dp_axes) > 1 else pc.dp_axes[0]
    return {"m": P(dp), "v": P(dp), "step": P()}


# ---------------------------------------------------------------------------
#  gradient synchronization
# ---------------------------------------------------------------------------

def sync_grads_tp(grads, specs, pc: ParallelConfig):
    """Exact psum over TP for TP-replicated leaves."""
    if pc.tp == 1:
        return grads

    def f(g, s):
        if s.tp_replicated:
            return lax.psum(g, pc.tp_axis)
        return g

    return jax.tree.map(f, grads, specs)


def sync_grads_dp(grads, specs, pc: ParallelConfig,
                  fabric: Fabric = TPU_V5E_ICI):
    """DP-axis sync per param_mode.  Returns grads in the layout the
    optimizer expects (tree for dp/fsdp, flat shard for zero1)."""
    if pc.param_mode == "zero1":
        shard, _ = tree_reduce_scatter(grads, pc.dp_axis_name, mean=True)
        return shard
    if pc.param_mode == "fsdp":
        if pc.dp == 1:
            return grads
        # fsdp-sharded leaves were already reduce-scattered by the VJP of
        # the forward all-gather but carry a sum over DP -> divide.
        # dp-replicated leaves still need a full allreduce (mean).
        flat, treedef = jax.tree.flatten(grads)
        # align the specs to the *grads* treedef: flatten_up_to raises on
        # any structural mismatch, where zip-by-position over two
        # independent flattenings would silently pair grad leaves with
        # the wrong ParamSpec (sharded leaves interleave with replicated
        # ones in tree order, so a skew here re-scatters the sync)
        sflat = treedef.flatten_up_to(specs)
        flat = [g / pc.dp if s.fsdp_dim is not None else g
                for g, s in zip(flat, sflat)]
        repl_idx = [i for i, s in enumerate(sflat) if s.fsdp_dim is None]
        if repl_idx:
            synced = dp_grad_allreduce([flat[i] for i in repl_idx], pc,
                                       mean=True, fabric=fabric)
            for i, v in zip(repl_idx, synced):
                flat[i] = v
        return jax.tree.unflatten(treedef, flat)
    # pure dp: the paper's generalized allreduce over the whole tree
    # (hierarchical per-level composition when pc.topology spans levels)
    if pc.dp == 1:
        return grads
    return dp_grad_allreduce(grads, pc, mean=True, fabric=fabric)


def replicate_scalar(x, pc: ParallelConfig, mesh_axes):
    """Make a scalar provably replicated for out_specs=P()."""
    return lax.pmean(x, mesh_axes)


# ---------------------------------------------------------------------------
#  backward-overlapped gradient sync: layer derivation + bucketing
# ---------------------------------------------------------------------------

def _leaf_layers(params_shapes):
    """Per-leaf layer index of the params tree, in tree-flatten order.

    The backward pass differentiates the model back-to-front, so the
    leaves whose gradients complete *first* are the deepest layers.
    Layer indices (higher = completes earlier in backward):

    * ``embed``        -> 0                (its grad completes last)
    * ``prefix[i]``    -> 1 + i
    * ``cycles``       -> 1 + n_prefix    (the stacked scan's backward
      emits every cycle's gradient at once, so the whole stack is one
      band -- this is the "scan-carried" arm of the dispatch design:
      scan-stacked archs get a single band-sized dispatch point)
    * ``final_norm`` / ``head`` -> 2 + n_prefix  (complete first)

    Dict flattening is alphabetical, NOT layer order, hence the
    path-based derivation.  The return aligns leaf-for-leaf with
    ``jax.tree.leaves(params_shapes)``.
    """
    n_prefix = len(params_shapes.get("prefix", []))
    flat, _ = jax.tree.flatten_with_path(params_shapes)
    layers = []
    for path, _leaf in flat:
        top = getattr(path[0], "key", None)
        if top == "embed":
            layers.append(0)
        elif top == "prefix":
            layers.append(1 + int(path[1].idx))
        elif top == "cycles":
            layers.append(1 + n_prefix)
        else:                       # final_norm, head
            layers.append(2 + n_prefix)
    return layers


def overlap_buckets_for(params_shapes, pc: ParallelConfig):
    """Reverse-layer gradient buckets for this params tree, or ``None``
    when the overlapped path is off (no ``overlap_bucket_bytes``, pure
    DP only -- fsdp/zero1 reshape gradient flow themselves)."""
    if (pc.overlap_bucket_bytes is None or pc.param_mode != "dp"
            or pc.dp <= 1):
        return None
    leaves = jax.tree.leaves(params_shapes)
    layers = _leaf_layers(params_shapes)
    sizes = [int(sd.size) * jnp.dtype(sd.dtype).itemsize for sd in leaves]
    return reverse_layer_buckets(layers, sizes, pc.overlap_bucket_bytes)


# ---------------------------------------------------------------------------
#  step builders
# ---------------------------------------------------------------------------

@dataclass
class StepBundle:
    train_step: Any
    in_shardings: Any
    out_shardings: Any
    params_shapes: Any
    opt_shapes: Any
    specs: Any
    pc: ParallelConfig


def make_train_step(cfg: ModelConfig, pc: ParallelConfig, mesh: Mesh,
                    oc: OptConfig, *, attn_impl: str = "xla",
                    fabric: Fabric = TPU_V5E_ICI,
                    donate: bool = True,
                    microbatches: int = 1) -> StepBundle:
    """``microbatches > 1``: split the local batch and accumulate
    gradients over a scan -- activation footprint (incl. the per-layer
    residual stacks) scales with 1/microbatches while gradient sync and
    the optimizer run once per step (standard grad accumulation).

    When ``pc.overlap_bucket_bytes`` is set (pure-DP only), gradient
    sync runs per reverse-layer bucket instead of over one post-backward
    flat tensor; ``pc.overlap_dispatch`` picks the dispatch point:
    ``"backward"`` (default) attaches ``custom_vjp`` markers so each
    bucket's allreduce starts the moment its layer band's backward
    completes, ``"post"`` runs the identical per-bucket collectives
    after the backward (the bit-exact A/B control), ``"skip"`` elides DP
    sync (benchmark compute-baseline only).  Gradient accumulation
    (``microbatches > 1``) syncs once per step, so the backward-marker
    arm falls back to the post-backward bucketed sync there.
    """
    if pc.overlap_dispatch not in ("backward", "post", "skip"):
        raise ValueError(f"overlap_dispatch={pc.overlap_dispatch!r} "
                         "(expected backward | post | skip)")
    params_shapes, specs = param_shapes(cfg, pc)
    opt_shapes = jax.eval_shape(
        partial(init_opt_state, pc=pc, specs=specs), params_shapes)
    mesh_axes = tuple(mesh.axis_names)
    buckets = overlap_buckets_for(params_shapes, pc)
    overlap_bwd = (buckets is not None and microbatches == 1
                   and pc.overlap_dispatch == "backward")

    def grad_of(params, batch):
        def local_loss(p):
            if overlap_bwd:
                # identity forward; each bucket's VJP dispatches its
                # dp_grad_allreduce as its backward completes
                p = attach_overlap_sync(p, buckets, pc, fabric=fabric)
            return loss_and_metrics(p, specs, batch, cfg, pc,
                                    attn_impl=attn_impl)
        return jax.value_and_grad(local_loss, has_aux=True)(params)

    def step_fn(params, opt_state, batch):
        if microbatches > 1:
            mb = jax.tree.map(
                lambda x: x.reshape((microbatches,
                                     x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)

            def acc_body(carry, b):
                (loss, (total, count, aux)), g = grad_of(params, b)
                tot_c, cnt_c, aux_c, g_c = carry
                g_c = jax.tree.map(jnp.add, g_c, g)
                return (tot_c + total, cnt_c + count, aux_c + aux,
                        g_c), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (total, count, aux, grads), _ = lax.scan(
                acc_body,
                (jnp.float32(0.0), jnp.int32(0), jnp.float32(0.0), g0),
                mb)
            # each microbatch loss is a mean over its own tokens: the
            # accumulated grad is a sum of per-microbatch means
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            aux = aux / microbatches
        else:
            # the per-device loss is discarded: metrics recompute the
            # global mean from the psum'd (total, count) below
            (_loss, (total, count, aux)), grads = grad_of(params, batch)
        grads = sync_grads_tp(grads, specs, pc)
        if buckets is not None:
            if overlap_bwd or pc.overlap_dispatch == "skip":
                # backward: the markers already synced every bucket
                # in-backward; skip: benchmark compute baseline, grads
                # deliberately left unsynced
                pass
            else:
                grads = bucketed_grad_sync(grads, buckets, pc,
                                           fabric=fabric)
        else:
            grads = sync_grads_dp(grads, specs, pc, fabric)
        if pc.param_mode == "dp":
            grads = clip_by_global_norm(grads, oc)
        elif pc.param_mode == "zero1" and pc.dp > 1:
            grads = clip_by_global_norm(grads, oc,
                                        sq_psum_axes=pc.dp_axis_name)
        if pc.param_mode == "zero1":
            new_params, new_opt = apply_updates_zero1(
                params, grads, opt_state, oc, pc)
        else:
            new_params, new_opt = apply_updates_dp(
                params, grads, opt_state, oc, pc)
        dp_axes = pc.dp_axis_name
        total_g = lax.psum(total, dp_axes) if pc.dp > 1 else total
        count_g = lax.psum(count.astype(jnp.float32), dp_axes) \
            if pc.dp > 1 else count.astype(jnp.float32)
        metrics = {
            "loss": replicate_scalar(total_g / jnp.maximum(count_g, 1.0),
                                     pc, mesh_axes),
            "aux_loss": replicate_scalar(aux, pc, mesh_axes),
            "tokens": replicate_scalar(count_g, pc, mesh_axes),
        }
        return new_params, new_opt, metrics

    p_specs = param_pspecs(params_shapes, specs, pc)
    o_specs = opt_pspecs(opt_shapes, specs, pc)
    batch_shapes = input_shapes(cfg, shape_kind="train", seq_len=8,
                                global_batch=pc.dp)  # structure only
    b_specs = batch_pspecs(batch_shapes, pc)

    shard_fn = shard_map(
        step_fn, mesh=mesh,
        in_specs=(p_specs, o_specs, b_specs),
        out_specs=(p_specs, o_specs,
                   {"loss": P(), "aux_loss": P(), "tokens": P()}),
        check_vma=False)
    jitted = jax.jit(shard_fn,
                     donate_argnums=(0, 1) if donate else ())
    in_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs),
             jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs),
             jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs))
    return StepBundle(jitted, in_sh, None, params_shapes, opt_shapes,
                      specs, pc)


def cache_pspecs(cfg: ModelConfig, pc: ParallelConfig,
                 seq_shard: bool = False):
    """PartitionSpecs matching init_caches' structure: batch dim sharded
    over DP; with ``seq_shard`` the KV caches' sequence dim additionally
    shards over the TP axis (flash-decoding layout); ``pos``/state
    scalars P().

    With dp == 1 (e.g. long_500k's global batch of 1) everything is
    replicated across the data axes."""
    from repro.models.attention import KVCache
    dp = None if pc.dp <= 1 else (
        pc.dp_axes if len(pc.dp_axes) > 1 else pc.dp_axes[0])
    tp = pc.tp_axis if (seq_shard and pc.tp > 1) else None
    shapes = jax.eval_shape(
        lambda: init_caches(cfg, pc, 1, max(8 * max(pc.tp, 1), 8),
                            rolling=False, seq_shard=seq_shard))

    def spec_of(stacked, sd, kv_seq: bool):
        nd = len(sd.shape)
        if nd == 0:
            return P()
        lead = 2 if stacked else 0
        if stacked and nd <= 2:        # stacked pos (n_cycles, cnt)
            return P(*([None] * nd))
        dims = [None] * nd
        dims[lead] = dp                # batch dim
        if kv_seq and nd >= lead + 3:
            dims[lead + 2] = tp        # (B, H, L, hd): shard L
        return P(*dims)

    def tree_specs(tree, stacked):
        def walk(node):
            if isinstance(node, KVCache):
                return KVCache(
                    spec_of(stacked, node.k, True),
                    spec_of(stacked, node.v, True),
                    spec_of(stacked, node.pos, False))
            if isinstance(node, (dict,)):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, (list,)):
                return [walk(v) for v in node]
            if isinstance(node, tuple) and hasattr(node, "_fields"):
                return type(node)(*[spec_of(stacked, f, False)
                                    for f in node])
            return spec_of(stacked, node, False)
        return walk(tree)

    return {"prefix": tree_specs(shapes["prefix"], False),
            "cycles": tree_specs(shapes["cycles"], True)}


@dataclass
class ServeBundle:
    serve_step: Any
    p_specs: Any
    c_specs: Any
    specs: Any
    params_shapes: Any


def make_serve_step(cfg: ModelConfig, pc: ParallelConfig, mesh: Mesh, *,
                    rolling: bool = False, seq_shard: bool = False,
                    attn_impl: str = "xla") -> ServeBundle:
    """One decode (or chunked-prefill) step against stacked caches.

    ``seq_shard``: TP-sequence-sharded KV caches (flash-decoding LSE
    merge) for replicated-KV archs -- decode (S_new == 1) only."""
    params_shapes, specs = param_shapes(cfg, pc)

    def step_fn(params, tokens, caches, pos0):
        logits, new_caches = decode_step(
            params, specs, tokens, caches, pos0, cfg, pc, rolling=rolling,
            seq_shard=seq_shard, attn_impl=attn_impl)
        return logits, new_caches

    p_specs = param_pspecs(params_shapes, specs, pc)
    c_specs = cache_pspecs(cfg, pc, seq_shard=seq_shard)
    dp = None if pc.dp <= 1 else (
        pc.dp_axes if len(pc.dp_axes) > 1 else pc.dp_axes[0])
    shard_fn = shard_map(
        step_fn, mesh=mesh,
        in_specs=(p_specs, P(dp, None), c_specs, P()),
        out_specs=(P(dp, None, None), c_specs),
        check_vma=False)
    jitted = jax.jit(shard_fn, donate_argnums=(2,))
    return ServeBundle(jitted, p_specs, c_specs, specs, params_shapes)


def paged_cache_pspecs(cfg: ModelConfig, pc: ParallelConfig):
    """PartitionSpecs matching init_paged_caches' structure.

    KV pools shard their ``n_blocks`` dim over DP (each DP shard serves
    its own requests out of its own blocks; block-table entries are
    shard-local physical indices), recurrent states shard their batch
    dim -- conveniently the same rule: the leading non-stacked dim."""
    dp = None if pc.dp <= 1 else (
        pc.dp_axes if len(pc.dp_axes) > 1 else pc.dp_axes[0])
    shapes = jax.eval_shape(
        lambda: init_paged_caches(cfg, pc, 1, 2 * max(pc.dp, 1), 8))

    def spec_of(stacked, sd):
        nd = len(sd.shape)
        lead = 2 if stacked else 0
        if nd <= lead:
            return P(*([None] * nd))
        dims = [None] * nd
        dims[lead] = dp
        return P(*dims)

    return {
        "prefix": jax.tree.map(lambda sd: spec_of(False, sd),
                               shapes["prefix"]),
        "cycles": jax.tree.map(lambda sd: spec_of(True, sd),
                               shapes["cycles"]),
    }


def make_paged_serve_step(cfg: ModelConfig, pc: ParallelConfig, mesh: Mesh,
                          *, attn_impl: str = "xla") -> ServeBundle:
    """One continuous-batching tick against paged caches.

    The program/state separation follows ``make_serve_step``: this
    builds the jitted shard_map *program* once; all mutable serving
    state (the cache pytree, the host-side block tables / lengths inside
    :class:`~repro.models.attention.PageCtx`) flows through as
    arguments, so one compiled step serves every admission pattern.
    Token shape ``(B, S)`` recompiles only per distinct S -- the engine
    keeps S in {1, prefill_chunk}."""
    from repro.models.attention import PageCtx
    params_shapes, specs = param_shapes(cfg, pc)

    def step_fn(params, tokens, caches, ctx):
        return paged_decode_step(params, specs, tokens, caches, ctx,
                                 cfg, pc, attn_impl=attn_impl)

    p_specs = param_pspecs(params_shapes, specs, pc)
    c_specs = paged_cache_pspecs(cfg, pc)
    dp = None if pc.dp <= 1 else (
        pc.dp_axes if len(pc.dp_axes) > 1 else pc.dp_axes[0])
    ctx_specs = PageCtx(block_table=P(dp, None), lengths=P(dp),
                        n_new=P(dp), reset=P(dp))
    shard_fn = shard_map(
        step_fn, mesh=mesh,
        in_specs=(p_specs, P(dp, None), c_specs, ctx_specs),
        out_specs=(P(dp, None, None), c_specs),
        check_vma=False)
    jitted = jax.jit(shard_fn, donate_argnums=(2,))
    return ServeBundle(jitted, p_specs, c_specs, specs, params_shapes)


def input_shapes(cfg: ModelConfig, *, shape_kind: str, seq_len: int,
                 global_batch: int, dtype=jnp.int32):
    """ShapeDtypeStruct stand-ins for every model input (dry-run pattern:
    weak-type-correct, shardable, no allocation)."""
    B, S = global_batch, seq_len
    if cfg.frontend == "audio":
        return {
            "embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    if cfg.frontend == "vision" and shape_kind == "train":
        s_text = max(S - cfg.n_patches, 8)
        return {
            "tokens": jax.ShapeDtypeStruct((B, s_text), jnp.int32),
            "patch_embeds": jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((B, s_text), jnp.int32),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
