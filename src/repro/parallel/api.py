"""Parallelism configuration and manual-SPMD collective helpers.

The whole model runs inside one ``jax.shard_map`` over the full mesh
(manual mode on every axis).  Axis roles:

* ``dp_axes``  -- data parallelism (possibly hierarchical: ("pod","data")).
  Gradient synchronization over these axes uses the paper's generalized
  allreduce / reduce-scatter / all-gather schedules.
* ``tp_axis``  -- Megatron-style tensor parallelism with sequence-parallel
  residuals: the residual stream is sharded over the sequence dim on
  ``tp_axis``; each block boundary does all-gather(seq) going in and
  reduce-scatter(seq) coming out.  With tp=1 both collectives are no-ops.

``collective_impl`` selects XLA-native all-gather/reduce-scatter or the
paper's schedule-based ppermute programs for the TP boundary collectives
(a §Perf experiment); DP gradient sync always goes through the paper's
machinery (that *is* the reproduction).

When ``dp_axes`` spans multiple fabric levels (e.g. ("pod", "data") with
DCN between pods and ICI inside), attach a
:class:`repro.topology.Topology` via the ``topology`` field: gradient
sync then routes through :func:`dp_grad_allreduce`, which picks
flat-vs-hierarchical (and the outer step count r) per message size from
the per-level fabric parameters instead of flattening everything into
one cyclic group.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.allreduce import (all_gather_flat, allreduce_flat,
                                  allreduce_tree, hierarchical_allreduce,
                                  reduce_scatter_flat)
from repro.core.cost_model import Fabric, TPU_V5E_ICI
from repro.core.monoid import CombineLike, resolve_combine
from repro.core.schedule import ShapeError, max_r
from repro.topology.fabric import Topology

AxisName = Union[str, Tuple[str, ...]]


@dataclass(frozen=True)
class ParallelConfig:
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    dp: int = 1                    # static product of dp axis sizes
    tp: int = 1
    param_mode: str = "dp"         # dp | zero1 | fsdp
    grad_r: Optional[int] = None   # gen-allreduce step override (None = autotune)
    grad_n_buckets: Optional[int] = None  # pipelined buckets (None = autotune)
    grad_combine: str = "auto"     # auto | add | pallas (ExecPlan combines)
    grad_group: str = "cyclic"     # cyclic | hypercube
    collective_impl: str = "xla"   # xla | group  (TP boundary collectives)
    moe_dispatch: str = "tp"       # tp | gshard | schedule  (MoE expert
    # dispatch: "tp" = TP-sharded experts, no dispatch collective;
    # "gshard" = expert-parallel all-to-all via lax.all_to_all (the
    # oracle); "schedule" = the same dispatch through the
    # permutation-group all_to_all_flat step tables)
    topology: Optional[Topology] = None  # multi-level fabric of dp_axes
    tuning: bool = False           # consult the measured tuning table
    # (repro.tuning) for gradient-sync schedule choice; False = analytic
    # cost model only
    decode_collectives: str = "xla"  # xla | plan  (serving decode-path TP
    # psum / vocab all-gather: "plan" runs them on ExecPlan schedules
    # picked by autotune.choose() at the decode message size -- the
    # r = max_r / traff_rounds latency regime the paper targets)
    remat: bool = True
    scan_layers: bool = True
    overlap_bucket_bytes: Optional[int] = None  # reverse-layer gradient
    # bucket size for the backward-overlapped sync (None = no bucketing:
    # one post-backward flat allreduce, the historical behavior)
    overlap_dispatch: str = "backward"  # backward | post | skip -- when
    # bucketing is on: "backward" dispatches each bucket's allreduce
    # from inside the backward pass via custom_vjp markers
    # (attach_overlap_sync), "post" syncs the same buckets after the
    # backward completes (the A/B control: identical collectives,
    # dispatch timing is the only difference), "skip" elides DP sync
    # entirely (benchmark compute-baseline ONLY -- grads stay unsynced)
    overlap_compute_us: Optional[float] = None  # per-bucket backward
    # compute estimate (us) forwarded to the autotuner as its
    # compute_overlap_us hint; None prices buckets by raw cost
    accum_dtype = jnp.float32

    @property
    def dp_axis_name(self) -> AxisName:
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    @property
    def hierarchical_dp(self) -> bool:
        """Whether DP gradient sync should compose per-level schedules."""
        return (self.topology is not None
                and self.topology.n_levels > 1
                and len(self.dp_axes) == self.topology.n_levels)


def dp_grad_allreduce(tree, pc: ParallelConfig, *, mean: bool = True,
                      fabric: Fabric = TPU_V5E_ICI,
                      op: CombineLike = "sum",
                      compute_overlap_us: Optional[float] = None,
                      tag: Optional[str] = None):
    """Gradient allreduce over the DP axes.

    With a multi-level ``pc.topology`` this routes through the
    topology-aware path (reduce-scatter on the fast inner level, the
    generalized allreduce with tunable r on the slow outer level,
    all-gather back); otherwise the flat generalized allreduce over the
    (possibly flattened) DP axis tuple.

    Gradient buckets of **any** size ride the DP split: the fused flat
    buffer is rarely divisible by ``dp``, and the collectives now run
    the balanced exact (ragged) split natively -- the autotuner prices
    such buckets by true moved bytes (no padding bytes), and the zero1
    path shards them exactly (see
    :func:`repro.core.allreduce.tree_reduce_scatter`).

    ``fabric`` tunes the *flat* path only; the hierarchical path reads
    per-level alpha/beta/gamma from ``pc.topology`` (override it via
    ``parallel_config_for(..., topology=...)`` for non-v5e machines).
    ``pc.grad_n_buckets`` pins the ExecPlan executor's pipelined bucket
    count (None = autotuned from the same fabric) and ``pc.grad_combine``
    its combine kernel routing ("auto" = Pallas combine_n on TPU).
    ``pc.tuning`` opts the schedule choice into the measured tuning
    table (:mod:`repro.tuning`): when a measurement taken on this
    backend covers the gradient's size, it overrides the model's pick.

    NOTE on ``pc.grad_r``: on a flat mesh it tunes the schedule over the
    full DP size (range [0, max_r(dp)]); on a hierarchical mesh it pins
    the hierarchical family and tunes the *outer level's* allreduce, so
    its valid range shrinks to [0, max_r(outer_size)].  Out-of-range
    values fail fast here with the hierarchical meaning spelled out
    rather than deep inside the schedule compiler.

    ``op`` generalizes the reduction over the same schedules: any
    monoid ("sum" / "max" / "min" / "mean" / a
    :class:`~repro.core.monoid.Monoid` / a callable).  Non-sum
    operators compose with ``mean=False`` only; ``pc.grad_combine``
    keeps selecting the *implementation* (Pallas vs plain elementwise)
    and composes with ``op`` as ``"<op>:pallas"``.

    ``compute_overlap_us`` is the backward-overlap hint forwarded to the
    autotuner on the flat path (the hierarchical path prices per level
    and takes no hint today); ``tag`` names a scope around this
    dispatch's ExecPlan ops (the overlapped sync passes
    ``"grad_bucket<k>"``).
    """
    if pc.dp == 1:
        return tree
    monoid, impl = resolve_combine(op)
    if monoid.name == "sum":
        combine = pc.grad_combine     # historical spellings, incl. "add"
    elif pc.grad_combine == "pallas" and monoid.fuses_pallas:
        combine = f"{monoid.name}:pallas"
    else:
        combine = monoid
    if mean and monoid.name not in ("sum", "mean"):
        raise ValueError(f"dp_grad_allreduce(op={monoid.name!r}) needs "
                         f"mean=False (mean only composes with sum)")
    with jax.named_scope("grad_sync"):
        if pc.hierarchical_dp:
            outer = pc.topology.outer
            if pc.grad_r is not None and \
                    not 0 <= pc.grad_r <= max_r(outer.size):
                raise ValueError(
                    f"grad_r={pc.grad_r} invalid for hierarchical DP over "
                    f"{pc.topology.describe()}: it tunes the outer level "
                    f"{outer.name}[{outer.size}], so the valid range is "
                    f"[0, {max_r(outer.size)}] (use grad_r=None to autotune "
                    f"flat-vs-hierarchical)")
            return hierarchical_allreduce(tree, pc.dp_axes, pc.topology,
                                          r=pc.grad_r, mean=mean,
                                          combine=combine,
                                          n_buckets=pc.grad_n_buckets,
                                          tune=pc.tuning)
        return allreduce_tree(tree, pc.dp_axis_name, mean=mean, r=pc.grad_r,
                              fabric=fabric, combine=combine,
                              n_buckets=pc.grad_n_buckets, tune=pc.tuning,
                              compute_overlap_us=compute_overlap_us,
                              tag=tag)


def grads_all_finite(tree, pc: ParallelConfig, *,
                     fabric: Fabric = TPU_V5E_ICI) -> jnp.ndarray:
    """Global loss-scale overflow check: True iff every gradient element
    on every DP rank is finite.

    The classic dynamic-loss-scaling guard is a *max*-allreduce, not a
    sum: each rank reduces its leaves to one "any non-finite?" indicator
    and the DP-wide maximum of the indicators decides whether the step
    applies or the scale backs off.  The indicator rides the exact same
    generalized schedules as the gradients (``op="max"`` through
    :func:`dp_grad_allreduce`), so the check works on hierarchical
    meshes and with measured tuning without any extra machinery --
    that one-scalar max-allreduce is the latency-optimal corner
    (r = max_r) of the paper's family by construction.

    Returns a boolean scalar (replicated across DP ranks).
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.bool_(True)
    bad = [jnp.any(~jnp.isfinite(g)) for g in leaves
           if jnp.issubdtype(g.dtype, jnp.inexact)]
    if not bad:
        return jnp.bool_(True)   # integer trees cannot overflow to inf
    local = jnp.stack(bad).any().astype(jnp.float32)
    if pc.dp == 1:
        return local == 0
    synced = dp_grad_allreduce(local[None], pc, mean=False, fabric=fabric,
                               op="max")
    return synced[0] == 0


# ---------------------------------------------------------------------------
#  backward-overlapped gradient sync (reverse-layer bucketing + markers)
# ---------------------------------------------------------------------------
#
# The post-backward sync pays for *all* gradient communication after the
# last backward FLOP -- nothing is hidden.  The overlapped path groups
# the parameter leaves into reverse-layer-order buckets
# (``reverse_layer_buckets``; sized by ``pc.overlap_bucket_bytes``) and
# wraps each bucket's params in a ``jax.custom_vjp`` identity marker
# (``attach_overlap_sync``) whose backward rule runs that bucket's
# ``dp_grad_allreduce``.  Autodiff reaches a marker's backward rule the
# moment every cotangent of its bucket exists, i.e. right when that
# layer band's backward completes -- so the last layers' gradients hit
# the wire while earlier layers are still differentiating, which is
# exactly the producer the multi-bucket pipelined ExecPlan executor
# wants.  ``bucketed_grad_sync`` runs the *same* per-bucket collectives
# after the backward instead (``pc.overlap_dispatch == "post"``): the
# two modes differ only in dispatch timing, so their results are
# bit-identical by construction -- the A/B pair the 8-device worker's
# bit-exactness gate and the overlap benchmark both lean on.

def reverse_layer_buckets(layers, sizes, bucket_bytes):
    """Greedy reverse-layer-order bucketing of parameter leaves.

    ``layers[i]`` is leaf i's layer index (backward completes highest
    layer first), ``sizes[i]`` its payload in bytes.  Leaves are taken
    in descending layer order (ties: ascending leaf index, so the
    partition is deterministic) and packed into buckets of at most
    ``bucket_bytes``; a leaf larger than the budget gets its own
    bucket.  Returns a list of index lists -- an exact partition of
    ``range(len(layers))``.

    >>> reverse_layer_buckets([0, 1, 1, 2], [4, 4, 4, 4], 8)
    [[3, 1], [2, 0]]
    >>> reverse_layer_buckets([0, 1], [4, 100], 8)   # oversize leaf
    [[1], [0]]
    >>> sorted(sum(reverse_layer_buckets([2, 0, 1], [9, 9, 9], 4), []))
    [0, 1, 2]
    """
    if len(layers) != len(sizes):
        raise ValueError(f"reverse_layer_buckets: {len(layers)} layers "
                         f"vs {len(sizes)} sizes")
    budget = max(int(bucket_bytes), 1)
    order = sorted(range(len(layers)), key=lambda i: (-layers[i], i))
    buckets, cur, cur_bytes = [], [], 0
    for i in order:
        if cur and cur_bytes + sizes[i] > budget:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += int(sizes[i])
    if cur:
        buckets.append(cur)
    return buckets


def _overlap_marker(pc: ParallelConfig, fabric: Fabric, tag: str):
    """Identity on a bucket's params whose VJP syncs the bucket's grads.

    Forward is the identity (zero cost, fused away); the backward rule
    runs this bucket's ``dp_grad_allreduce(mean=True)`` on the
    cotangents, so gradients emerge from ``jax.grad`` already
    DP-synced -- dispatched at the execution point where this bucket's
    backward completed, not after the whole pass.
    """
    @jax.custom_vjp
    def marker(*leaves):
        return leaves

    def fwd(*leaves):
        return leaves, None

    def bwd(_, cts):
        synced = dp_grad_allreduce(
            list(cts), pc, mean=True, fabric=fabric,
            compute_overlap_us=pc.overlap_compute_us, tag=tag)
        return tuple(synced)

    marker.defvjp(fwd, bwd)
    return marker


def attach_overlap_sync(tree, buckets, pc: ParallelConfig, *,
                        fabric: Fabric = TPU_V5E_ICI):
    """Wrap each bucket of ``tree``'s leaves in its dispatch marker.

    ``buckets`` is the index partition from
    :func:`reverse_layer_buckets` over ``jax.tree.flatten(tree)``
    order.  Apply to the *params* before the loss: the returned tree
    computes identically forward, and under ``jax.grad`` each bucket's
    gradient comes back DP-mean-synced by the marker's backward rule
    (callers must then skip the post-backward ``sync_grads_dp``).
    """
    leaves, treedef = jax.tree.flatten(tree)
    out = list(leaves)
    for k, bucket in enumerate(buckets):
        marker = _overlap_marker(pc, fabric, f"grad_bucket{k}")
        synced = marker(*[out[i] for i in bucket])
        for i, v in zip(bucket, synced):
            out[i] = v
    return jax.tree.unflatten(treedef, out)


def bucketed_grad_sync(grads, buckets, pc: ParallelConfig, *,
                       fabric: Fabric = TPU_V5E_ICI):
    """Post-backward sync of the *same* per-bucket collectives.

    The ``overlap_dispatch == "post"`` control arm: per bucket, the
    identical leaf list in the identical order through the identical
    ``dp_grad_allreduce`` call as :func:`attach_overlap_sync`'s
    backward rule -- only the dispatch point differs, which is what
    makes backward-vs-post bit-exact comparisons meaningful.
    """
    leaves, treedef = jax.tree.flatten(grads)
    out = list(leaves)
    for k, bucket in enumerate(buckets):
        synced = dp_grad_allreduce(
            [out[i] for i in bucket], pc, mean=True, fabric=fabric,
            compute_overlap_us=pc.overlap_compute_us,
            tag=f"grad_bucket{k}")
        for i, v in zip(bucket, synced):
            out[i] = v
    return jax.tree.unflatten(treedef, out)


def tp_rank(pc: ParallelConfig):
    return lax.axis_index(pc.tp_axis) if pc.tp > 1 else jnp.int32(0)


# ---------------------------------------------------------------------------
#  sequence-parallel boundary collectives
# ---------------------------------------------------------------------------

def seq_all_gather(x: jnp.ndarray, pc: ParallelConfig, axis: int = 1):
    """(B, S/tp, d) -> (B, S, d) over the TP axis."""
    if pc.tp == 1:
        return x
    if pc.collective_impl == "group":
        shape = x.shape
        flat = jnp.moveaxis(x, axis, 0).reshape(x.shape[axis], -1)
        g = all_gather_flat(flat.reshape(-1), pc.tp_axis)
        g = g.reshape(pc.tp * shape[axis], -1)
        g = g.reshape((pc.tp * shape[axis],) + shape[:axis] + shape[axis + 1:])
        return jnp.moveaxis(g, 0, axis)
    return lax.all_gather(x, pc.tp_axis, axis=axis, tiled=True)


def seq_reduce_scatter(x: jnp.ndarray, pc: ParallelConfig, axis: int = 1):
    """(B, S, d) partial-sums -> (B, S/tp, d) reduced shards over TP.

    The sequence dim must divide ``tp`` (both the XLA ``psum_scatter``
    and the shard reshape below need uniform per-rank shards; the ragged
    flat collectives cover uneven *flat* buffers, not uneven tensor
    dims) -- a violation raises :class:`~repro.core.schedule.ShapeError`
    instead of silently mis-reshaping.
    """
    if pc.tp == 1:
        return x
    if x.shape[axis] % pc.tp:
        raise ShapeError(
            f"seq_reduce_scatter: dim {axis} not divisible by tp={pc.tp}",
            expected=f"multiple of {pc.tp}", actual=x.shape[axis])
    if pc.collective_impl == "group":
        moved = jnp.moveaxis(x, axis, 0)
        flat = moved.reshape(-1)
        shard = reduce_scatter_flat(flat, pc.tp_axis,
                                    accum_dtype=None)
        out_shape = (moved.shape[0] // pc.tp,) + moved.shape[1:]
        return jnp.moveaxis(shard.reshape(out_shape), 0, axis)
    return lax.psum_scatter(x, pc.tp_axis, scatter_dimension=axis, tiled=True)


def tp_psum(x, pc: ParallelConfig):
    if pc.tp == 1:
        return x
    return lax.psum(x, pc.tp_axis)


# ---------------------------------------------------------------------------
#  decode-time TP collectives (serving)
# ---------------------------------------------------------------------------
#
# Tensor-parallel decode moves tiny messages -- a few KB of activations
# per token step -- which is the latency-dominated corner where the
# paper's large-r / traff_rounds schedules beat bandwidth-optimal
# pipelines.  With ``pc.decode_collectives == "plan"`` the serve step's
# TP psum and vocab all-gather run on ExecPlan ppermute programs whose
# schedule is picked by :func:`repro.core.autotune.choose` at trace time
# from the actual decode message size (consulting the measured tuning
# table when ``pc.tuning``).  Each pick is appended to a module-level
# log so tests and benches can assert what was chosen, including
# ``Choice.source == "measured"``.

_DECODE_CHOICE_LOG: list = []


def decode_choice_log():
    """Trace-time decode collective picks: [(op, nbytes, Choice), ...]."""
    return list(_DECODE_CHOICE_LOG)


def reset_decode_choice_log():
    _DECODE_CHOICE_LOG.clear()


def _decode_choice(pc: ParallelConfig, nbytes: int, itemsize: int, op: str):
    from repro.core.autotune import choose, schedule_for
    choice = choose(pc.tp, int(nbytes), TPU_V5E_ICI,
                    tune=pc.tuning, itemsize=itemsize)
    _DECODE_CHOICE_LOG.append((op, int(nbytes), choice))
    return choice, schedule_for(choice, pc.tp)


def tp_decode_psum(x, pc: ParallelConfig):
    """TP psum for the decode path (see module note above)."""
    if pc.tp == 1:
        return x
    if pc.decode_collectives != "plan":
        return lax.psum(x, pc.tp_axis)
    itemsize = jnp.dtype(x.dtype).itemsize
    choice, sched = _decode_choice(pc, x.size * itemsize, itemsize, "psum")
    out = allreduce_flat(x.reshape(-1), pc.tp_axis, sched,
                         accum_dtype=pc.accum_dtype,
                         n_buckets=choice.n_buckets)
    return out.reshape(x.shape).astype(x.dtype)


def tp_decode_all_gather(x, pc: ParallelConfig, axis: int = -1):
    """TP all-gather for the decode path (vocab-parallel logits).

    A pure gather has exactly one schedule family here -- the paper's
    distribution phase (``build_all_gather``, ceil(lg P) steps) -- so
    unlike the psum there is no family to pick.  ``choose()`` still runs
    at the gathered message size for its pipelining decision
    (``n_buckets``) and so the pick lands in the decode choice log with
    its ``source`` tag.
    """
    if pc.tp == 1:
        return x
    if pc.decode_collectives != "plan":
        return lax.all_gather(x, pc.tp_axis, axis=axis, tiled=True)
    from repro.core.schedule import build_all_gather
    axis = axis % x.ndim
    itemsize = jnp.dtype(x.dtype).itemsize
    nbytes = int(x.size) * itemsize * pc.tp     # total gathered bytes
    choice, _ = _decode_choice(pc, nbytes, itemsize, "all_gather")
    moved = jnp.moveaxis(x, axis, 0)
    g = all_gather_flat(moved.reshape(-1), pc.tp_axis,
                        build_all_gather(pc.tp),
                        n_buckets=choice.n_buckets)
    g = g.reshape((pc.tp * moved.shape[0],) + moved.shape[1:])
    return jnp.moveaxis(g, 0, axis)


# ---------------------------------------------------------------------------
#  parameter partitioning metadata
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParamSpec:
    """How one parameter is laid out across the mesh.

    tp_dim:   dimension sharded over the TP axis (None = replicated in TP;
              such params need a psum over TP of their grads).
    fsdp_dim: dimension sharded over the DP axes in "fsdp" mode
              (None = replicated; grads then sync via the paper's
              allreduce).  Chosen automatically as the largest dim
              divisible by dp.
    """

    tp_dim: Optional[int] = None
    fsdp_dim: Optional[int] = None
    stacked: int = 0               # leading stacking dims (consumed by scans)

    @property
    def tp_replicated(self) -> bool:
        return self.tp_dim is None


def choose_fsdp_dim(shape: Tuple[int, ...], dp: int,
                    avoid: Optional[int] = None) -> Optional[int]:
    """Largest dim divisible by dp (excluding ``avoid``, the tp dim).

    Divisibility here is a hard ``shard_map`` constraint (per-device
    param shards enter the step function as static equal shapes), not a
    collectives limitation: leaves left unsharded (``None``) still sync
    their gradients through the ragged flat allreduce, which charges
    and moves only true bytes for awkward sizes.
    """
    best, best_size = None, 0
    for i, s in enumerate(shape):
        if i == avoid:
            continue
        if s % dp == 0 and s > best_size:
            best, best_size = i, s
    return best


def shard_leaf(x: jnp.ndarray, spec: ParamSpec, pc: ParallelConfig,
               tp_index: int, dp_index: int) -> jnp.ndarray:
    """Slice a *full* parameter down to this device's shard (init path)."""
    if spec.tp_dim is not None and pc.tp > 1:
        n = x.shape[spec.tp_dim] // pc.tp
        x = lax.dynamic_slice_in_dim(x, tp_index * n, n, spec.tp_dim)
    if pc.param_mode == "fsdp" and spec.fsdp_dim is not None and pc.dp > 1:
        n = x.shape[spec.fsdp_dim] // pc.dp
        x = lax.dynamic_slice_in_dim(x, dp_index * n, n, spec.fsdp_dim)
    return x


def fsdp_gather(x: jnp.ndarray, spec: ParamSpec, pc: ParallelConfig,
                *, sliced: bool = False):
    """All-gather an fsdp-sharded param for use; VJP is reduce-scatter,
    which is exactly ZeRO-3 gradient flow.

    ``sliced``: the leading stacking dims have already been consumed by
    the (cycle, group) scans, so the fsdp dim shifts down by ``stacked``.
    """
    if pc.param_mode != "fsdp" or spec.fsdp_dim is None or pc.dp == 1:
        return x
    axis = spec.fsdp_dim - (int(spec.stacked) if sliced else 0)
    return lax.all_gather(x, pc.dp_axis_name, axis=axis, tiled=True)


def fsdp_gather_tree(params, specs, pc: ParallelConfig, *,
                     sliced: bool = False):
    # ParamSpec is an unregistered dataclass, i.e. a pytree leaf, so the
    # specs tree aligns leaf-for-leaf with the params tree.
    return jax.tree.map(
        lambda x, s: fsdp_gather(x, s, pc, sliced=sliced), params, specs)
