"""JAX executors for compiled collective schedules.

Maps the paper's permutation-composition communication model onto JAX SPMD:

* every communication operator ``t_g`` is a static ``lax.ppermute``
  (a cyclic shift for ``CyclicGroup`` -- the native pattern of a TPU ICI
  ring/torus; a pairwise exchange for ``HypercubeGroup``);
* every distributed vector is one ``(u,)`` row; a step's outgoing rows
  travel as one flat concatenation, sliced back into rows on arrival;
* combines are fused local adds (the Pallas ``combine_n`` kernel on TPU).

All functions below must be called *inside* ``jax.shard_map`` (manual SPMD)
over the axis (or tuple of axes) being reduced.  The schedule is compiled
and verified ahead of trace time (see :mod:`repro.core.schedule`), then
lowered once into a dense :class:`~repro.core.execplan.ExecPlan` of static
numpy index tables (cached per schedule), so the traced program is a
straight-line sequence of static gathers, ppermutes and batched combines
that XLA's latency-hiding scheduler can overlap with compute.  The old
per-row Python replay (one ``(u,)`` array per live vector, restacked every
step) is gone -- :func:`repro.core.execplan.execute` is the only replay.

**Multi-bucket pipelining**: ``n_buckets > 1`` splits the message into
equal buckets that replay the same plan staggered by one step, so bucket
``k``'s ``ppermute`` is staged while bucket ``k-1``'s combines run (the
doubly-pipelined structure of Traeff, arXiv:2109.12626).  The autotuned
bucket count comes from the extended cost model
(:func:`repro.core.cost_model.pipelined_schedule_cost`), which charges
pipeline fill/drain latencies against the comm/combine overlap.

TPU adaptation note (vs. the paper's 10GE cluster): the cyclic group's
powers ``t^k`` are *multi-hop* on a physical ring when k > 1.  XLA lowers a
``collective-permute`` with shift k to k ring hops (or uses the torus'
wraparound links), so the per-step latency term alpha grows with the hop
distance.  The schedules still apply unchanged -- only the Fabric
parameters used by the autotuner change (alpha_step ~ alpha_link * hops).

Hierarchical path (multi-pod / multi-node): a flat schedule over the
flattened ``(pod, data)`` index pays DCN latency and bandwidth on *every*
step, because each cyclic shift moves some pair of ranks across the pod
boundary and the SPMD step completes only when the slowest transfer lands.
:func:`hierarchical_allreduce` instead replays a
:class:`~repro.topology.hierarchical.HierarchicalSchedule`: reduce-scatter
over the fast inner axis (``lax.ppermute`` over ``"data"`` only -- pure
ICI), then the generalized allreduce with tunable ``r`` over the slow
outer axis on a 1/inner-sized chunk (the only DCN traffic), then
all-gather back over the inner axis.  The flat-vs-hierarchical decision,
the outer ``r`` and the outer bucket count are autotuned per message size
by :func:`repro.topology.hierarchical.choose_collective`.
"""
from __future__ import annotations

import dataclasses
import math
from functools import lru_cache
from typing import (TYPE_CHECKING, Callable, List, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .autotune import choose, schedule_for
from .cost_model import (Fabric, TPU_V5E_ICI, choose_a2a, choose_n_buckets,
                         ragged_choose_n_buckets)
from .execplan import (ExecPlan, compile_a2a_plan, compile_plan, execute)
from .monoid import CombineLike, resolve_combine
from .schedule import (Schedule, ShapeError, build_all_gather,
                       build_generalized, build_reduce_scatter,
                       ragged_sizes)

if TYPE_CHECKING:  # repro.topology is the layer above this one; importing
    # it at module scope would cycle through repro.core.__init__, so the
    # executors below bind to it at call time.
    from repro.topology.fabric import Topology
    from repro.topology.hierarchical import HierarchicalSchedule

AxisName = Union[str, Tuple[str, ...]]
CombineFn = CombineLike   # legacy alias; combine= is monoid-aware now


def axis_size(axis_name: AxisName) -> int:
    if isinstance(axis_name, (tuple, list)):
        return math.prod(lax.axis_size(a) for a in axis_name)
    return lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
#  ragged (exact-split) chunk plumbing
# ---------------------------------------------------------------------------
#  The balanced split of repro.core.schedule.ragged_sizes assigns chunk c
#  exactly sizes[c] elements (never rounding m up to a multiple of P).
#  ppermute rows must still be SPMD-uniform, so chunks share the physical
#  width u_max = ceil(m / P) with a zero-filled tail; combines only pair
#  rows holding the same chunk index per device, so the tails stay zero
#  and the final gather extracts each chunk's exact valid prefix.  For
#  divisible m every index table degenerates to a plain reshape.

def _build_extract_index(sizes: Tuple[int, ...], w: int) -> np.ndarray:
    idx = np.concatenate(
        [c * w + np.arange(s, dtype=np.int64)
         for c, s in enumerate(sizes)]) if sum(sizes) else \
        np.zeros((0,), np.int64)
    idx.setflags(write=False)
    return idx


_EXTRACT_CACHE_MAX_ELEMS = 1 << 20


@lru_cache(maxsize=64)
def _cached_extract_index(sizes: Tuple[int, ...], w: int) -> np.ndarray:
    return _build_extract_index(sizes, w)


def _ragged_extract_index(sizes: Tuple[int, ...], w: int) -> np.ndarray:
    """(sum(sizes),) indices extracting each chunk's valid prefix from a
    row-major ``(P, w)`` stacked buffer in chunk order.

    Only the general (caller-provided, possibly unbalanced) allgatherv
    path needs an index gather; the balanced split used everywhere else
    goes through the slice-based :func:`_flat_chunks` /
    :func:`_ragged_flatten`, which build no O(m) constants.  Caching is
    capped per entry (vectors above ``_EXTRACT_CACHE_MAX_ELEMS`` are
    rebuilt per call, never pinned) and by entry count; the worst-case
    resident set is maxsize * cap * 8 bytes, not unbounded.
    """
    if sum(sizes) > _EXTRACT_CACHE_MAX_ELEMS:
        return _build_extract_index(sizes, w)
    return _cached_extract_index(sizes, w)


def _flat_chunks(x: jnp.ndarray, P: int) -> Tuple[jnp.ndarray, int]:
    """The balanced exact split as one flat ``(P * w,)`` buffer, ``w =
    ceil(m / P)``: chunk ``c`` occupies ``[c * w, (c + 1) * w)`` with its
    ``ragged_sizes(m, P)[c]`` valid elements first and a zero tail.
    Returns ``(flat, w)``; for ``P | m`` the buffer is ``x`` itself.

    Built from 1-D slices and one concatenate.  The executors index this
    buffer with 1-D (dynamic) slices and never row-slice a ``(P, w)``
    matrix: the TPU compiler's time for a row slice of a reshaped 2-D
    buffer grows with its size (tens of seconds per 64 Mi elements),
    while a 1-D slice compiles in constant time.
    """
    m = x.shape[0]
    if m % P == 0 and m:
        return x, m // P
    u, rem = divmod(m, P)
    w = u + 1                                   # ceil(m / P); rem >= 1
    zero = jnp.zeros((1,), x.dtype)
    parts = [x[:rem * w]]
    for c in range(P - rem):
        start = rem * w + c * u
        parts += [x[start:start + u], zero]
    return jnp.concatenate(parts), w


def exact_chunks(x: jnp.ndarray, P: int):
    """Split a flat vector into the ``(P, u_max)`` chunk buffer of the
    balanced exact split (a plain reshape when ``P`` divides ``m``);
    returns ``(chunks, m)``.  Public counterpart of
    :func:`repro.core.schedule.ragged_sizes`: row ``c`` holds chunk
    ``c``'s ``sizes[c]`` valid elements, zero-filled to the common
    width.  Used by the zero1 optimizer to slice parameters with the
    same geometry as their gradient shards (the executors keep the flat
    form, :func:`_flat_chunks`).
    """
    flat, w = _flat_chunks(x, P)
    return flat.reshape(P, w), x.shape[0]


def _ragged_flatten(flat: jnp.ndarray, m: int, P: int) -> jnp.ndarray:
    """Inverse of :func:`_flat_chunks` for a ``(P * w,)`` buffer of
    reduced chunks in chunk order: exact ``(m,)`` concatenation of the
    valid prefixes (1-D slices; the zero tails are dropped, not
    gathered)."""
    w = flat.shape[0] // P
    if m == P * w:
        return flat
    u, rem = divmod(m, P)
    if w != u + 1:
        raise ShapeError("_ragged_flatten: row width != ceil(m / P)",
                         expected=u + 1, actual=w)
    return jnp.concatenate([flat[:rem * w]] +
                           [flat[c * w:c * w + u] for c in range(rem, P)])


def _lazy_init_rows(flat: jnp.ndarray, w: int, plan: ExecPlan, d) -> List:
    """Per-slot initial rows as *lazy* dynamic slices of the flat chunk
    buffer: row k is chunk ``init_rows[k, d]``, i.e. ``flat[c * w:(c + 1)
    * w]``, left as a dynamic-slice op for XLA to fuse into its first
    consumer.  Unwritten slots start as None."""
    rows: List = []
    for k in range(plan.n_rows0):
        idx = lax.dynamic_index_in_dim(jnp.asarray(plan.init_rows[k]), d,
                                       keepdims=False)
        rows.append(lax.dynamic_slice_in_dim(flat, idx * w, w))
    return rows + [None] * (plan.n_slots - plan.n_rows0)


def _bucket_rows(rows: List, n_buckets: int):
    """Split every slot row into n_buckets column slices (padding the
    row length to a multiple of the bucket count)."""
    u = next(r.shape[0] for r in rows if r is not None)
    n_buckets = max(1, min(int(n_buckets), u if u else 1))
    if n_buckets == 1:
        return [rows], u
    ub = -(-u // n_buckets)
    pad = ub * n_buckets - u

    def padded(r):
        return jnp.concatenate([r, jnp.zeros((pad,), r.dtype)]) if pad else r

    rows = [None if r is None else padded(r) for r in rows]
    return [[None if r is None else r[j * ub:(j + 1) * ub] for r in rows]
            for j in range(n_buckets)], u


def _merge_rows(bucket_rows: List[List], u: int) -> List:
    """Inverse of :func:`_bucket_rows`: full-width row per slot."""
    if len(bucket_rows) == 1:
        return bucket_rows[0]
    out = []
    for parts in zip(*bucket_rows):
        out.append(None if parts[0] is None
                   else jnp.concatenate(parts)[:u])
    return out


def _linear_axis_index(axis_name: AxisName):
    return lax.axis_index(axis_name)


def _final_gather(rows: List, plan: ExecPlan, d) -> jnp.ndarray:
    """Put the reduced rows into chunk order: the flat ``(P * w,)``
    concatenation of chunk 0, 1, ... ``P - 1``.

    The final placement is device-dependent (chunk c sits in slot
    ``final_rows[c, d]``), so each chunk is one dynamic 1-D slice of the
    concatenated rows; the slot -> position remap is static and
    composes with the table.
    """
    used = np.unique(plan.final_rows[plan.final_rows >= 0])
    pos = np.full(plan.n_slots, -1, dtype=np.int32)
    pos[used] = np.arange(len(used), dtype=np.int32)
    tbl = pos[plan.final_rows]                      # (P, P) row positions
    w = rows[int(used[0])].shape[0]
    full = jnp.concatenate([rows[int(s)] for s in used])
    order = jnp.take(jnp.asarray(tbl), d, axis=1)   # (P,)
    return jnp.concatenate([lax.dynamic_slice_in_dim(full, order[c] * w, w)
                            for c in range(tbl.shape[0])])


# ---------------------------------------------------------------------------
#  flat (1-D) collectives; call inside shard_map
# ---------------------------------------------------------------------------

def allreduce_flat(x: jnp.ndarray, axis_name: AxisName,
                   sched: Schedule, *, accum_dtype=None,
                   combine: CombineFn = "auto",
                   n_buckets: int = 1,
                   tag: Optional[str] = None) -> jnp.ndarray:
    """Generalized allreduce of a flat vector using a compiled schedule.

    Accepts **any** length: uneven sizes run natively on the balanced
    exact split (chunk ``c`` carries ``sched.chunk_sizes(m)[c]``
    elements; the physical rows share the width ``ceil(m / P)`` with
    zero tails that the final gather drops).  ``n_buckets`` pipelines
    the message across equal buckets (see module docstring); ``combine``
    selects the combine *operator* (a Monoid, "sum" / "max" / "min" /
    "mean", or a binary callable) and/or its implementation ("auto",
    "add", "pallas" -- see
    :func:`repro.core.monoid.resolve_combine`).  Mean's divide and
    premul_sum's input scale run here, once over the whole message.
    ``tag`` labels the executor's trace span (see
    :func:`repro.core.execplan.execute`).
    """
    P = sched.P
    actual = axis_size(axis_name)
    if P != actual:
        raise ShapeError(f"schedule P != size of axis {axis_name!r}",
                         expected=P, actual=actual)
    monoid, _ = resolve_combine(combine)
    if P == 1:
        return monoid.finalize(monoid.prepare(x, P), P).astype(x.dtype)
    orig_dtype = x.dtype
    if accum_dtype is not None:
        x = x.astype(accum_dtype)
    x = monoid.prepare(x, P)
    m = x.shape[0]
    flat, w = _flat_chunks(x, P)                          # (P * u_max,)
    plan = compile_plan(sched)
    d = _linear_axis_index(axis_name)
    rows = _lazy_init_rows(flat, w, plan, d)
    bucket_rows, u = _bucket_rows(rows, n_buckets)
    bucket_rows = execute(plan, bucket_rows, axis_name, combine=combine,
                          tag=tag)
    rows = _merge_rows(bucket_rows, u)
    out = _final_gather(rows, plan, d)                     # (P * u_max,)
    out = _ragged_flatten(out, m, P)                       # exact (m,)
    out = monoid.finalize(out, P)
    return out.astype(orig_dtype)


def reduce_scatter_flat(x: jnp.ndarray, axis_name: AxisName,
                        sched: Optional[Schedule] = None, *,
                        accum_dtype=None,
                        combine: CombineFn = "auto",
                        n_buckets: int = 1) -> jnp.ndarray:
    """Reduction phase only: returns this device's fully reduced chunk.

    Device d ends up owning chunk d (canonical place-0 layout).  Any
    input length is accepted: under the balanced exact split device d's
    chunk is ``x[offsets[d] : offsets[d] + sizes[d]]`` with ``sizes =
    ragged_sizes(m, P)``; the returned buffer always has the physical
    width ``ceil(m / P)``, zero-filled past the valid prefix on devices
    whose chunk is one element short (for ``m`` divisible by ``P`` the
    whole buffer is valid, exactly as before).  Use
    :func:`all_gather_flat` with ``sizes=`` to reassemble exactly.
    ``combine`` selects the operator exactly as in
    :func:`allreduce_flat` (monoid bookends included).
    """
    P = axis_size(axis_name)
    if sched is None:
        sched = build_reduce_scatter(P)
    elif sched.P != P:
        raise ShapeError(f"schedule P != size of axis {axis_name!r}",
                         expected=sched.P, actual=P)
    monoid, _ = resolve_combine(combine)
    if P == 1:
        return monoid.finalize(monoid.prepare(x, P), P).astype(x.dtype)
    orig_dtype = x.dtype
    if accum_dtype is not None:
        x = x.astype(accum_dtype)
    x = monoid.prepare(x, P)
    flat, w = _flat_chunks(x, P)
    plan = compile_plan(sched)
    d = _linear_axis_index(axis_name)
    rows = _lazy_init_rows(flat, w, plan, d)
    bucket_rows, u = _bucket_rows(rows, n_buckets)
    bucket_rows = execute(plan, bucket_rows, axis_name, combine=combine)
    rows = _merge_rows(bucket_rows, u)
    # the single final row's slot is SPMD-uniform; canonical place-0
    # layout means device d already owns chunk d.
    slot = int(plan.final_rows.max())
    return monoid.finalize(rows[slot], P).astype(orig_dtype)


def all_gather_flat(chunk: jnp.ndarray, axis_name: AxisName,
                    sched: Optional[Schedule] = None, *,
                    n_buckets: int = 1,
                    sizes: Optional[Sequence[int]] = None) -> jnp.ndarray:
    """Distribution phase only: device d contributes chunk d, all devices
    end with the concatenation of all chunks.

    ``sizes`` turns this into an exact **allgatherv**: entry d is the
    valid prefix length of rank d's chunk (the physical rows stay
    uniform at ``chunk.shape[0]``), and the result is the exact
    ``sum(sizes)``-element concatenation of the prefixes -- the inverse
    of a ragged :func:`reduce_scatter_flat` when ``sizes =
    ragged_sizes(m, P)``.
    """
    P = axis_size(axis_name)
    if sched is None:
        sched = build_all_gather(P)
    elif sched.P != P:
        raise ShapeError(f"schedule P != size of axis {axis_name!r}",
                         expected=sched.P, actual=P)
    if sizes is not None:
        sizes = tuple(int(s) for s in sizes)
        if len(sizes) != P:
            raise ShapeError("all_gather_flat: sizes must have one entry "
                             "per rank", expected=P, actual=len(sizes))
        w = int(chunk.shape[0])
        if sizes and (max(sizes) > w or min(sizes) < 0):
            raise ShapeError("all_gather_flat: chunk valid prefix outside "
                             f"the physical row width {w}",
                             expected=f"0 <= size <= {w}",
                             actual=(min(sizes), max(sizes)))
    if P == 1:
        return chunk if sizes is None else chunk[:sizes[0]]
    plan = compile_plan(sched)
    rows = [chunk] + [None] * (plan.n_slots - 1)
    bucket_rows, u = _bucket_rows(rows, n_buckets)
    bucket_rows = execute(plan, bucket_rows, axis_name)
    rows = _merge_rows(bucket_rows, u)
    d = _linear_axis_index(axis_name)
    out = _final_gather(rows, plan, d)                     # (P * w,)
    if sizes is None:
        return out
    total = sum(sizes)
    w = int(chunk.shape[0])
    if sizes == ragged_sizes(total, P) and \
            (total == P * w or w == total // P + 1):
        return _ragged_flatten(out, total, P)  # balanced: 1-D slices
    idx = _ragged_extract_index(sizes, w)      # general allgatherv
    return jnp.take(out, jnp.asarray(idx))


def all_to_all_flat(x: jnp.ndarray, axis_name: AxisName, *,
                    kind: str = "auto",
                    fabric: Fabric = TPU_V5E_ICI,
                    n_buckets: int = 1) -> jnp.ndarray:
    """Permutation-group all-to-all of a flat vector over ``axis_name``.

    Device ``d`` contributes ``P`` equal chunks ``x[c*u:(c+1)*u]``
    (chunk ``c`` destined for rank ``c``) and receives the concatenation
    of every rank's chunk ``d`` -- the exact transpose
    ``lax.all_to_all`` computes on equally-split buffers, replayed as
    the same static ``ppermute`` step tables the reductions use (see
    :func:`repro.core.execplan.compile_a2a_plan`).

    ``kind``: "direct" (P-1 single-row steps, minimal traffic),
    "bruck" (ceil(lg P) steps of ~P/2 rows, minimal latency), or
    "auto" -- picked per message size by the alpha-beta cost model
    (:func:`repro.core.cost_model.choose_a2a`).  ``n_buckets`` software-
    pipelines the exchange exactly like the reductions (there are no
    combines to overlap, but staging bucket ``k``'s ppermute behind
    bucket ``k-1``'s still splits the wire serialization on
    asynchronous fabrics).

    All-to-all is a pure permutation of P*P distinct blocks, so unlike
    the reductions it has no ragged form whose tails an SPMD program
    could drop uniformly: the length must divide ``P`` (the same
    contract as ``lax.all_to_all``), enforced as a typed
    :class:`~repro.core.schedule.ShapeError`.
    """
    P = axis_size(axis_name)
    if P == 1:
        return x
    m = x.shape[0]
    if m % P:
        raise ShapeError(
            f"all_to_all_flat needs P | m over axis {axis_name!r}",
            expected=f"multiple of {P}", actual=m)
    if kind == "auto":
        kind = choose_a2a(P, m * x.dtype.itemsize, fabric)
    plan = compile_a2a_plan(P, kind)
    d = _linear_axis_index(axis_name)
    rows = _lazy_init_rows(x, m // P, plan, d)
    bucket_rows, u = _bucket_rows(rows, n_buckets)
    bucket_rows = execute(plan, bucket_rows, axis_name)
    rows = _merge_rows(bucket_rows, u)
    return _final_gather(rows, plan, d)


# ---------------------------------------------------------------------------
#  pytree API with bucketing + autotuned schedule choice
# ---------------------------------------------------------------------------

def _flatten_tree(tree):
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [leaf.shape for leaf in leaves]
    sizes = [int(np.prod(s)) if len(s) else 1 for s in shapes]
    dtypes = [leaf.dtype for leaf in leaves]
    if leaves:
        common = jnp.result_type(*dtypes)
        flat = jnp.concatenate([leaf.reshape(-1).astype(common)
                                for leaf in leaves])
    else:
        flat = jnp.zeros((0,))
    return flat, (treedef, shapes, sizes, dtypes)


def _unflatten_tree(flat, spec):
    treedef, shapes, sizes, dtypes = spec
    leaves, off = [], 0
    for sh, sz, dt in zip(shapes, sizes, dtypes):
        leaves.append(flat[off:off + sz].reshape(sh).astype(dt))
        off += sz
    return jax.tree.unflatten(treedef, leaves)


def allreduce_tree(tree, axis_name: AxisName, *,
                   r: Optional[int] = None,
                   mean: bool = False,
                   fabric: Fabric = TPU_V5E_ICI,
                   accum_dtype=jnp.float32,
                   combine: CombineFn = "auto",
                   n_buckets: Optional[int] = None,
                   tune: Optional[bool] = None,
                   compute_overlap_us: Optional[float] = None,
                   tag: Optional[str] = None):
    """Allreduce (sum or mean) a pytree of arrays over ``axis_name`` using
    the generalized algorithm.

    If ``r`` is None the step count is autotuned from the fabric parameters
    via the paper's eq (37) / exact search (section 8).  All leaves are
    fused into one flat buffer so the whole gradient pays the per-step
    latency once, then the buffer is *re-split* into ``n_buckets``
    pipelined buckets (``None`` = autotuned from the fabric via the
    extended cost model) so communication of bucket k overlaps combines
    of bucket k-1.  ``tune`` opts the autotuner into the measured tuning
    table (see :mod:`repro.tuning`; None reads ``REPRO_TUNING``).

    ``combine`` selects the operator for the whole family (any Monoid /
    "sum" / "max" / "min" / "mean" / callable): the autotuner prices
    candidates with the monoid's own gamma, and for non-add monoids the
    f32 accumulation cast is skipped (max/min lose nothing to the
    accumulator, and an int max must stay bit-exact past 2**24).
    ``mean`` composes only with the sum operator.

    ``compute_overlap_us`` is the backward-overlap hint forwarded to the
    autotuner (:func:`repro.core.autotune.choose`): the overlappable
    compute still running when this collective dispatches, which makes
    the chooser rank candidates by *exposed* rather than raw cost.  It
    only applies when the schedule is autotuned (``r is None``).
    ``tag`` labels the executor's trace span (per-bucket identification
    for the overlapped gradient sync).
    """
    P = axis_size(axis_name)
    monoid, _ = resolve_combine(combine)
    if mean and monoid.name not in ("sum", "mean"):
        raise ValueError(f"mean=True only composes with the sum operator, "
                         f"not {monoid.name!r}")
    if monoid.kind != "add":
        accum_dtype = None
    if P == 1:
        return tree
    flat, spec = _flatten_tree(tree)
    itemsize = int(flat.dtype.itemsize)
    nbytes = flat.size * itemsize
    if r is None:
        # raggedness is an *element*-count property: the executor splits
        # elements, so the chooser needs the itemsize, not just bytes
        ch = choose(P, int(nbytes), fabric, tune=tune, itemsize=itemsize,
                    monoid=monoid, compute_overlap_us=compute_overlap_us)
        sched = schedule_for(ch, P)
        if n_buckets is None:
            n_buckets = ch.n_buckets
    else:
        sched = build_generalized(P, r)
        if n_buckets is None:
            if flat.size % P:
                n_buckets = ragged_choose_n_buckets(sched, int(nbytes),
                                                    fabric,
                                                    itemsize=itemsize,
                                                    monoid=monoid)
            else:
                n_buckets = choose_n_buckets(sched, int(nbytes), fabric,
                                             monoid=monoid)
    out = allreduce_flat(flat, axis_name, sched, accum_dtype=accum_dtype,
                         combine=combine, n_buckets=n_buckets, tag=tag)
    if mean and monoid.name == "sum":
        out = out / P
    return _unflatten_tree(out, spec)


# ---------------------------------------------------------------------------
#  hierarchical collectives over multi-level fabrics
# ---------------------------------------------------------------------------

def hierarchical_allreduce_flat(x: jnp.ndarray, axis_names: Sequence[str],
                                hs: "HierarchicalSchedule", *,
                                accum_dtype=None,
                                combine: CombineFn = "auto",
                                n_buckets: int = 1) -> jnp.ndarray:
    """Replay a :class:`HierarchicalSchedule` over the named mesh axes.

    ``axis_names`` are ordered outermost (slowest) first, aligned with
    ``hs.topology.levels``; every ppermute runs over exactly one axis, so
    inner-level steps never touch the outer (DCN) links.  ``n_buckets``
    pipelines the outer-level allreduce -- the phase that rides the slow
    links and so profits most from comm/combine overlap.

    The monoid's affine bookends act on the *whole* composition, not per
    level: premul's scale is applied once before the first inner
    reduce-scatter and mean's divide once after the last all-gather
    (the per-level executors run the bookend-free core combine), so a
    2-level mesh scales by f -- not f^2 -- and mean divides by the full
    ``topology.P`` in one exact step.
    """
    topo = hs.topology
    if len(axis_names) != topo.n_levels:
        raise ShapeError(f"axis names {axis_names!r} != levels of "
                         f"{topo.describe()}", expected=topo.n_levels,
                         actual=len(axis_names))
    for name, lvl in zip(axis_names, topo.levels):
        if lax.axis_size(name) != lvl.size:
            raise ShapeError(f"axis {name!r} size != topology level "
                             f"{lvl.name}", expected=lvl.size,
                             actual=lax.axis_size(name))
    monoid, impl = resolve_combine(combine)
    if monoid.pre_scale is not None or monoid.post_divide:
        # strip the bookends off what the per-level executors see (they
        # must run the bare core combine -- a per-level prepare/finalize
        # would compound the scale once per stage); keep the caller's
        # Pallas hint where the string form can still express it
        core = dataclasses.replace(monoid, pre_scale=None,
                                   post_divide=False)
        combine = "pallas" if (impl == "pallas" and core.kind == "add") \
            else core
    if topo.P == 1:
        return monoid.finalize(monoid.prepare(x, 1), 1).astype(x.dtype)
    orig_dtype = x.dtype
    if accum_dtype is not None:
        x = x.astype(accum_dtype)
    x = monoid.prepare(x, topo.P)
    m = x.shape[0]
    inner = topo.inner_size
    # The per-level composition is kept on the divisible layout: each
    # inner reduce-scatter must hand the next level a chunk whose
    # boundaries all ranks agree on, and chaining *balanced* ragged
    # splits level-by-level would make the final all-gather's extraction
    # depend on every intermediate width.  One explicit pad to the inner
    # multiple (at most inner_size - 1 zeros) keeps the composition
    # exact; the outer allreduce below is ragged-native regardless.
    mp = -(-m // inner) * inner
    if mp != m:
        x = jnp.concatenate([x, jnp.zeros((mp - m,), x.dtype)])
    # reduce-scatter down the inner axes, innermost (fastest) first
    inner_axes = [axis_names[i] for i in hs.inner_levels]
    cur = x
    for sched, axis in zip(hs.rs, inner_axes):
        cur = reduce_scatter_flat(cur, axis, sched, combine=combine)
    # generalized allreduce of the chunk across the outer axis
    cur = allreduce_flat(cur, axis_names[0], hs.ar, combine=combine,
                         n_buckets=n_buckets)
    # all-gather back up, reverse order
    for sched, axis in zip(hs.ag, reversed(inner_axes)):
        cur = all_gather_flat(cur, axis, sched)
    return monoid.finalize(cur[:m], topo.P).astype(orig_dtype)


def hierarchical_allreduce(tree, axis_names: Sequence[str],
                           topology: "Topology", *,
                           r: Optional[int] = None,
                           mean: bool = False,
                           accum_dtype=jnp.float32,
                           combine: CombineFn = "auto",
                           n_buckets: Optional[int] = None,
                           tune: Optional[bool] = None):
    """Allreduce (sum or mean) a pytree over hierarchical mesh axes.

    ``r`` tunes the outer-level step count; with ``r=None`` the plan
    (flat vs hierarchical, the step count, and the pipelined bucket
    count) is autotuned per message size from the per-level fabric
    parameters.  A flat plan executes the chosen schedule over the
    flattened axis tuple -- hierarchical is only used when the cost
    model says it wins.  ``tune`` opts the plan chooser into the
    measured tuning table (single-level topologies only; see
    :func:`repro.topology.hierarchical.choose_collective`).
    """
    from repro.topology.hierarchical import (HierarchicalSchedule,
                                             build_hierarchical,
                                             choose_collective,
                                             schedules_for_plan)
    P = topology.P
    monoid, _ = resolve_combine(combine)
    if mean and monoid.name not in ("sum", "mean"):
        raise ValueError(f"mean=True only composes with the sum operator, "
                         f"not {monoid.name!r}")
    if monoid.kind != "add":
        accum_dtype = None
    if P == 1:
        return tree
    flat, spec = _flatten_tree(tree)
    nbytes = flat.size * flat.dtype.itemsize
    if r is None:
        plan = choose_collective(topology, int(nbytes), tune=tune,
                                 itemsize=int(flat.dtype.itemsize))
        sched = schedules_for_plan(plan, topology)
        if n_buckets is None:
            n_buckets = plan.n_buckets
    else:
        sched = build_hierarchical(topology, r)
    if n_buckets is None:
        n_buckets = 1
    if isinstance(sched, HierarchicalSchedule):
        out = hierarchical_allreduce_flat(flat, tuple(axis_names), sched,
                                          accum_dtype=accum_dtype,
                                          combine=combine,
                                          n_buckets=n_buckets)
    else:
        out = allreduce_flat(flat, tuple(axis_names), sched,
                             accum_dtype=accum_dtype, combine=combine,
                             n_buckets=n_buckets)
    if mean and monoid.name == "sum":
        out = out / P
    return _unflatten_tree(out, spec)


def psum_tree(tree, axis_name: AxisName, *, mean: bool = False):
    """XLA-native baseline for comparisons."""
    out = lax.psum(tree, axis_name)
    if mean:
        out = jax.tree.map(lambda x: x / axis_size(axis_name), out)
    return out


# ---------------------------------------------------------------------------
#  ZeRO-style helpers: reduce-scatter grads / all-gather params over DP axis
# ---------------------------------------------------------------------------

def tree_reduce_scatter(tree, axis_name: AxisName, *, mean: bool = False,
                        accum_dtype=jnp.float32):
    """Fuse a pytree into one buffer, reduce-scatter it, and return this
    device's ``(ceil(size / P),)`` shard plus the spec needed to
    reassemble.  The total size need not divide ``P``: the shard is the
    exact ragged chunk of the balanced split, zero-filled past its valid
    prefix (``ragged_sizes(size, P)[d]`` elements)."""
    P = axis_size(axis_name)
    flat, spec = _flatten_tree(tree)
    m = flat.shape[0]
    shard = reduce_scatter_flat(flat, axis_name, accum_dtype=accum_dtype)
    if mean and P > 1:
        shard = shard / P
    return shard, (spec, m)


def tree_all_gather(shard, spec_m, axis_name: AxisName):
    """Inverse of :func:`tree_reduce_scatter` (exact allgatherv: each
    rank contributes only its ragged chunk's valid prefix)."""
    spec, m = spec_m
    P = axis_size(axis_name)
    flat = all_gather_flat(shard, axis_name,
                           sizes=ragged_sizes(m, P) if P > 1 else None)
    return _unflatten_tree(flat[:m], spec)
