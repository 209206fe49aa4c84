"""Lowered execution layer: Schedule -> ExecPlan -> vectorized replay.

The schedule compiler (:mod:`repro.core.schedule`) emits symbolic steps
over *row lists*; the original executor replayed them as Python lists of
per-device ``(u,)`` arrays with a ``jnp.stack``/unstack round-trip per
step and per-row Python loops rebuilt at every trace.  This module
compiles a verified :class:`~repro.core.schedule.Schedule` **once** into
an :class:`ExecPlan` of dense, static numpy index tables, then executes
the whole replay *in place* on a single stacked ``(R, u)`` buffer:

* the compiler register-allocates every live distributed vector to a
  fixed **slot** of the buffer for its whole lifetime: rows that a step
  keeps are never copied, a combine writes its result into the slot of
  the resident row it consumes, and a received row lands in a slot freed
  by a row that died -- so each step is one static gather feeding the
  ``ppermute`` plus two static in-place updates (slices where the slots
  are contiguous, scatters otherwise), instead of one op per live row;
* the slot tables compose every storage reordering, so no permutation
  is ever materialized at runtime; zero-communication bookkeeping steps
  (e.g. the Ring schedule's final row compaction) fold away entirely;
* initial/final placement tables (previously rebuilt with O(P^2) Python
  loops at every trace) are precomputed and cached per schedule.

On top of the lowered plan, :func:`execute` implements **multi-bucket
software pipelining**: the caller splits the message into ``n_buckets``
bucket buffers and the tick loop stages bucket ``k``'s ``ppermute``
while bucket ``k-1``'s combines run (program order within a tick: all
sends first, then all combines), which lets an asynchronous backend
overlap the wire time of one bucket with the combine time of another --
the doubly-pipelined structure of Traeff (arXiv:2109.12626).  All
combines of a tick are batched into one fused call routed through the
Pallas :func:`~repro.kernels.fused_combine.combine_n` kernel instead of
per-bucket chained ``jnp.add`` -- by default on TPU only; off-TPU
``combine="auto"`` stays on ``jnp.add`` (interpret-mode Pallas is a
correctness path, not a fast one) and ``combine="pallas"`` opts into
the kernel explicitly.

:func:`simulate_plan` is a pure-numpy runner over the *same* tables,
used by the tests to prove the lowering bit-exact against the symbolic
simulator oracle for every (P, r, kind).

The training stack feeds this executor two ways: the post-backward path
reduces one flat gradient tensor through a single (possibly
multi-bucket) :func:`execute`, while the backward-overlapped path
(:func:`repro.parallel.api.attach_overlap_sync`) dispatches one
``execute`` per reverse-layer gradient bucket *as the backward pass
produces it*, tagging each dispatch (``tag="grad_bucket<k>"``) so a
profile and the exposed-comm roofline
(:func:`repro.core.cost_model.overlap_tick_costs`) can line the
per-bucket dispatches up against backward compute.

The replay's ops carry named scopes, so a device profile attributes
them at run time: ``execplan.<kind>`` around the replay (inside the
caller's ``tag``, where given), ``tick<t>`` around each tick and
``combine`` around each tick's combines.
"""
from __future__ import annotations

import math
from contextlib import nullcontext
from dataclasses import dataclass
from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .monoid import CombineLike, Monoid, resolve_combine
from .schedule import Schedule, ShapeError, ragged_offsets, ragged_sizes


def _frozen(a) -> np.ndarray:
    a = np.asarray(a, dtype=np.int32)
    a.setflags(write=False)
    return a


# ---------------------------------------------------------------------------
#  cached placement tables (previously O(P^2) Python loops per trace)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def initial_row_table(sched: Schedule) -> np.ndarray:
    """tbl[row, d] = which local chunk device d puts in initial row."""
    P = sched.P
    R = len(sched.initial_slots)
    tbl = np.zeros((R, P), dtype=np.int32)
    for k in range(R):
        for d in range(P):
            tbl[k, d] = sched.chunk_of_initial_row(k, d)
    return _frozen(tbl)


@lru_cache(maxsize=None)
def final_row_table(sched: Schedule) -> np.ndarray:
    """tbl[c, d] = which final *schedule* row holds reduced chunk c on d
    (-1 where the schedule does not materialize that chunk)."""
    P = sched.P
    tbl = np.full((P, P), -1, dtype=np.int32)
    for k in range(len(sched.final_slots)):
        for d in range(P):
            tbl[sched.final_chunk_index(k, d), d] = k
    return _frozen(tbl)


# ---------------------------------------------------------------------------
#  the lowered plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ExecStep:
    """One lowered communication step over the slot-allocated buffer.

    Execution (all reads of ``buf`` precede all writes; destination slot
    sets are disjoint by construction):

        tx  = buf[tx_slots]                        # one static gather
        rx  = ppermute(tx)
        buf[add_dst] = buf[add_src] (+) rx[add_arr]   # combines
        buf[recv_slots] = rx[recv_arr]                # freed slots

    ``add_src == add_dst`` almost always (the combine absorbs the
    resident row in place); a resident that survives the step elsewhere
    forces a fresh destination slot.  Slots a step does not mention keep
    their rows untouched -- kept rows are never copied.
    """

    shift: int
    perm: Tuple[Tuple[int, int], ...]   # ppermute (src, dst) pairs
    tx_slots: np.ndarray                # (T,)  slots to send
    add_src: np.ndarray                 # (A,)  resident slots read
    add_dst: np.ndarray                 # (A,)  slots written with the sum
    add_arr: np.ndarray                 # (A,)  arrival index per combine
    recv_slots: np.ndarray              # (Rv,) slots receiving new rows
    recv_arr: np.ndarray                # (Rv,) arrival index per recv

    @property
    def n_tx(self) -> int:
        return len(self.tx_slots)

    @property
    def n_adds(self) -> int:
        return len(self.add_src)

    @property
    def in_place_adds(self) -> bool:
        return bool((self.add_src == self.add_dst).all())


@dataclass(frozen=True)
class ExecPlan:
    """Dense, trace-free lowering of one compiled Schedule.

    ``n_slots``            -- buffer height; the executor runs the whole
    replay on one ``(n_slots, u)`` array per device.
    ``init_rows[row, d]``  -- chunk of device d's input placed in slot
    ``row`` (initial rows occupy slots 0..R0-1 in schedule order).
    ``final_rows[c, d]``   -- slot holding reduced chunk c on device d
    after the last step (-1 where the chunk is not materialized).  Slot
    assignment is SPMD-uniform; only the chunk labels differ per device.
    """

    P: int
    kind: str
    n_rows0: int
    n_slots: int
    steps: Tuple[ExecStep, ...]
    init_rows: np.ndarray               # (R0, P)
    final_rows: np.ndarray              # (P, P)

    @property
    def n_steps(self) -> int:
        return len(self.steps)


def tick_structure(plan: ExecPlan, n_buckets: int) -> List[List[Tuple[int, int]]]:
    """The executor's software-pipelining timeline as data.

    Returns one entry per tick of :func:`execute` /
    :func:`simulate_plan`: the ``(bucket, step)`` pairs active at that
    tick, in bucket order -- tick ``t`` runs step ``t - j`` of bucket
    ``j``, over ``n_steps + n_buckets - 1`` ticks.  This is the single
    source of truth the per-tick cost model
    (:func:`repro.core.cost_model.ragged_tick_costs`) and the traced
    replay (:mod:`repro.obs.instrument`) both follow, so predicted and
    measured timelines line up tick-for-tick by construction.

    >>> from repro.core.schedule import build_generalized
    >>> plan = compile_plan(build_generalized(4, 0))
    >>> tick_structure(plan, 2)[:3]
    [[(0, 0)], [(0, 1), (1, 0)], [(0, 2), (1, 1)]]
    >>> len(tick_structure(plan, 2)) == plan.n_steps + 1
    True
    """
    B = max(int(n_buckets), 1)
    S = plan.n_steps
    return [[(j, t - j) for j in range(B) if 0 <= t - j < S]
            for t in range(S + B - 1)]


@lru_cache(maxsize=None)
def compile_plan(sched: Schedule) -> ExecPlan:
    """Lower a verified Schedule into slot-allocated index tables (cached).

    Register allocation over buffer slots: ``slot_of`` maps each live
    symbolic row to its fixed physical slot.  A kept row keeps its slot;
    a combine reuses the slot of the resident row it consumes (unless
    that row survives the step elsewhere, which forces a fresh slot);
    received rows fill the lowest freed/unused slots in arrival order --
    which keeps hot index ranges contiguous, so the executor's gathers
    and updates lower to static slices wherever the schedule allows.

    >>> from repro.core.schedule import build_generalized
    >>> plan = compile_plan(build_generalized(4, 0))
    >>> plan.n_steps, plan.n_slots, plan.n_rows0
    (4, 4, 4)
    >>> plan is compile_plan(build_generalized(4, 0))   # cached
    True
    """
    g = sched.group
    P = sched.P
    R0 = len(sched.initial_slots)
    slot_of = {row: row for row in range(R0)}   # symbolic row -> slot
    n_slots = R0
    free: List[int] = []
    steps: List[ExecStep] = []
    for st in sched.steps:
        keeps = [i for i, op in enumerate(st.out) if op.kind == "keep"]
        recvs = [i for i, op in enumerate(st.out) if op.kind == "recv"]
        adds = [i for i, op in enumerate(st.out) if op.kind == "add"]
        tx_slots = [slot_of[r] for r in st.tx_rows]
        if st.n_tx == 0 and not recvs and not adds:
            # pure bookkeeping: re-label surviving rows, free the rest.
            new_slot_of = {i: slot_of[st.out[i].res] for i in keeps}
            free = sorted((set(free) | set(slot_of.values()))
                          - set(new_slot_of.values()))
            slot_of = new_slot_of
            continue
        kept_rows = {st.out[i].res for i in keeps}
        res_uses: dict = {}
        for i in adds:
            res_uses[st.out[i].res] = res_uses.get(st.out[i].res, 0) + 1
        new_slot_of = {i: slot_of[st.out[i].res] for i in keeps}
        in_place = [i for i in adds
                    if st.out[i].res not in kept_rows
                    and res_uses[st.out[i].res] == 1]
        fresh = [i for i in adds if i not in in_place]
        for i in in_place:
            new_slot_of[i] = slot_of[st.out[i].res]
        # slots whose rows die this step become free for new arrivals
        surviving = set(new_slot_of.values())
        free = sorted((set(free) | set(slot_of.values())) - surviving)

        def alloc() -> int:
            nonlocal n_slots
            if free:
                return free.pop(0)
            n_slots += 1
            return n_slots - 1

        for i in recvs + fresh:
            new_slot_of[i] = alloc()
        add_all = in_place + fresh
        steps.append(ExecStep(
            shift=st.shift,
            perm=tuple((d, g.apply(st.shift, d)) for d in range(P)),
            tx_slots=_frozen(tx_slots),
            add_src=_frozen([slot_of[st.out[i].res] for i in add_all]),
            add_dst=_frozen([new_slot_of[i] for i in add_all]),
            add_arr=_frozen([st.out[i].arr for i in add_all]),
            recv_slots=_frozen([new_slot_of[i] for i in recvs]),
            recv_arr=_frozen([st.out[i].arr for i in recvs]),
        ))
        slot_of = new_slot_of
    # remap the final schedule-row table to slots
    sched_tbl = final_row_table(sched)
    final_rows = np.full((P, P), -1, dtype=np.int32)
    for c in range(P):
        for d in range(P):
            k = sched_tbl[c, d]
            if k >= 0:
                final_rows[c, d] = slot_of[k]
    return ExecPlan(P=P, kind=sched.kind, n_rows0=R0, n_slots=n_slots,
                    steps=tuple(steps), init_rows=initial_row_table(sched),
                    final_rows=_frozen(final_rows))


# ---------------------------------------------------------------------------
#  vectorized JAX executor with multi-bucket software pipelining
# ---------------------------------------------------------------------------

def _row(buf, i: int, u: int):
    """Row ``i`` of a flat buffer of ``u``-element rows: a static 1-D
    slice.  The executor keeps every multi-row buffer flat because the
    TPU compiler's time for row slices of a 2-D buffer grows with the
    buffer's size, while 1-D slices compile in constant time."""
    i = int(i)
    return buf if buf.shape[0] == u else buf[i * u:(i + 1) * u]


def _pallas_combine(jobs, monoid: Monoid = None):
    """Fuse all (res, arr) pairwise combines of a tick into ONE Pallas
    ``combine_n`` call over the concatenated flat buffers.

    ``jobs`` is a list of (res_rows, arr_rows): equally long lists of
    flat rows; returns the combined rows, one list per job.  The K-way
    kernel (K=2 here) reads both operands once from HBM and writes the
    combine (``monoid.kind``: add / max / min -- all one VPU op per
    element over the same VMEM tiling), instead of one chained
    elementwise dispatch per bucket.  Interpret mode is used off-TPU
    (:func:`repro.kernels.ops.interpret`).

    The kernel's output carries its operands' varying manual axes, so on
    the TPU it traces inside a ``shard_map`` with the default
    ``check_vma=True``.  JAX's Pallas interpreter does not trace under
    that checker, so an off-TPU caller that forces ``combine="pallas"``
    builds its ``shard_map`` with ``check_vma=False`` ("auto" picks the
    elementwise op there and needs neither).
    """
    import jax.numpy as jnp

    from repro.kernels import ops as kernel_ops
    from repro.kernels.fused_combine import _BLOCK, combine_n

    from .monoid import SUM
    if monoid is None:
        monoid = SUM
    res_flat = jnp.concatenate([r for res, _ in jobs for r in res])
    arr_flat = jnp.concatenate([a for _, arr in jobs for a in arr])
    n = res_flat.shape[0]
    dt = res_flat.dtype
    # max/min never lose precision to the accumulator: skip the widening
    accum = jnp.float32 if (monoid.kind == "add"
                            and jnp.issubdtype(dt, jnp.inexact)) else dt
    block = min(_BLOCK, 128 * max(1, math.ceil(n / 128)))
    out = combine_n(jnp.stack([res_flat, arr_flat]), accum_dtype=accum,
                    interpret=kernel_ops.interpret(), block=block,
                    op=monoid.kind)
    outs, off = [], 0
    for res, _ in jobs:
        rows = []
        for r in res:
            rows.append(out[off:off + r.shape[0]])
            off += r.shape[0]
        outs.append(rows)
    return outs


def execute(plan: ExecPlan, bucket_rows: Sequence[List], axis_name, *,
            combine: CombineLike = "auto",
            tag: Optional[str] = None) -> List[List]:
    """Replay ``plan`` over per-bucket slot-row lists inside shard_map.

    ``bucket_rows`` is a list of ``n_buckets`` row lists, each of length
    ``plan.n_slots`` holding this bucket's ``(u_b,)`` row per slot (None
    for not-yet-written slots); all buckets replay the same plan over
    disjoint slices of the message.  Slots are *aliases*: a kept row is
    untouched (zero copies -- on XLA CPU, where functional whole-buffer
    updates materialize, this is what makes the replay cheap), a combine
    rebinds the destination slot, a received row is a row view of the
    ppermute result.

    The tick loop software-pipelines the buckets: at tick ``t`` bucket
    ``j`` runs step ``t - j``, every active bucket's ``ppermute`` is
    issued before any bucket's combines, and all combines of the tick
    are batched into a single fused call on the Pallas path.  With one
    bucket this degenerates to the plain vectorized replay.

    ``combine`` is the *operator*, resolved by
    :func:`repro.core.monoid.resolve_combine`: a :class:`Monoid`, a
    monoid name ("sum" / "max" / "min" / "mean"), a binary callable, or
    one of the implementation spellings "auto" (sum; Pallas
    ``combine_n`` on TPU, plain elementwise elsewhere), "add" (sum via
    ``jnp.add``), "pallas" (sum via the kernel), "<op>:pallas".  The
    affine bookends of mean / premul_sum are the caller's job (they act
    on the whole message, not per step).

    ``tag`` is an optional caller-supplied label, a named scope around
    the replay's ops -- the backward-overlapped gradient sync
    (:func:`repro.parallel.api.dp_grad_allreduce`) tags each gradient
    bucket (e.g. ``"grad_bucket3"``) so per-bucket dispatches are
    identifiable in a device profile.
    """
    import jax

    from repro.kernels import ops as kernel_ops

    monoid, impl = resolve_combine(combine)
    if impl == "auto":
        impl = "pallas" if (monoid.fuses_pallas
                            and not kernel_ops.interpret()) else "op"
    if impl == "pallas" and not monoid.fuses_pallas:
        raise ValueError(f"monoid {monoid.name!r} has no fused Pallas "
                         f"kernel; use the elementwise path")
    bucket_rows = [list(rows) for rows in bucket_rows]
    ticks = tick_structure(plan, len(bucket_rows))
    with jax.named_scope(tag) if tag else nullcontext(), \
            jax.named_scope(f"execplan.{plan.kind}"):
        _execute_ticks(plan, bucket_rows, ticks, axis_name, monoid, impl)
    return bucket_rows


def _execute_ticks(plan: ExecPlan, bucket_rows: List[List], ticks,
                   axis_name, monoid: Monoid, impl: str) -> None:
    """Stage the tick loop in place over ``bucket_rows`` (see execute),
    each tick under its ``tick<t>`` scope."""
    import jax

    # every row has the bucket width u; multi-row buffers stay flat
    u = next(r.shape[0] for rows in bucket_rows for r in rows
             if r is not None)
    for t, active in enumerate(ticks):
        with jax.named_scope(f"tick{t}"):
            _execute_tick(plan, bucket_rows, active, axis_name, monoid,
                          impl, u)


def _execute_tick(plan: ExecPlan, bucket_rows: List[List], active,
                  axis_name, monoid: Monoid, impl: str, u: int) -> None:
    """Stage one tick: every active bucket's send, then its combines
    (the ``combine`` scope), then its received rows land."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    # 1) issue phase: stage every active bucket's communication
    rx = {}
    for j, s in active:
        sp = plan.steps[s]
        if sp.n_tx:
            rows = bucket_rows[j]
            tx = jnp.concatenate([rows[i] for i in sp.tx_slots])
            rx[j] = lax.ppermute(tx, axis_name, perm=sp.perm)
    # 2) combine phase: all pairwise combines of this tick
    with jax.named_scope("combine"):
        if impl == "pallas":
            jobs, owners = [], []
            for j, s in active:
                sp = plan.steps[s]
                if sp.n_adds:
                    rows = bucket_rows[j]
                    jobs.append(([rows[i] for i in sp.add_src],
                                 [_row(rx[j], a, u) for a in sp.add_arr]))
                    owners.append((j, s))
            if jobs:        # ticks of recv-only steps have no combines
                for (j, s), summed in zip(owners,
                                          _pallas_combine(jobs, monoid)):
                    for k, dst in enumerate(plan.steps[s].add_dst):
                        bucket_rows[j][dst] = summed[k]
        else:
            op = monoid.jax_op
            for j, s in active:
                sp = plan.steps[s]
                rows = bucket_rows[j]
                # read every resident before rebinding any slot: a fresh
                # destination may reuse a slot another combine still reads
                sums = [op(rows[src], _row(rx[j], arr, u))
                        for src, arr in zip(sp.add_src, sp.add_arr)]
                for dst, v in zip(sp.add_dst, sums):
                    rows[dst] = v
    # 3) land received rows in their freed slots
    for j, s in active:
        sp = plan.steps[s]
        rows = bucket_rows[j]
        for slot, arr in zip(sp.recv_slots, sp.recv_arr):
            rows[slot] = _row(rx[j], arr, u)


# ---------------------------------------------------------------------------
#  pure-numpy reference runner (the lowering's own oracle)
# ---------------------------------------------------------------------------

def _np_chunks(vec: np.ndarray, P: int) -> np.ndarray:
    """(P, u_max) chunk buffer under the balanced ragged split: chunk c
    holds ``ragged_sizes(m, P)[c]`` real elements, zero-filled to the
    common physical width ``u_max = ceil(m / P)`` (the ppermute rows of
    an SPMD program must be uniform; only the *valid* prefix varies)."""
    m = vec.shape[0]
    sizes = ragged_sizes(m, P)
    offs = ragged_offsets(sizes)
    u = max(-(-m // P), 1)
    out = np.zeros((P, u), vec.dtype)
    for c in range(P):
        out[c, :sizes[c]] = vec[offs[c]:offs[c] + sizes[c]]
    return out


def simulate_plan(sched: Schedule, vectors: List[np.ndarray],
                  n_buckets: int = 1, op=np.add) -> List[np.ndarray]:
    """Replay the *lowered* plan tables over P explicit numpy processes.

    Mirrors :func:`execute` table-for-table (including the bucket split,
    the in-place slot updates, and the ragged zero-filled chunk tails),
    so bit-exact agreement with :func:`repro.core.simulator.simulate`
    proves the lowering correct independently of JAX.  ``op`` is the
    elementwise combine (any monoid's ``np_op``; default sum), applied
    to exactly the same (resident, arrival) pairs as the JAX executor.
    Handles every schedule kind and *any* message length -- uneven
    sizes use the balanced exact split of
    :func:`repro.core.schedule.ragged_sizes`:

    * ``generalized`` / ``ring``: full input vectors, full results;
    * ``reduce_scatter``: any-length inputs, device d returns its owned
      chunk zero-padded to the common physical width ``ceil(m / P)``;
    * ``all_gather`` / ``bruck_all_gather``: device d contributes chunk d
      (``vectors[d]``, lengths may differ by one), every device returns
      the exact concatenation.

    >>> import numpy as np
    >>> from repro.core.schedule import build_generalized
    >>> vecs = [np.full(7, d) for d in range(4)]        # 7 % 4 != 0
    >>> simulate_plan(build_generalized(4, 0), vecs)[0].tolist()
    [6, 6, 6, 6, 6, 6, 6]
    """
    plan = compile_plan(sched)
    P = plan.P
    assert len(vectors) == P
    gather_kinds = ("all_gather", "bruck_all_gather")

    if plan.kind in gather_kinds:
        chunk_sizes = tuple(v.shape[0] for v in vectors)
        w = max(max(chunk_sizes), 1)
        init = []
        for d in range(P):
            row = np.zeros((1, w), vectors[d].dtype)
            row[0, :chunk_sizes[d]] = vectors[d]
            init.append(row)
    else:
        m = vectors[0].shape[0]
        chunk_sizes = ragged_sizes(m, P)
        init = []
        for d in range(P):
            ch = _np_chunks(vectors[d], P)
            init.append(ch[plan.init_rows[:, d]])
    u = init[0].shape[1]
    n_buckets = max(1, min(n_buckets, u if u else 1))
    ub = -(-u // n_buckets)
    bufs = []
    for d in range(P):
        full = np.zeros((plan.n_slots, ub * n_buckets), init[d].dtype)
        full[:plan.n_rows0, :u] = init[d]
        bufs.append([full[:, j * ub:(j + 1) * ub].copy()
                     for j in range(n_buckets)])

    B, S = n_buckets, plan.n_steps
    for t in range(S + B - 1):
        active = [(j, t - j) for j in range(B) if 0 <= t - j < S]
        rx = {}
        for j, s in active:
            sp = plan.steps[s]
            if sp.n_tx:
                arr = [None] * P
                for src, dst in sp.perm:
                    arr[dst] = bufs[src][j][sp.tx_slots].copy()
                rx[j] = arr
        for j, s in active:
            sp = plan.steps[s]
            for d in range(P):
                if sp.n_adds:
                    bufs[d][j][sp.add_dst] = op(bufs[d][j][sp.add_src],
                                                rx[j][d][sp.add_arr])
                if len(sp.recv_slots):
                    bufs[d][j][sp.recv_slots] = rx[j][d][sp.recv_arr]

    state = [np.concatenate(bufs[d], axis=1)[:, :u] for d in range(P)]
    results = []
    for d in range(P):
        cols = plan.final_rows[:, d]
        if (cols >= 0).all():
            # ragged gather: chunk c contributes only its valid prefix
            results.append(np.concatenate(
                [state[d][cols[c]][:chunk_sizes[c]] for c in range(P)]))
        else:
            # reduce-scatter: only the owned chunk is materialized; it is
            # returned at the physical width (zero tail where ragged)
            c = int(np.nonzero(cols >= 0)[0][0])
            results.append(state[d][cols[c]])
    return results


# ---------------------------------------------------------------------------
#  permutation-group all-to-all over the same step tables
# ---------------------------------------------------------------------------
#  An all-to-all (device d holds P chunks x_d[0..P-1]; afterwards device d
#  holds y_d[c] = x_c[d]) is pure data movement under the cyclic group --
#  every transfer is a power of the generator t, so it compiles into the
#  exact ExecStep/ExecPlan tables the reductions use, just with no
#  combines.  Row e is the *displacement class* e: initially device d
#  stores x_d[(d+e) % P] there (the chunk destined for rank d+e), and the
#  device-dependence lives entirely in the same init/final placement
#  tables every other schedule already uses:
#
#  * direct  -- P-1 steps; step e applies t^e to row e, delivering every
#    displacement in one hop: u bytes per step, minimal total traffic
#    (the large-message regime);
#  * bruck   -- ceil(lg P) steps [Bruck & Ho '93]; step k applies t^(2^k)
#    to every row whose displacement has bit k set, so a block with
#    displacement e travels exactly the shifts of e's binary expansion
#    and accumulates e mod P.  Log-step latency at ~P/2 rows per step
#    (the small-message regime).
#
#  After the last step row e on device d holds x_{d-e}[d], i.e. result
#  chunk c sits in row (d - c) mod P -- the final gather's table.

A2A_KINDS = ("direct", "bruck")


@lru_cache(maxsize=None)
def compile_a2a_plan(P: int, kind: str = "direct") -> ExecPlan:
    """Lower a P-process all-to-all into cached ExecPlan tables.

    >>> plan = compile_a2a_plan(8, "bruck")
    >>> plan.n_steps, [st.n_tx for st in plan.steps]
    (3, [4, 4, 4])
    >>> compile_a2a_plan(8, "direct").n_steps
    7
    """
    if kind not in A2A_KINDS:
        raise ValueError(f"unknown all-to-all kind {kind!r} "
                         f"(expected one of {A2A_KINDS})")
    if P < 1:
        raise ShapeError("all-to-all needs P >= 1", expected=">= 1",
                         actual=P)
    d = np.arange(P)
    init_rows = (d[None, :] + np.arange(P)[:, None]) % P     # [e, d]
    final_rows = (d[None, :] - np.arange(P)[:, None]) % P    # [c, d]
    none = _frozen([])
    steps: List[ExecStep] = []

    def step(shift: int, rows: List[int]) -> ExecStep:
        return ExecStep(
            shift=shift,
            perm=tuple((int(x), int((x + shift) % P)) for x in range(P)),
            tx_slots=_frozen(rows), add_src=none, add_dst=none,
            add_arr=none, recv_slots=_frozen(rows),
            recv_arr=_frozen(list(range(len(rows)))))

    if kind == "direct":
        for e in range(1, P):
            steps.append(step(e, [e]))
    else:
        n = 1
        while n < P:
            rows = [e for e in range(1, P) if e & n]
            steps.append(step(n % P, rows))
            n <<= 1
    return ExecPlan(P=P, kind=f"all_to_all_{kind}", n_rows0=P, n_slots=P,
                    steps=tuple(steps), init_rows=_frozen(init_rows),
                    final_rows=_frozen(final_rows))


def simulate_a2a(vectors: List[np.ndarray],
                 kind: str = "direct") -> List[np.ndarray]:
    """Numpy oracle for the schedule-driven all-to-all: replay the plan
    tables over P explicit processes.  Result ``d`` is the concatenation
    of chunk ``d`` of every process's vector -- exactly
    ``lax.all_to_all`` on the equally-split flat buffers.

    >>> vecs = [np.arange(3) + 10 * d for d in range(3)]
    >>> [v.tolist() for v in simulate_a2a(vecs, "bruck")]
    [[0, 10, 20], [1, 11, 21], [2, 12, 22]]
    """
    P = len(vectors)
    m = vectors[0].shape[0]
    if m % P:
        raise ShapeError("all-to-all needs P | m",
                         expected=f"multiple of {P}", actual=m)
    plan = compile_a2a_plan(P, kind)
    u = m // P
    state = []
    for d in range(P):
        ch = vectors[d].reshape(P, u)
        state.append(ch[plan.init_rows[:, d]].copy())
    for sp in plan.steps:
        arr = [None] * P
        for src, dst in sp.perm:
            arr[dst] = state[src][sp.tx_slots].copy()
        for d in range(P):
            state[d][sp.recv_slots] = arr[d][sp.recv_arr]
    return [np.concatenate([state[d][plan.final_rows[c, d]]
                            for c in range(P)]) for d in range(P)]
