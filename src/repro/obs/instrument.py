"""Blocking per-tick traced replay of a compiled collective schedule.

:func:`repro.core.execplan.execute` stages the whole replay inside one
``shard_map``/jit trace, so host-side wall clocks can only see the fused
program's total time -- never the per-tick send/combine breakdown the
predicted-vs-measured validation (:mod:`repro.obs.validate`) needs.
This module is the opt-in measurement mode: it replays the *same*
:class:`~repro.core.execplan.ExecPlan` tables over the *same*
:func:`~repro.core.execplan.tick_structure` timeline, but drives the
tick loop from the host, with each tick split into two separately
jitted ``shard_map`` phases

* **send**   -- gather every active bucket's ``tx_slots`` rows and issue
  its ``ppermute``;
* **combine** -- apply the tick's pairwise combines and land received
  rows in their freed slots;

and a ``jax.block_until_ready`` fence after each phase.  The fences are
the point: they trade the fused program's overlap away for an exact
per-phase timeline, which is why this is a *measurement* mode and never
the production path (the production path keeps its <2% disabled-tracing
overhead; see ``trace_off_overhead`` in the executor benchmark).

Each rep replays all ticks from the same initial buffer; the rep with
the smallest total is kept (host noise only ever adds time).  The
replay verifies its result against a numpy reduction of the inputs, so
a timeline is never reported for a wrong answer.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from . import trace as obs_trace


@dataclass(frozen=True)
class TickRecord:
    """Measured timing of one executor tick of the blocking replay."""

    tick: int
    steps: Tuple[Tuple[int, int], ...]   # active (bucket, step) pairs
    comm_us: float                       # send phase (gather + ppermute)
    combine_us: float                    # combine phase (adds + recv lands)

    @property
    def total_us(self) -> float:
        return self.comm_us + self.combine_us

    def to_dict(self) -> dict:
        return {"tick": self.tick,
                "steps": [list(p) for p in self.steps],
                "comm_us": self.comm_us, "combine_us": self.combine_us,
                "total_us": self.total_us}


@dataclass(frozen=True)
class ReplayReport:
    """One traced replay: identity, per-tick timeline, correctness."""

    kind: str
    r: int
    P: int
    m: int                               # message elements
    itemsize: int
    n_buckets: int
    ticks: Tuple[TickRecord, ...]
    reps: int
    verified: bool
    max_abs_err: float
    result: Optional[np.ndarray] = field(default=None, repr=False,
                                         compare=False)

    @property
    def nbytes(self) -> int:
        return self.m * self.itemsize

    @property
    def total_us(self) -> float:
        return sum(t.total_us for t in self.ticks)

    def measured_tick_us(self) -> List[float]:
        return [t.total_us for t in self.ticks]

    def to_dict(self) -> dict:
        return {"kind": self.kind, "r": self.r, "P": self.P, "m": self.m,
                "itemsize": self.itemsize, "nbytes": self.nbytes,
                "n_buckets": self.n_buckets, "reps": self.reps,
                "verified": self.verified,
                "max_abs_err": self.max_abs_err,
                "total_us": self.total_us,
                "ticks": [t.to_dict() for t in self.ticks]}


# ---------------------------------------------------------------------------
#  state preparation (numpy mirror of the executor's bucket split)
# ---------------------------------------------------------------------------

def _initial_state(plan, vectors, n_buckets):
    """(P, B, n_slots, ub) initial buffer + (u, ub, chunk_sizes, m).

    Mirrors :func:`repro.core.execplan.simulate_plan`'s init: device d's
    input is split into the balanced ragged chunk buffer and placed by
    ``plan.init_rows[:, d]``; each slot row is then cut into
    ``n_buckets`` equal column slices (zero-padded to ``ub * B``)."""
    from repro.core.execplan import _np_chunks
    from repro.core.schedule import ragged_sizes

    P = plan.P
    m = int(vectors[0].shape[0])
    chunk_sizes = ragged_sizes(m, P)
    u = max(-(-m // P), 1)
    B = max(1, min(int(n_buckets), u))
    ub = -(-u // B)
    state = np.zeros((P, B, plan.n_slots, ub), vectors[0].dtype)
    for d in range(P):
        ch = _np_chunks(np.asarray(vectors[d]), P)
        init = ch[plan.init_rows[:, d]]                  # (R0, u)
        padded = np.zeros((plan.n_rows0, ub * B), init.dtype)
        padded[:, :u] = init
        state[d, :, :plan.n_rows0, :] = \
            padded.reshape(plan.n_rows0, B, ub).transpose(1, 0, 2)
    return state, (u, ub, chunk_sizes, m)


def _extract_results(plan, state, geom):
    """Per-device exact reduced vectors from the final (P,B,S,ub) state."""
    u, ub, chunk_sizes, m = geom
    P = plan.P
    out = []
    for d in range(P):
        full = np.concatenate(list(state[d]), axis=1)[:, :u]  # (n_slots, u)
        cols = plan.final_rows[:, d]
        out.append(np.concatenate(
            [full[cols[c]][:chunk_sizes[c]] for c in range(P)]))
    return out


# ---------------------------------------------------------------------------
#  per-tick jitted phase functions
# ---------------------------------------------------------------------------

def _tick_phase_fns(plan, active, axis_name, mesh):
    """(send_fn, combine_fn) for one tick's active (bucket, step) pairs.

    ``send_fn(buf) -> rx_tuple`` stages every active bucket's gather +
    ``ppermute`` (every live step transmits, so each active pair yields
    one rx array); ``combine_fn(buf, rx_tuple) -> buf`` applies the
    tick's combines and lands received rows.  Both are ``shard_map``
    over the leading device axis of the (P, B, n_slots, ub) buffer.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    spec = P(axis_name, None, None, None)
    rx_spec = tuple(P(axis_name, None, None) for _ in active)

    def send(buf):
        b = buf[0]
        outs = []
        for j, s in active:
            sp = plan.steps[s]
            tx = b[j][jnp.asarray(sp.tx_slots)]
            outs.append(lax.ppermute(tx, axis_name, perm=sp.perm)[None])
        return tuple(outs)

    def combine(buf, rxs):
        b = buf[0]
        for (j, s), rx3 in zip(active, rxs):
            sp = plan.steps[s]
            rx = rx3[0]
            if sp.n_adds:
                sums = b[j, jnp.asarray(sp.add_src)] + \
                    rx[jnp.asarray(sp.add_arr)]
                b = b.at[j, jnp.asarray(sp.add_dst)].set(sums)
            if len(sp.recv_slots):
                b = b.at[j, jnp.asarray(sp.recv_slots)].set(
                    rx[jnp.asarray(sp.recv_arr)])
        return b[None]

    send_fn = jax.jit(jax.shard_map(
        send, mesh=mesh, in_specs=spec, out_specs=rx_spec))
    combine_fn = jax.jit(jax.shard_map(
        combine, mesh=mesh, in_specs=(spec, rx_spec), out_specs=spec))
    return send_fn, combine_fn


# ---------------------------------------------------------------------------
#  the traced replay
# ---------------------------------------------------------------------------

def traced_allreduce(sched, vectors, *, n_buckets: int = 1,
                     mesh=None, axis_name: str = "data",
                     reps: int = 3, tracer=None) -> ReplayReport:
    """Replay an allreduce schedule tick-by-tick with per-phase fences.

    ``vectors`` is one flat numpy array per device (the per-device
    inputs of the sum-allreduce).  Returns a :class:`ReplayReport` whose
    tick timeline is the best (minimum-total) of ``reps`` replays, with
    the result verified against ``np.add.reduce(vectors)``.

    When the given (or global) tracer is enabled, every rep emits
    nested ``replay > tick > send/combine`` spans plus per-tick
    ``tx_bytes`` / ``add_bytes`` counters, so the exported Chrome trace
    shows the same timeline the report tabulates.
    """
    import jax

    from repro.core.cost_model import HOST_CPU, ragged_tick_costs
    from repro.core.execplan import compile_plan, tick_structure

    if tracer is None:
        tracer = obs_trace.get_tracer()
    plan = compile_plan(sched)
    P = plan.P
    if mesh is None:
        mesh = jax.make_mesh((P,), (axis_name,))
    vectors = [np.asarray(v) for v in vectors]
    itemsize = int(vectors[0].dtype.itemsize)
    state0, geom = _initial_state(plan, vectors, n_buckets)
    u, ub, chunk_sizes, m = geom
    B = state0.shape[1]
    ticks = tick_structure(plan, B)
    # bytes moved/reduced per tick (fabric-independent fields only)
    tick_bytes = ragged_tick_costs(sched, m * itemsize, HOST_CPU, B,
                                   itemsize=itemsize)

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as Pspec
    sharding = NamedSharding(mesh, Pspec(axis_name, None, None, None))
    buf0 = jax.device_put(state0, sharding)

    fns = [_tick_phase_fns(plan, active, axis_name, mesh)
           for active in ticks]

    def replay(record):
        buf = buf0
        timings = []
        import time
        for t, (active, (send_fn, combine_fn)) in enumerate(zip(ticks, fns)):
            with tracer.span("tick", cat="replay", tick=t,
                             steps=[list(p) for p in active]) if record \
                    else obs_trace._NULL_SPAN:
                t0 = time.perf_counter_ns()
                with tracer.span("send", cat="replay") if record \
                        else obs_trace._NULL_SPAN:
                    rx = jax.block_until_ready(send_fn(buf))
                t1 = time.perf_counter_ns()
                with tracer.span("combine", cat="replay") if record \
                        else obs_trace._NULL_SPAN:
                    buf = jax.block_until_ready(combine_fn(buf, rx))
                t2 = time.perf_counter_ns()
            if record:
                tracer.counter("tx_bytes", tick_bytes[t]["tx_bytes"])
                tracer.counter("add_bytes", tick_bytes[t]["add_bytes"])
            timings.append(((t1 - t0) / 1e3, (t2 - t1) / 1e3))
        return buf, timings

    with tracer.span("replay", cat="replay", kind=plan.kind, r=sched.r,
                     P=P, m=m, n_buckets=B, n_ticks=len(ticks),
                     reps=reps):
        final_buf, _ = replay(record=False)           # warmup / compile
        best = None
        for _ in range(max(int(reps), 1)):
            final_buf, timings = replay(record=True)
            total = sum(a + b for a, b in timings)
            if best is None or total < best[0]:
                best = (total, timings)

    results = _extract_results(plan, np.asarray(final_buf), geom)
    ref = np.add.reduce(np.stack(vectors), axis=0)
    err = max(float(np.max(np.abs(res - ref))) if m else 0.0
              for res in results)
    tol = 1e-4 * max(1.0, float(np.max(np.abs(ref))) if m else 1.0)
    records = tuple(
        TickRecord(tick=t, steps=tuple(tuple(p) for p in active),
                   comm_us=round(comm, 3), combine_us=round(comb, 3))
        for t, (active, (comm, comb)) in enumerate(zip(ticks, best[1])))
    return ReplayReport(kind=plan.kind, r=sched.r, P=P, m=m,
                        itemsize=itemsize, n_buckets=B, ticks=records,
                        reps=reps, verified=bool(err <= tol),
                        max_abs_err=err, result=results[0])
