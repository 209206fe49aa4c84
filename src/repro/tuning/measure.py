"""Measured autotuning: time the candidate grid on the running backend.

The analytic model ranks schedules; this module *times* them.  For each
message-size bucket it jits every candidate ``(kind, r, n_buckets)`` as
the same shard_map ppermute program the real executor runs, verifies it
against ``lax.psum`` once, and times all candidates interleaved
round-robin (best-of-``reps``), so machine-load drift hits every
candidate equally -- the timing discipline of
``benchmarks/executor_worker.py``.  Results are recorded into the
persistent :class:`~repro.tuning.cache.TuningCache` under the running
backend's fingerprint and summarized into a JSON payload for
``results/tuning.json``.

The size grid includes *ragged* entries (element counts coprime with the
device count) so the table measures the executor's exact-split path on
true moved bytes; the analytic pick reported next to each winner prices
those sizes with the ragged cost model
(:func:`repro.core.cost_model.ragged_schedule_cost`).

Requires more than one jax device in-process; the CLI driver
(``benchmarks/run.py tune``) spawns a worker with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for that.
"""

from __future__ import annotations

import os
import time
from dataclasses import asdict
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.autotune import choose
from repro.core.cost_model import HOST_CPU, Fabric
from repro.core.monoid import MONOIDS
from repro.core.schedule import (build_dual_root, build_generalized,
                                 build_ring, build_traff_rounds, max_r)
from repro.obs import trace as obs_trace
from repro.obs.log import data, get_logger
from repro.obs.skew import device_arrival_probe

from .cache import Measurement, TuningCache, current_fingerprint
from .policy import (NOISE_THRESHOLD, SKEW_THRESHOLD_US, skewed_cells,
                     unstable_cells)

_log = get_logger("repro.tuning.measure")

Candidate = Tuple[str, int, int]  # (kind, r, n_buckets)

# combine operators the grid times; each op gets its own measurements
# (policy lookups never answer across operators).  max covers the whole
# non-sum family: min/mean run the identical executor with one
# comparison/divide swapped, so their wallclock is max's.
GRID_OPS: Tuple[str, ...] = ("sum", "max")

# candidates whose per-bucket chunk would shrink below this are skipped:
# dispatch overhead dominates and the measurement is pure noise
MIN_BUCKET_CHUNK_BYTES = 8 * 1024

# "+36B" entries are deliberately *ragged*: 36 extra bytes = 9 extra f32
# elements, so the element count is coprime with the 8-device grid and
# the executor runs the balanced exact split -- these datapoints let the
# measured table pick different winners for badly-divisible sizes than
# the model's uniform-chunk ranking would.
SMOKE_SIZES: Sequence[Tuple[str, int]] = (
    ("64KiB", 64 << 10),
    ("64KiB+36B", (64 << 10) + 36),
    ("256KiB", 256 << 10),
)
FULL_SIZES: Sequence[Tuple[str, int]] = (
    ("64KiB", 64 << 10),
    ("64KiB+36B", (64 << 10) + 36),
    ("256KiB", 256 << 10),
    ("1MiB", 1 << 20),
    ("1MiB+36B", (1 << 20) + 36),
    ("4MiB", 4 << 20),
)


def candidate_grid(P: int, nbytes: int, *, smoke: bool = False) -> List[Candidate]:
    """Schedule kind x r x n_buckets grid for one message size.

    >>> candidate_grid(8, 1 << 20)[:3]
    [('generalized', 0, 1), ('generalized', 0, 2), ('generalized', 0, 4)]
    >>> [c for c in candidate_grid(8, 1 << 20) if c[0] == "ring"]
    [('ring', 0, 1), ('ring', 0, 2), ('ring', 0, 4)]
    >>> sorted({c[0] for c in candidate_grid(8, 1 << 20)})
    ['dual_root', 'generalized', 'ring', 'traff_rounds']
    """
    buckets = (1, 2) if smoke else (1, 2, 4)
    kinds: List[Tuple[str, int]] = [("generalized", r) for r in range(max_r(P) + 1)]
    kinds.append(("traff_rounds", 0))
    kinds.append(("dual_root", 0))
    kinds.append(("ring", 0))
    grid = []
    for kind, r in kinds:
        for b in buckets:
            if b > 1 and nbytes / P / b < MIN_BUCKET_CHUNK_BYTES:
                continue
            grid.append((kind, r, b))
    return grid


def _schedule(kind: str, P: int, r: int):
    if kind == "ring":
        return build_ring(P)
    if kind == "traff_rounds":
        return build_traff_rounds(P)
    if kind == "dual_root":
        return build_dual_root(P)
    return build_generalized(P, r)


def _bench_interleaved(variants: Dict[str, object], x, iters: int, reps: int):
    """(best, per_rep) round-robin timings: ``best[name]`` is the minimum
    per-call microseconds over reps, ``per_rep[name]`` every rep's own
    figure in rep order (the spread feeds ``Measurement.noise``)."""
    import jax

    for fn in variants.values():
        jax.block_until_ready(fn(x))  # warm-up / compile
    per_rep: Dict[object, List[float]] = {name: [] for name in variants}
    for _ in range(reps):
        for name, fn in variants.items():
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x)
            jax.block_until_ready(out)
            per_rep[name].append((time.perf_counter() - t0) / iters * 1e6)
    best = {name: min(ts) for name, ts in per_rep.items()}
    return best, per_rep


def _noise(reps_us: Sequence[float]) -> float:
    """Relative rep-to-rep spread ``(max - min) / min`` of one cell."""
    if not reps_us:
        return 0.0
    lo = min(reps_us)
    return (max(reps_us) - lo) / lo if lo > 0 else 0.0


def run_tuning(
    *,
    smoke: bool = False,
    out: Optional[str] = None,
    cache_path: Optional[os.PathLike] = None,
    model_fabric: Fabric = HOST_CPU,
    iters: Optional[int] = None,
    reps: int = 3,
) -> dict:
    """Measure the grid, update the persistent cache, return the summary.

    ``out`` additionally writes the summary JSON (``results/tuning.json``).
    ``model_fabric`` is only used to report the analytic model's pick next
    to the measured winner -- measurements never depend on it.
    """
    import json

    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P_

    from jax import shard_map
    from repro.core.allreduce import allreduce_flat

    n = len(jax.devices())
    if n < 2:
        raise RuntimeError(
            "measured tuning needs >= 2 devices; launch via "
            "'python benchmarks/run.py tune' which forces 8 host devices"
        )
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(0)
    sizes = SMOKE_SIZES if smoke else FULL_SIZES
    iters = iters if iters is not None else (2 if smoke else 5)

    def jit_collective(fn):
        return jax.jit(
            shard_map(
                lambda v: fn(v[0])[None],
                mesh=mesh,
                in_specs=P_("data", None),
                out_specs=P_("data", None),
            )
        )

    fp = current_fingerprint()
    cache = TuningCache.load(cache_path)
    tracer = obs_trace.get_tracer()
    results = []
    refs = {
        "sum": lambda v: lax.psum(v, "data"),
        "max": lambda v: lax.pmax(v, "data"),
        "min": lambda v: lax.pmin(v, "data"),
    }
    for label, nbytes in sizes:
        m = nbytes // 4
        x = rng.standard_normal((n, m)).astype(np.float32)
        grid = candidate_grid(n, nbytes, smoke=smoke)
        # arrival-skew telemetry for this message size: how unevenly the
        # devices come ready for one identical dispatch (persisted per
        # measurement so PAP-aware scheduling has real data to start from:
        # the full per-device profile feeds policy.arrival_deltas and from
        # there the skew-aware path of autotune.choose)
        try:
            arr = device_arrival_probe(nbytes=nbytes)
            skew_us = arr.skew_us
            deltas_us = arr.deltas_us if len(arr.deltas_us) == n else None
        except Exception as e:  # never let telemetry sink a tuning run
            _log.warn("arrival_probe_failed", size=label, error=repr(e))
            skew_us = None
            deltas_us = None
        tracer.counter("arrival_skew_us", skew_us if skew_us is not None
                       else 0.0, cat="tuning")
        for op in GRID_OPS:
            monoid = MONOIDS[op]
            variants = {}
            for kind, r, b in grid:
                sched = _schedule(kind, n, r)
                variants[(kind, r, b)] = jit_collective(
                    lambda v, s=sched, nb=b, mo=monoid: allreduce_flat(
                        v, "data", s, n_buckets=nb, combine=mo
                    )
                )
            with tracer.span("tune.verify", cat="tuning", size=label, op=op,
                             n_candidates=len(variants)):
                ref = np.asarray(jit_collective(refs[op])(x))[0]
                for name, fn in variants.items():
                    np.testing.assert_allclose(
                        np.asarray(fn(x))[0],
                        ref,
                        rtol=1e-5,
                        atol=1e-5,
                        err_msg=f"candidate {op}:{name} disagrees with lax.p{op}",
                    )
            with tracer.span("tune.bench", cat="tuning", size=label, op=op,
                             iters=iters, reps=reps) as sp:
                timed, per_rep = _bench_interleaved(variants, x, iters, reps)
                sp.set(best_us=min(timed.values()))
            meas_rows = []
            for (kind, r, b), us in sorted(timed.items(), key=lambda kv: kv[1]):
                reps_us = tuple(round(t, 3) for t in per_rep[(kind, r, b)])
                noise = round(_noise(reps_us), 4)
                meas = Measurement(
                    P=n, nbytes=nbytes, kind=kind, r=r, n_buckets=b, us=us,
                    itemsize=4,  # the grid times f32 buffers
                    op=op,
                    reps_us=reps_us,
                    noise=noise,
                    skew_us=skew_us,
                    deltas_us=deltas_us,
                )
                cache.record(fp, meas)
                meas_rows.append(asdict(meas))
                data(f"tune,{label},{op},{kind},r={r},b={b},{us:.1f}")
                if noise > NOISE_THRESHOLD:
                    _log.warn("noisy_cell", size=label, op=op, kind=kind,
                              r=r, n_buckets=b, noise=noise)
            win = meas_rows[0]
            # benchmarks run f32 buffers: raggedness is per-element
            # (itemsize=4); candidates are priced with the op's gamma
            model = choose(
                n, nbytes, model_fabric, tune=False, itemsize=4, monoid=monoid
            )
            results.append(
                {
                    "label": label,
                    "bytes": nbytes,
                    "op": op,
                    "measured_winner": {
                        k: win[k] for k in ("kind", "r", "n_buckets", "us")
                    },
                    "model_pick": {
                        "kind": model.kind,
                        "r": model.r,
                        "n_buckets": model.n_buckets,
                        "model_us": round(model.cost * 1e6, 1),
                    },
                    "measurements": meas_rows,
                }
            )
    saved = cache.save(cache_path)
    all_meas = [Measurement.from_dict(m) for r_ in results
                for m in r_["measurements"]]
    unstable = unstable_cells(all_meas)
    if unstable:
        _log.warn("unstable_cells", count=len(unstable),
                  threshold=NOISE_THRESHOLD)
    skewed = skewed_cells(all_meas)
    if skewed:
        _log.warn("skewed_cells", count=len(skewed),
                  threshold_us=SKEW_THRESHOLD_US)
    payload = {
        "fingerprint": asdict(fp),
        "mode": "smoke" if smoke else "full",
        "model_fabric": model_fabric.name,
        "cache_path": str(saved),
        "noise_threshold": NOISE_THRESHOLD,
        "unstable_cells": unstable,
        "skew_threshold_us": SKEW_THRESHOLD_US,
        "skewed_cells": skewed,
        "notes": (
            "best-of-reps interleaved wallclock per call; candidates are the "
            "executor's own jitted shard_map programs, verified against "
            "lax.psum before timing. The cache keeps one figure per "
            "(fingerprint, P, size, kind, r, n_buckets) grid point."
        ),
        "results": results,
    }
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
        data(f"tune,WROTE,{out}")
    return payload
