"""Overlap benchmark: exposed vs hidden gradient-sync communication.

Runs on 8 forced host devices (launched by ``benchmarks/run.py executor
--overlap``).  Three *complete train steps* over the same comm-heavy
model differ only in ``ParallelConfig.overlap_dispatch``:

* ``skip``     -- no DP gradient sync at all: the pure compute baseline
  ``t_compute`` (forward + backward + optimizer, zero grad comm);
* ``post``     -- the reverse-layer buckets synced after the backward
  completes: every byte of gradient communication is serialized behind
  the compute, so ``t_post - t_compute`` measures the total comm cost;
* ``backward`` -- the same buckets dispatched from inside the backward
  pass via the ``custom_vjp`` markers (``attach_overlap_sync``):
  ``t_overlap - t_compute`` is the *exposed* comm -- what the dispatch
  interleaving failed to hide behind backward compute.

The three arms run identical collectives on identical buckets (post and
backward are bit-identical by construction, see
``tests/_multidevice_worker.py overlap``), so the derived ratios isolate
dispatch timing:

* ``speedup_overlap = t_post / t_overlap`` -- step-time win of moving
  the dispatches into the backward (gated as a floor);
* ``exposed_ratio = (t_overlap - t_compute) / (t_post - t_compute)``
  -- the fraction of comm left exposed (gated lower-is-better: 1.0
  means nothing hid, 0.0 means everything did).

XLA CPU executes collectives synchronously on the compute stream, so
the hidden fraction here comes from instruction-level interleaving, not
true async comm -- the ratios are still dispatch-structure-sensitive
(a regression that re-serializes every bucket behind the backward moves
both), which is what the gate guards.  The model-error overlay
(``overlap_fit_*``) prices the same buckets with the roofline
``exposed = max(0, comm - hidden_budget)`` of
``repro.core.cost_model.overlap_tick_costs`` under the HOST_CPU fabric,
mirroring the executor bench's informational fit ratio.

Prints ``overlap,<label>,<arm>,<us_per_step>`` rows and writes the JSON
summary (``results/overlap.json``) to ``--out``.
"""
import argparse
import json
import os
import sys
import time

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core.autotune import choose  # noqa: E402
from repro.core.cost_model import HOST_CPU  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.model import init_params, param_shapes  # noqa: E402
from repro.obs.log import data, get_logger  # noqa: E402
from repro.parallel.api import ParallelConfig  # noqa: E402
from repro.train.optimizer import OptConfig, init_opt_state  # noqa: E402
from repro.train.step import make_train_step, overlap_buckets_for  # noqa: E402

log = get_logger("benchmarks.overlap")

# comm-heavy, compute-light: wide embeddings + narrow blocks keep the
# gradient bytes large relative to the FLOPs of a short batch, so the
# exposed/hidden split is measured where it matters
CONFIGS = {
    "tiny": ModelConfig(name="ovl-tiny", family="dense", n_layers=2,
                        d_model=128, n_heads=4, n_kv_heads=4, d_ff=256,
                        vocab=1024, head_dim=32, act="swiglu"),
    "base": ModelConfig(name="ovl-base", family="dense", n_layers=4,
                        d_model=256, n_heads=8, n_kv_heads=8, d_ff=512,
                        vocab=2048, head_dim=32, act="swiglu"),
}
BUCKET_BYTES = 1 << 20          # ~1 MiB reverse-layer buckets
BATCH, SEQ = 8, 16


def make_arm(cfg, mesh, dispatch):
    """One jitted train step + its state, differing only in dispatch."""
    pc = ParallelConfig(dp=8, tp=1, param_mode="dp",
                        overlap_bucket_bytes=BUCKET_BYTES,
                        overlap_dispatch=dispatch)
    oc = OptConfig(lr=1e-3)
    bundle = make_train_step(cfg, pc, mesh, oc, donate=False)
    params, _ = init_params(cfg, pc, jax.random.PRNGKey(0))
    opt = init_opt_state(params, pc=pc, specs=bundle.specs)
    tok = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ),
                             0, cfg.vocab)
    lab = jax.random.randint(jax.random.PRNGKey(2), (BATCH, SEQ),
                             0, cfg.vocab)
    batch = {"tokens": tok, "labels": lab}

    def step():
        return bundle.train_step(params, opt, batch)

    return step, pc, bundle


def bench_interleaved(arms, samples):
    """Time the arms round-robin, one fenced step per sample, and keep
    each arm's per-step MINIMUM.  The min is the noise-floor estimator:
    XLA CPU's collective rendezvous occasionally stalls for seconds (a
    logged false-positive "thread stuck" watchdog), and a single stall
    would poison any mean- or best-of-window figure, while the min only
    needs one clean sample per arm.  Round-robin keeps machine-load
    drift symmetric across dispatch modes."""
    for step in arms.values():              # compile + rendezvous warm-up
        jax.block_until_ready(step())
        jax.block_until_ready(step())
    best = {name: float("inf") for name in arms}
    for _ in range(samples):
        for name, step in arms.items():
            t0 = time.perf_counter()
            jax.block_until_ready(step())
            best[name] = min(best[name],
                             (time.perf_counter() - t0) * 1e6)
    return best


def bucket_model_costs_us(cfg, pc):
    """Per-bucket modeled comm cost (HOST_CPU) of the bench's buckets."""
    shapes, _ = param_shapes(cfg, pc)
    buckets = overlap_buckets_for(shapes, pc)
    leaves = jax.tree.leaves(shapes)
    costs = []
    for bucket in buckets:
        nbytes = sum(int(leaves[i].size) * jnp.dtype(leaves[i].dtype).itemsize
                     for i in bucket)
        ch = choose(pc.dp, nbytes, HOST_CPU, tune=False, itemsize=4)
        costs.append(ch.cost * 1e6)
    return costs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n, 1), ("data", "model"))
    labels = ["tiny"] if args.smoke else ["tiny", "base"]
    samples = 6 if args.smoke else 12

    results = []
    for label in labels:
        cfg = CONFIGS[label]
        arms, pcs = {}, {}
        for dispatch in ("skip", "post", "backward"):
            step, pc, _ = make_arm(cfg, mesh, dispatch)
            arms[dispatch] = step
            pcs[dispatch] = pc
        timed = bench_interleaved(arms, samples)
        row = {"label": label, "bench": "overlap",
               "bucket_bytes": BUCKET_BYTES, "P": n}
        for name, us in timed.items():
            row[f"{name}_us"] = round(us, 1)
            data(f"overlap,{label},{name},{us:.1f}")
        eps = 1.0  # us; floors the denominators against timer jitter
        t_compute = timed["skip"]
        comm_us = max(timed["post"] - t_compute, eps)
        exposed_us = max(timed["backward"] - t_compute, 0.0)
        hidden_us = max(timed["post"] - timed["backward"], 0.0)
        row["speedup_overlap"] = round(timed["post"] / timed["backward"], 3)
        row["exposed_ratio"] = round(max(exposed_us, eps) / comm_us, 3)
        row["hidden_us"] = round(hidden_us, 1)
        row["exposed_us"] = round(exposed_us, 1)
        row["comm_us"] = round(comm_us, 1)
        # model-error overlay (informational, like the executor bench's
        # fit ratio): the roofline prices the same buckets under
        # HOST_CPU -- comm fit compares total modeled comm against the
        # serialized measurement, exposed fit applies the measured
        # hidden budget to the modeled comm
        # (exposed_model = max(0, comm_model - hidden))
        pc = pcs["backward"]
        bucket_costs = bucket_model_costs_us(cfg, pc)
        model_comm_us = sum(bucket_costs)
        model_exposed_us = max(model_comm_us - hidden_us, 0.0)
        row["n_buckets"] = len(bucket_costs)
        row["model_comm_us"] = round(model_comm_us, 1)
        row["model_exposed_us"] = round(model_exposed_us, 1)
        row["overlap_fit_comm"] = round(comm_us / model_comm_us, 3)
        # meaningless when the model predicts full hiding (exposed 0)
        row["overlap_fit_exposed"] = (
            None if model_exposed_us <= 0.0
            else round(max(exposed_us, eps) / model_exposed_us, 3))
        data(f"overlap,{label},exposed_ratio,{row['exposed_ratio']:.3f}")
        data(f"overlap,{label},speedup_overlap,"
             f"{row['speedup_overlap']:.3f}")
        results.append(row)
        log.info("overlap_row", label=label,
                 speedup=row["speedup_overlap"],
                 exposed_ratio=row["exposed_ratio"],
                 fit_comm=row["overlap_fit_comm"])

    # executor-bench-style informational overlay: geomean fabric
    # miscalibration of the comm model against the serialized (post -
    # skip) measurement; large on CPU because the step-level dispatch
    # overheads (shard_map entry, per-bucket jit regions) are not part
    # of the per-collective alpha-beta-gamma fabric
    fits = [r["overlap_fit_comm"] for r in results
            if r["overlap_fit_comm"] > 0]
    geo = (float(np.exp(np.mean(np.log(fits)))) if fits else None)
    payload = {
        "P": n, "platform": jax.default_backend(),
        "mode": "smoke" if args.smoke else "full",
        "autotune_fabric": HOST_CPU.name,
        "model_error_geomean_ratio":
            None if geo is None else round(geo, 3),
        "notes": ("Three full train steps differ only in "
                  "overlap_dispatch: skip (compute baseline), post "
                  "(bucketed sync after backward), backward (custom_vjp "
                  "markers dispatch each bucket inside the backward). "
                  "XLA CPU runs collectives synchronously, so hidden "
                  "time comes from instruction interleaving rather than "
                  "async comm; the gated ratios (speedup_overlap floor, "
                  "exposed_ratio ceiling) are dispatch-structure "
                  "sensitive either way.  overlap_fit_* are the "
                  "informational roofline-model overlays; their "
                  "geomean sits well below the ~103x fabric "
                  "miscalibration the executor trace bench commits "
                  "for host-cpu (results/model_error_smoke.md), i.e. "
                  "within the existing fit tolerance."),
        "results": results,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    data(f"overlap,WROTE,{args.out}")


if __name__ == "__main__":
    main()
