"""Training: the data-parallel step of ``make_train_step``, one replica
per chip, gradients synchronised by the library's own ExecPlan
allreduce (``sync_grads_dp`` -> ``dp_grad_allreduce``) with the
``ParallelConfig`` defaults.

Set-up builds one object, the compiled step with its parameters and
AdamW state, and drives it from the seed through its first
``CHECKED_STEPS`` steps with the window's own call and feed; the first
compiles.  The window then runs that same object on: each step's global
batch is made on the host and placed as the step takes it, and at most
one step is in flight ahead of the host.

End to end: ``train_tokens_per_s``, all tokens of all steps of the
window over the window's wall time (the last step waited for).

``correct``, against the plain reference run once the program's state
is freed, on the same seeded weights and batches:

* ``loss_gap`` -- worst relative gap of a checked step's loss;
* ``grad_norm_gap`` -- worst tensor's gap between the norms of the
  first gradient as AdamW got it (its first moment after one step over
  ``1 - b1``);
* ``update_norm_gap`` -- worst tensor's gap between the norms of the
  weights' change over the checked steps.

A norm's gap is measured against the larger of the reference's norm of
that tensor and of the median tensor.  Tensors whose reference gradient
is under a thousandth of the median tensor's are left out of both norm
checks: AdamW moves them by round-off alone.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

import flops
import harness as H
import registry
import weights as W

CHECKED_STEPS = 3
NEGLIGIBLE = 1e-3


def norm_gap(prog: dict, ref: dict, keep) -> float:
    """Worst tensor's |prog - ref| over max(ref, median ref)."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def build(ctx, labels_fault=None):
    """Set-up up to the first step: (step bundle, params, opt state,
    feed, seed key, tokens per step).  ``labels_fault`` rewrites each
    batch's labels (a planted fault of the control's checks)."""
    import jax
    from functools import partial

    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.step import make_train_step

    c, mix = ctx.cell.config, ctx.cell.traffic
    dp = mix["dp"]
    if dp != len(ctx.devices):
        raise ValueError(f"{ctx.cell.name}: dp={dp} on "
                         f"{len(ctx.devices)} chips")
    cfg = registry.program_config(c)
    mesh = make_mesh((dp, 1), ("data", "model"), devices=ctx.devices)
    pc = parallel_config_for(mesh, param_mode="dp")
    bundle = make_train_step(cfg, pc, mesh, OptConfig(**c["optimizer"]))
    key = H.seed_key(ctx.seed)
    params = registry.place_params(c, key, cfg, pc, bundle.in_shardings[0])
    opt = jax.jit(partial(init_opt_state, pc=pc, specs=bundle.specs),
                  out_shardings=bundle.in_shardings[1])(params)
    gen = H.traffic_kind(mix)

    def feed(step: int):
        tokens, labels = gen.batch(mix, ctx.seed, step, cfg.vocab)
        if labels_fault is not None:
            labels = labels_fault(labels)
        return jax.device_put({"tokens": tokens, "labels": labels},
                              bundle.in_shardings[2])

    return bundle, params, opt, feed, key, gen.rows(mix) * mix["seq_len"]


def checked_steps(c: dict, bundle, params, opt, feed, key):
    """Drive the step through the checked steps.  Returns (params, opt,
    readings): each step's loss, the per-tensor norms of the first
    gradient as AdamW got it, and of the weights' change."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: {k: jnp.linalg.norm(v.ravel())
                               for k, v in W.named(t).items()})
    b1 = c["optimizer"]["b1"]
    losses = []
    for k in range(CHECKED_STEPS):
        params, opt, m = bundle.train_step(params, opt, feed(k))
        losses.append(m["loss"])
        if k == 0:
            grad = {n: float(v) / (1 - b1) for n, v in norms(opt["m"]).items()}
    update = {n: float(v) for n, v in jax.jit(lambda p, k0: norms(
        jax.tree.map(jnp.subtract, p, W.program_tree(c, k0))))(
            params, key).items()}
    return params, opt, {"losses": [float(x) for x in losses],
                         "grad": grad, "update": update}


def reference_readings(ctx, key, precision: str = "float32") -> dict:
    """The plain reference's readings on the same weights and batches."""
    c, mix = ctx.cell.config, ctx.cell.traffic
    ref = H.load_module(f"{H.BENCH}/configs/{c['reference']}.py",
                        "bench_reference_" + c["reference"])
    gen = H.traffic_kind(mix)
    losses, grad, update = ref.train_reference(
        c, c["optimizer"], key,
        [gen.batch(mix, ctx.seed, k, c["vocab_size"])
         for k in range(CHECKED_STEPS)], precision=precision,
        devices=ctx.devices)
    return {"losses": losses, "grad": grad, "update": update}


def left_out(ref: dict):
    """Tensors whose reference gradient is negligible beside the median
    tensor's."""
    med = float(np.median(list(ref["grad"].values())))
    return sorted(k for k, v in ref["grad"].items() if v < NEGLIGIBLE * med)


def compare(got: dict, ref: dict) -> Dict[str, float]:
    keep = [k for k in ref["grad"] if k not in left_out(ref)]
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                            zip(got["losses"], ref["losses"])),
            "grad_norm_gap": norm_gap(got["grad"], ref["grad"], keep),
            "update_norm_gap": norm_gap(got["update"], ref["update"], keep)}


def run(ctx) -> H.Outcome:
    import jax

    c, mix = ctx.cell.config, ctx.cell.traffic
    clock, span, tracer = ctx.clock, ctx.tracer.span, ctx.tracer
    bundle, params, opt, feed, key, tokens_per_step = build(ctx)
    params, opt, got = checked_steps(c, bundle, params, opt, feed, key)
    setup_s = time.perf_counter() - ctx.t_process
    setup_compile_s, setup_hits = clock.secs, clock.hits

    seconds = ctx.seconds
    trace_span = (0.25 * seconds, 0.25 * seconds + min(10.0, 0.5 * seconds))
    tracing = traced = False
    pending, done_at = [], []
    step = CHECKED_STEPS
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if tracer.on and not traced and not tracing and now >= trace_span[0]:
            jax.block_until_ready(params)
            tracer.start()
            tracing = True
        if tracing and now >= trace_span[1]:
            jax.block_until_ready(params)
            tracer.stop()
            tracing, traced = False, True
        with span("bench.feed"):
            batch = feed(step)
        with span("bench.train_step"):
            params, opt, m = bundle.train_step(params, opt, batch)
        pending.append(m["loss"])
        step += 1
        if len(pending) >= 2:
            with span("bench.block"):
                pending[-2].block_until_ready()
            done_at.append(time.perf_counter())
    with span("bench.block"):
        jax.block_until_ready((params, opt))
    t_end = time.perf_counter()
    if tracing:
        tracer.stop()
    n_steps = step - CHECKED_STEPS
    window_losses = np.asarray([float(x) for x in pending])
    failed = int((~np.isfinite(window_losses)).sum())
    peak = H.memory_peak_bytes(ctx.devices)
    step_ms = 1e3 * np.diff(done_at)
    notes = [
        f"setup_s={setup_s!r} compile_s={setup_compile_s!r} "
        f"cache_hits={setup_hits}",
        f"window: steps={n_steps} tokens_per_step={tokens_per_step} "
        f"wall_s={t_end - t0!r} "
        f"compiles_in_window={clock.compiles_between(t0, t_end)}",
        "step_ms: " + (" ".join(
            f"{q}={v!r}" for q, v in zip(
                ("min", "p50", "p90", "max"),
                np.percentile(step_ms, [0, 50, 90, 100]).tolist()))
            if len(step_ms) else "none"),
        f"checked losses={got['losses']!r} last_window_loss="
        f"{float(window_losses[-1]) if n_steps else None!r}",
        f"memory_peak_bytes={peak}",
    ]
    del params, opt, bundle, pending
    H.free_device_memory()

    t_ref = time.perf_counter()
    ref = reference_readings(ctx, key)
    notes.append(f"check: reference losses={ref['losses']!r} "
                 f"left_out={left_out(ref)} "
                 f"ref_s={time.perf_counter() - t_ref!r}")
    gaps = compare(got, ref)
    facts = {"tokens_per_step": tokens_per_step,
             "flops_per_token": flops.train_flops_per_token(c, mix["seq_len"]),
             "chips": mix["dp"], "device_kind": ctx.devices[0].device_kind}
    return H.Outcome(
        end_to_end={"setup_s": setup_s,
                    "train_tokens_per_s":
                        n_steps * tokens_per_step / (t_end - t0)},
        attempted=n_steps, failed=failed,
        checks=H.judge(c, gaps),
        memory_peak_bytes=peak, facts=facts, notes=notes)
