"""Run the main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: kernels, serve, train
    python chip_smoke.py --chips 4    # four chips: collectives, dp=4 step

One chip (the default):

* kernels -- ``combine_n``, ``rmsnorm`` and ``flash_attention`` compiled
  by Mosaic at the main path's real sizes, each against its oracle in
  ``repro.kernels.ref``;
* serve -- ``Engine`` on granite-8b at its published widths, depth cut
  to 8 of 36 layers, serving 8 seeded requests; one request's tokens are
  scored under the model's plain full forward
  (``repro.serve.reference``);
* train -- ``make_train_step`` on granite-8b widths at 1 layer for 3
  steps: finite losses, parameters that move.

Four chips (``--chips 4``), and nothing else: every ExecPlan allreduce
family bit-exact against ``lax.psum`` on int32 at P = 4 and P = 3, a
ragged reduce-scatter / all-gather round trip, the Pallas combine inside
a ``jax.shard_map`` with its default checker, and a dp = 4 train step
against the same step at dp = 1 on the global batch.

Timings, memory and compile seconds go on lines that name the device.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero and prints no such line when JAX finds no
TPU, when the repository's ``src/`` is not beside it, or when any check
fails.  It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# serve traffic: prompts of 64..512 tokens, 32 greedy tokens each
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, NEW_TOKENS = 8, 64, 512, 32
SERVE_LAYERS, TRAIN_LAYERS = 8, 1
# The engine (paged cache, 128-token prefill chunks, batches of 4) and
# the reference (one full pass) run granite's bf16 activations at other
# shapes, so their activations round differently: one bf16 rounding moves
# a value by up to 2^-8 ~ 0.4%, and the roundings that differ compound
# through 8 layers and into the logits.  The logits are unit scale
# (normalised hidden state, 1/sqrt(d) head), so a near tie broken the
# other way costs a few hundredths to tenths of a logit, while the best
# of 49152 sits about 4 above a token the reference does not favour (the
# smoke prints that median).  0.5 leaves room for the rounding and is
# still 8x under a wrong pick.
SERVE_LOGIT_TOL = 0.5


class Failed(Exception):
    """A check of this smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


# ---------------------------------------------------------------- timing
class CompileClock:
    """Seconds XLA spent compiling (or reading its persistent cache), and
    persistent-cache hits, as JAX's own monitoring events report them."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.secs, self.hits


def peak_gib(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2**30


def report(dev, phase: str, **kv) -> None:
    """One line of results, prefixed by the device it ran on."""
    import jax
    fields = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{dev.platform} {dev.device_kind} x{jax.device_count()}] "
          f"{phase}: {fields}", flush=True)


# ---------------------------------------------------------------- kernels
def phase_kernels(dev, combine_elems=16 * 2**20, ragged_elems=2**20 + 37,
                  norm_rows=4096, seq=2048, cache=4096):
    """Mosaic kernels against their oracles at the main path's sizes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 8)

    # combine_n: a 64 MiB gradient bucket (16 Mi fp32 / 32 Mi bf16) and a
    # ragged tail.  Exact: each element is one fp32 add of two operands,
    # rounded once to the output dtype by kernel and oracle alike.
    for dtype, n in ((jnp.float32, combine_elems),
                     (jnp.bfloat16, 2 * combine_elems),
                     (jnp.float32, ragged_elems)):
        stack = jax.random.normal(ks[0], (2, n), jnp.float32).astype(dtype)
        got = ops.combine_many(stack)
        want = ref.combine_n_ref(stack)
        check(bool(jnp.array_equal(got, want)),
              f"combine_n {jnp.dtype(dtype).name} n={n} differs from oracle")
        report(dev, "kernel", name="combine_n",
               dtype=jnp.dtype(dtype).name, n=n, max_abs_err=0.0)

    # rmsnorm at granite's d_model.  fp32: the kernel sums 4096 squares
    # in another order and uses the VPU's rsqrt, each a few ulps; 1e-4 is
    # hundreds of ulps yet 40x under one bf16 rounding, so a silent bf16
    # path would still fail.  bf16: kernel and oracle round an fp32
    # result that differs in its last bits, so they may land one bf16 ulp
    # (2^-7 relative) apart.
    x = jax.random.normal(ks[1], (norm_rows, 4096), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (4096,), jnp.float32)
    for dtype, rtol in ((jnp.float32, 1e-4), (jnp.bfloat16, 2.0 ** -7)):
        got = ops.norm(x.astype(dtype), w.astype(dtype), impl="pallas")
        want = ref.rmsnorm_ref(x.astype(dtype), w.astype(dtype))
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        err = float(np.max(np.abs(got - want)))
        np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-6)
        report(dev, "kernel", name="rmsnorm", dtype=jnp.dtype(dtype).name,
               shape=f"{norm_rows}x4096", max_abs_err=f"{err:.3e}")

    # flash_attention at granite's heads (32 q / 8 kv, head_dim 128): a
    # causal prefill and one decode query against a full cache.  bf16
    # inputs; both sides round the softmax weights to bf16 for the MXU
    # (2^-9 relative) in their own order and round the output to bf16
    # (2^-9 again), so outputs of magnitude <= 1 agree within ~4e-3;
    # 1e-2 leaves room for the kernel's online-softmax rescaling.
    for name, sq, sk in (("prefill", seq, seq), ("decode", 1, cache)):
        q = jax.random.normal(ks[3], (1, 32, sq, 128), jnp.bfloat16)
        k = jax.random.normal(ks[4], (1, 8, sk, 128), jnp.bfloat16)
        v = jax.random.normal(ks[5], (1, 8, sk, 128), jnp.bfloat16)
        causal = sq > 1
        got = ops.attention(q, k, v, causal=causal, impl="pallas")
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        err = float(np.max(np.abs(got - want)))
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)
        report(dev, "kernel", name="flash_attention", shape=name,
               q=f"1x32x{sq}x128", kv=f"1x8x{sk}x128",
               max_abs_err=f"{err:.3e}")


# ---------------------------------------------------------------- serve
def phase_serve(dev, clock, cfg, seed, *, n_requests=N_REQUESTS,
                prompt_range=(PROMPT_MIN, PROMPT_MAX),
                new_tokens=NEW_TOKENS, max_len=1024, prefill_chunk=128,
                tol=SERVE_LOGIT_TOL):
    """Engine end to end; one request scored against the full forward."""
    import jax

    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.models.model import init_params
    from repro.serve.engine import Engine, Request
    from repro.serve.reference import served_token_margins

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    pc = parallel_config_for(mesh, param_mode="dp")
    t0 = time.perf_counter()
    params, _ = init_params(cfg, pc, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    report(dev, "serve.setup", params=n_params,
           init_s=f"{time.perf_counter() - t0:.2f}")

    rng = np.random.default_rng(seed)

    def requests():
        return [Request(prompt=rng.integers(0, cfg.vocab, int(n))
                        .astype(np.int32), max_new_tokens=new_tokens)
                for n in rng.integers(prompt_range[0], prompt_range[1] + 1,
                                      n_requests)]

    eng = Engine(cfg, pc, mesh, params, batch_slots=4, max_len=max_len,
                 prefill_chunk=prefill_chunk, block_size=16)
    for label in ("cold", "warm"):
        reqs = requests()
        c0, h0 = clock.mark()
        t0 = time.perf_counter()
        eng.generate(reqs)
        wall = time.perf_counter() - t0
        c1, h1 = clock.mark()
        toks = sum(len(r.out_tokens) for r in reqs)
        check(all(len(r.out_tokens) == new_tokens for r in reqs),
              "a request got the wrong number of tokens")
        ttft = sorted(r.ttft_us / 1e3 for r in reqs)
        report(dev, f"serve.{label}", requests=len(reqs), tokens=toks,
               wall_s=f"{wall:.3f}", tokens_per_s=f"{toks / wall:.1f}",
               ttft_ms_p50=f"{ttft[len(ttft) // 2]:.1f}",
               ttft_ms_max=f"{ttft[-1]:.1f}",
               compile_s=f"{c1 - c0:.2f}", cache_hits=h1 - h0)
    report(dev, "serve.memory", peak_gib=f"{peak_gib(dev):.2f}",
           ticks=eng.stats()["ticks"])

    # score the request with the longest prompt under the full forward
    r = max(reqs, key=lambda r: len(r.prompt))
    t0 = time.perf_counter()
    margins = served_token_margins(cfg, params, r.prompt, r.out_tokens)
    # controls: a random token at each step, and the engine's own tokens
    # scored after a different prompt of the same length
    rand = served_token_margins(cfg, params, r.prompt,
                                rng.integers(0, cfg.vocab, new_tokens))
    other = rng.integers(0, cfg.vocab, len(r.prompt)).astype(np.int32)
    wrong = served_token_margins(cfg, params, other, r.out_tokens)
    report(dev, "serve.check", prompt=len(r.prompt), tokens=len(margins),
           max_margin=f"{margins.max():.4f}", tol=tol,
           exact_picks=int((margins == 0).sum()),
           random_token_median_margin=f"{np.median(rand):.3f}",
           wrong_context_over_tol=int((wrong > tol).sum()),
           ref_s=f"{time.perf_counter() - t0:.2f}")
    check(margins.max() <= tol,
          f"served token scores {margins.max():.4f} below the reference's "
          f"best (tolerance {tol})")
    del eng, params
    gc.collect()


# ---------------------------------------------------------------- train
def _train_setup(cfg, mesh, oc, seed):
    import jax

    from repro.launch.mesh import parallel_config_for
    from repro.models.model import init_params
    from repro.train.optimizer import init_opt_state
    from repro.train.step import make_train_step

    pc = parallel_config_for(mesh, param_mode="dp")
    bundle = make_train_step(cfg, pc, mesh, oc)
    params, _ = init_params(cfg, pc, jax.random.PRNGKey(seed))
    params = jax.device_put(params, bundle.in_shardings[0])
    opt = jax.device_put(init_opt_state(params, pc, bundle.specs),
                         bundle.in_shardings[1])
    return bundle, params, opt


def _batch(cfg, bundle, step, seq, global_batch, seed):
    import jax

    from repro.data.pipeline import DataConfig, synth_batch
    dc = DataConfig(seq_len=seq, global_batch=global_batch, seed=seed)
    return jax.device_put(synth_batch(cfg, dc, step), bundle.in_shardings[2])


def phase_train(dev, clock, cfg, seed, *, steps=3, seq=512, global_batch=4):
    """make_train_step for a few steps: finite losses, moving params."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import OptConfig

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    oc = OptConfig(lr=1e-4, warmup_steps=1, total_steps=steps)
    bundle, params, opt = _train_setup(cfg, mesh, oc, seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    before = np.asarray(params["head"]["w"][:8, :8])
    losses = []
    for step in range(steps):
        batch = _batch(cfg, bundle, step, seq, global_batch, seed)
        c0, h0 = clock.mark()
        t0 = time.perf_counter()
        params, opt, metrics = bundle.train_step(params, opt, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        c1, h1 = clock.mark()
        losses.append(loss)
        report(dev, "train.step", step=step, loss=f"{loss:.5f}",
               step_s=f"{dt:.3f}", tokens=seq * global_batch,
               compile_s=f"{c1 - c0:.2f}", cache_hits=h1 - h0)
    finite = all(bool(jnp.isfinite(x).all())
                 for x in jax.tree.leaves(params))
    moved = float(np.abs(np.asarray(params["head"]["w"][:8, :8])
                         - before).max())
    report(dev, "train.memory", params=n_params,
           peak_gib=f"{peak_gib(dev):.2f}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(finite, "non-finite parameters after training")
    check(moved > 0, "parameters did not change")
    del params, opt, bundle
    gc.collect()


# ---------------------------------------------------------------- 4 chips
def phase_collectives(dev, clock, P: int, m: int):
    """Every allreduce family at P devices vs lax.psum, bit-exact on
    int32; the ragged RS -> AG round trip; and the fp32 Pallas combine
    under shard_map's default checker."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as Ps

    from repro.core.allreduce import (all_gather_flat, allreduce_flat,
                                      reduce_scatter_flat)
    from repro.core.schedule import (build_dual_root, build_generalized,
                                     build_ring, build_traff_rounds, max_r,
                                     ragged_sizes)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((P,), ("data",), devices=jax.devices()[:P])
    spec = Ps("data", None)
    sharding = NamedSharding(mesh, spec)

    def smap(fn):
        return jax.jit(jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                                     in_specs=spec, out_specs=spec))

    rng = np.random.default_rng(P)
    xi = jax.device_put(rng.integers(-1000, 1000, (P, m)).astype(np.int32),
                        sharding)
    want = np.asarray(smap(lambda v: lax.psum(v, "data"))(xi))
    scheds = [build_generalized(P, r) for r in range(max_r(P) + 1)]
    scheds += [build_ring(P), build_traff_rounds(P), build_dual_root(P)]
    c0, h0 = clock.mark()
    t0 = time.perf_counter()
    for sched in scheds:
        got = np.asarray(smap(
            lambda v, s=sched: allreduce_flat(v, "data", s))(xi))
        check(np.array_equal(got, want),
              f"P={P} {sched.kind} r={sched.r} int32 allreduce != psum")
    sizes = ragged_sizes(m, P)
    got = np.asarray(smap(lambda v: all_gather_flat(
        reduce_scatter_flat(v, "data"), "data", sizes=sizes))(xi))
    check(np.array_equal(got, want), f"P={P} ragged RS -> AG != psum")
    report(dev, "collectives", P=P, m=m, dtype="int32",
           families=",".join(f"{s.kind}/r{s.r}" for s in scheds),
           bit_exact="yes", rs_ag_sizes=f"{min(sizes)}..{max(sizes)}",
           wall_s=f"{time.perf_counter() - t0:.2f}",
           compile_s=f"{clock.mark()[0] - c0:.2f}")

    # fp32 through combine="auto": on the TPU that is the Pallas
    # combine_n, traced under the default check_vma=True.  Each element
    # sums the same P fp32 terms as psum in another order, so the two
    # differ by a few ulps of the partial sums: rtol 1e-5 / atol 1e-5 on
    # unit-scale inputs.
    xf = jax.device_put(rng.standard_normal((P, m)).astype(np.float32),
                        sharding)
    sched = build_generalized(P, 1)
    f = smap(lambda v: allreduce_flat(v, "data", sched))
    hlo = f.lower(xf).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"P={P}: no Pallas kernel in the compiled allreduce")
    got = np.asarray(f(xf))
    wantf = np.asarray(smap(lambda v: lax.psum(v, "data"))(xf))
    err = float(np.max(np.abs(got - wantf)))
    np.testing.assert_allclose(got, wantf, rtol=1e-5, atol=1e-5)
    report(dev, "collectives", P=P, m=m, dtype="float32", combine="auto",
           checker="check_vma=True", tpu_custom_call="yes",
           max_abs_err=f"{err:.3e}")


def phase_dp_train(dev, clock, cfg, seed, *, seq=256, global_batch=8):
    """One dp=4 train step (gradient sync through dp_grad_allreduce)
    against the same step at dp=1 on the global batch."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import OptConfig

    oc = OptConfig(lr=1e-4, warmup_steps=1, total_steps=1)
    out = {}
    for dp in (1, 4):
        mesh = make_mesh((dp, 1), ("data", "model"),
                         devices=jax.devices()[:dp])
        bundle, params, opt = _train_setup(cfg, mesh, oc, seed)
        batch = _batch(cfg, bundle, 0, seq, global_batch, seed)
        c0, _ = clock.mark()
        t0 = time.perf_counter()
        params, opt, metrics = bundle.train_step(params, opt, batch)
        loss = float(metrics["loss"])
        report(dev, "dp_train.step", dp=dp, loss=f"{loss:.6f}",
               step_s=f"{time.perf_counter() - t0:.3f}",
               compile_s=f"{clock.mark()[0] - c0:.2f}",
               peak_gib=f"{peak_gib(dev):.2f}")
        out[dp] = (loss, jax.device_get(params))
        del params, opt, bundle, batch
        gc.collect()
    # The first AdamW step moves each parameter by lr * g/(|g| + eps)
    # (plus the shared weight decay), i.e. by lr * sign(g) wherever the
    # clipped gradient is far above eps.  Gradients that differ only by
    # rounding (fp32 sums over 4 shards vs 1, bf16 matmul outputs) thus
    # give identical parameters except where rounding flips the sign of
    # a near-zero gradient, which moves them at most 2 * lr apart.  So:
    # every parameter within 2 * lr (+1% for the fp32 update), and at
    # most 1% of them apart by more than lr / 1000 -- a missing or wrong
    # gradient sync flips the sign of a third or more.
    l1, p1 = out[1]
    l4, p4 = out[4]
    worst, n_apart, n_all = 0.0, 0, 0
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
        worst = max(worst, float(d.max()))
        n_apart += int((d > oc.lr / 1000).sum())
        n_all += d.size
    report(dev, "dp_train.compare", loss_dp1=f"{l1:.6f}",
           loss_dp4=f"{l4:.6f}", max_param_diff=f"{worst:.3e}",
           lr=oc.lr, frac_apart=f"{n_apart / n_all:.2e}")
    # the loss is the same token mean, from bf16 forwards at batch 8 on
    # one chip and 2 per chip: rounding moves it far less than 1e-3
    check(abs(l1 - l4) <= 1e-3 * abs(l1),
          f"dp=4 loss {l4} vs dp=1 loss {l1}")
    check(worst <= 2 * oc.lr * 1.01, f"params differ by {worst}")
    check(n_apart <= 0.01 * n_all,
          f"{n_apart}/{n_all} params differ by more than lr/1000")


# ---------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip collective phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository beside this script "
              f"({SRC}/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's devices are {dev.platform} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    report(dev, "device", platform=dev.platform, count=len(devs),
           pallas_interpret=ops.interpret(), compile_cache=cache_dir)

    granite = get_config("granite_8b")
    t_start = time.perf_counter()
    try:
        check(not ops.interpret(), "Pallas kernels would run interpreted")
        if args.chips == 4:
            for P in (4, 3):
                phase_collectives(dev, clock, P, m=2**20 + 3)
            phase_dp_train(dev, clock, dataclasses.replace(
                granite, n_layers=TRAIN_LAYERS), args.seed)
        else:
            phase_kernels(dev)
            # train before serve: peak_bytes_in_use is the process's
            # high-water mark, and the smaller phase must read its own
            train_cfg = dataclasses.replace(granite, n_layers=TRAIN_LAYERS)
            report(dev, "train.config", name=granite.name,
                   n_layers=f"{TRAIN_LAYERS}/{granite.n_layers}")
            phase_train(dev, clock, train_cfg, args.seed)
            serve_cfg = dataclasses.replace(granite, n_layers=SERVE_LAYERS)
            report(dev, "serve.config", name=granite.name,
                   n_layers=f"{SERVE_LAYERS}/{granite.n_layers}",
                   d_model=granite.d_model, heads=granite.n_heads,
                   kv_heads=granite.n_kv_heads, head_dim=granite.head_dim,
                   d_ff=granite.d_ff, vocab=granite.vocab)
            phase_serve(dev, clock, serve_cfg, args.seed)
    except (Failed, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    secs, hits = clock.mark()
    report(dev, "total", wall_s=f"{time.perf_counter() - t_start:.2f}",
           compile_s=f"{secs:.2f}", cache_hits=hits)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
