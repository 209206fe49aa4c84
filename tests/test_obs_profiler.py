"""The tracer's profiler sink, the engine's tick spans and the named
scopes of the compiled programs, on the CPU.

While a JAX profiler session runs, ``repro.obs.trace`` spans land on the
profile's host plane with their args as stats; the engine's ticks carry
their counters there; and the device programs' ``op_name`` paths carry
the scopes the benchmark's per-layer metrics read (``attn``, ``mlp``,
``weight_cast``, ``head``, ``optimizer``, ``grad_sync``, ``execplan.*``);
and the paged serve step's attention reads the gathered K/V blocks with
no copy repeated per query head and no f32 copy.
"""
from __future__ import annotations

import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every host event of the one
    profile under ``trace_dir``, in start order."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


@pytest.fixture
def chrome_off():
    prev = obs_trace.set_tracer(obs_trace.Tracer(enabled=False))
    yield
    obs_trace.set_tracer(prev)


def test_span_args_land_on_the_host_plane(tmp_path, chrome_off):
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs_trace.profiling()
        with obs_trace.span("engine.probe", cat="t", s=128, kind="chunk") \
                as sp:
            sp.set(tokens=5)
            obs_trace.counter("queued", 3)
            with obs_trace.span("engine.inner"):
                pass
        obs_trace.get_tracer().instant("engine.mark", step=7)
    finally:
        jax.profiler.stop_trace()
    ev = {name: (s, e, st) for name, s, e, st in _host_events(tmp_path)
          if name.startswith("engine.")}
    assert ev["engine.probe"][2] == {"s": 128, "kind": "chunk", "tokens": 5,
                                     "queued": 3}
    outer, inner = ev["engine.probe"], ev["engine.inner"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert ev["engine.mark"][2] == {"step": 7}
    # the Chrome sink stayed off
    assert obs_trace.get_tracer().n_events == 0


def test_no_sink_on_gives_the_shared_null_span(chrome_off):
    assert not obs_trace.profiling()
    a = obs_trace.span("engine.tick", s=1)
    b = obs_trace.get_tracer().span("other")
    assert a is obs_trace._NULL_SPAN and b is obs_trace._NULL_SPAN


def test_import_repro_obs_imports_no_jax():
    code = ("import sys; import repro.obs; "
            "from repro.obs import trace; "
            "assert not trace.profiling(); "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=SRC),
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_compile_cache_keys_on_scopes_not_on_source(monkeypatch, tmp_path):
    """The persistent cache's key holds each operation's scopes, so a
    profile never shows another version's; it holds no source location,
    so neither the entry point nor the checkout's path splits it."""
    from repro.launch import compile_cache
    names = ("jax_compilation_cache_dir",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")
    was = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    try:
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key

        def f(x):
            with jax.named_scope("attn"):
                return x * 2

        text = jax.jit(f).lower(np.ones(4, np.float32)).as_text(
            debug_info=True)
        assert "jit(f)/attn/mul" in text
        assert ".py" not in text
    finally:
        for n, v in was.items():
            jax.config.update(n, v)


def test_engine_ticks_and_their_counters(tmp_path, chrome_off):
    from repro.configs import ARCHS, get_config, get_reduced
    from repro.launch.mesh import make_mesh
    from repro.models.model import init_params
    from repro.parallel.api import ParallelConfig
    from repro.serve.engine import Engine, Request

    arch = next(a for a in ARCHS if get_config(a).is_decoder)
    cfg = get_reduced(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    pc = ParallelConfig(dp=1, tp=1)
    params, _ = init_params(cfg, pc, jax.random.PRNGKey(0))
    eng = Engine(cfg, pc, mesh, params, batch_slots=2, max_len=64,
                 prefill_chunk=8)
    planned = []
    build = eng._build_tick

    def spy():
        S, rows, toks, n_new, emit, ctx = build()
        planned.append({"s": S, "rows": len(rows), "tokens": int(n_new.sum()),
                        "pad_slots": eng.B * S - int(n_new.sum()),
                        "kv_blocks_used": sum(m.n_used for m in eng.kv),
                        "kv_tokens": int((eng.lengths + n_new).sum()),
                        # each new token scores its causal prefix
                        "attn_pairs": sum(int(eng.lengths[b]) + i + 1
                                          for b in range(eng.B)
                                          for i in range(int(n_new[b]))),
                        "queued": len(eng.queue), "emit": bool(emit)})
        return S, rows, toks, n_new, emit, ctx

    eng._build_tick = spy
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, (n,)).astype(np.int32),
                    max_new_tokens=3) for n in (5, 12, 3)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(reqs)
    finally:
        jax.profiler.stop_trace()
    events = [ev for ev in _host_events(tmp_path)
              if ev[0].startswith("engine.")]
    ticks = [ev for ev in events if ev[0] == "engine.tick"]
    assert len(ticks) == eng.stats()["ticks"] == len(planned)
    assert any(p["s"] > 1 for p in planned) and any(p["s"] == 1
                                                    for p in planned)
    for (_, t0, t1, args), want in zip(ticks, planned):
        assert args == {k: v for k, v in want.items() if k != "emit"}
        leaves = [ev[0] for ev in events
                  if ev[0] != "engine.tick" and t0 <= ev[1] and ev[2] <= t1]
        assert leaves == ["engine.admit", "engine.build", "engine.dispatch"] \
            + (["engine.fetch"] if want["emit"] else []) + ["engine.sample"]


_LOWER = textwrap.dedent("""
    import re, sys
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import ARCHS, get_config, get_reduced
    from repro.core.allreduce import allreduce_flat
    from repro.core.schedule import build_generalized
    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.models.attention import PageCtx
    from repro.models.model import init_paged_caches
    from repro.train.optimizer import OptConfig
    from repro.train.step import (input_shapes, make_paged_serve_step,
                                  make_train_step)

    def scopes(text):
        names = re.findall(r'op_name="([^"]*)"', text)
        return sorted({t for n in names for t in re.split(r"[/()]", n)
                       if t})

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            tree)

    arch = next(a for a in ARCHS if get_config(a).is_decoder)
    cfg = get_reduced(arch)
    dev = jax.devices()
    mesh2 = make_mesh((2, 1), ("data", "model"), devices=dev[:2])
    pc2 = parallel_config_for(mesh2, param_mode="dp")
    tb = make_train_step(cfg, pc2, mesh2, OptConfig())
    batch = input_shapes(cfg, shape_kind="train", seq_len=16,
                         global_batch=2)
    train = tb.train_step.lower(tb.params_shapes, tb.opt_shapes,
                                batch).as_text(dialect="hlo",
                                               debug_info=True)
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=dev[:1])
    pc1 = parallel_config_for(mesh1, param_mode="dp")
    sb = make_paged_serve_step(cfg, pc1, mesh1)
    B, S, nb, bs = 2, 8, 4, 8
    caches = init_paged_caches(cfg, pc1, B, 1 + B * nb, bs)
    ctx = PageCtx(block_table=jnp.zeros((B, nb), jnp.int32),
                  lengths=jnp.zeros(B, jnp.int32),
                  n_new=jnp.ones(B, jnp.int32), reset=jnp.zeros(B, bool))
    serve = sb.serve_step.lower(sb.params_shapes,
                                jax.ShapeDtypeStruct((B, S), jnp.int32),
                                sds(caches), sds(ctx)).as_text(
        dialect="hlo", debug_info=True)

    def ar(x):
        return allreduce_flat(x[0], "data", build_generalized(2, 0),
                              combine="add")[None]
    sm = jax.shard_map(ar, mesh=mesh2, in_specs=jax.P("data"),
                       out_specs=jax.P("data"))
    plan = jax.jit(sm).lower(jax.ShapeDtypeStruct((2, 64), jnp.float32)
                             ).as_text(dialect="hlo", debug_info=True)
    for name, text in (("train", train), ("serve", serve), ("plan", plan)):
        print(name, " ".join(scopes(text)))
    with open(sys.argv[1], "w") as f:
        f.write(serve)
    print("serve_dims", B, S, nb, bs, cfg.n_kv_heads, cfg.n_heads, cfg.hd)
""")


def _instructions(hlo, shape, op=r"\w+"):
    """``op_name`` of every ``op`` instruction of ``hlo`` whose result has
    the shape ``shape`` (e.g. ``"f32[2,4,32,16]"``); "" where it has
    none."""
    pat = re.compile(r"^\s*(?:ROOT )?\S+ = " + re.escape(shape)
                     + r"(?:\{[^}]*\})? (?:" + op + r")\(")
    return [(re.findall(r'op_name="([^"]*)"', line) or [""])[0]
            for line in hlo.splitlines() if pat.match(line)]


def test_compiled_programs_carry_the_named_scopes(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    serve_hlo = tmp_path / "serve.hlo"
    out = subprocess.run([sys.executable, "-c", _LOWER, str(serve_hlo)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = {line.split(" ", 1)[0]: set(line.split()[1:])
           for line in out.stdout.splitlines() if line}
    assert {"attn", "mlp", "weight_cast", "head", "optimizer",
            "grad_sync"} <= got["train"]
    assert any(t.startswith("execplan.") for t in got["train"])
    assert {"attn", "mlp", "weight_cast", "head"} <= got["serve"]
    assert "optimizer" not in got["serve"]
    plan = got["plan"]
    assert any(re.fullmatch(r"execplan\.\w+", t) for t in plan)
    assert {"tick0", "combine"} <= plan

    # the paged serve step attends over the gathered blocks as they are:
    # no K/V repeated per query head of a GQA group, no f32 K/V view
    dims = next(line.split()[1:] for line in out.stdout.splitlines()
                if line.startswith("serve_dims "))
    B, S, nb, bs, hkv, hq, hd = map(int, dims)
    G, T = hq // hkv, nb * bs
    assert G > 1
    serve = serve_hlo.read_text()
    view = f"[{B},{nb},{hkv},{bs},{hd}]"      # pool[block_table]
    gathered = _instructions(serve, "bf16" + view, "gather")
    assert gathered and all("/attn/" in n for n in gathered), gathered
    for dtype in ("bf16", "f32"):
        assert not _instructions(serve, f"{dtype}[{B},{hkv},{G},{T},{hd}]")
        assert not _instructions(serve, f"{dtype}[{B},{hq},{T},{hd}]")
    for shape in (view, f"[{B},{hkv},{T},{hd}]"):
        assert not _instructions(serve, "f32" + shape)
    scores = _instructions(serve, f"f32[{B},{hkv},{G},{S},{nb},{bs}]",
                           "dot")
    assert scores and all("/attn/" in n for n in scores), scores


def test_the_serve_step_on_prepared_weights_casts_no_weight():
    """Lowered on ``serve_weights`` of the parameters, as ``Engine``
    serves, the paged serve step has no ``convert`` whose result is a
    bf16 weight or one layer's slice of a stacked one; lowered on the
    fp32 tree it has such converts, all under ``weight_cast``."""
    import jax.numpy as jnp
    from repro.configs import ARCHS, get_config, get_reduced
    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.models.attention import PageCtx
    from repro.models.model import init_paged_caches, serve_weights
    from repro.train.step import make_paged_serve_step

    cfg = get_reduced(next(a for a in ARCHS if get_config(a).is_decoder))
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    pc = parallel_config_for(mesh, param_mode="dp")
    sb = make_paged_serve_step(cfg, pc, mesh)
    B, S, nb, bs = 2, 8, 4, 8
    caches = jax.eval_shape(
        lambda: init_paged_caches(cfg, pc, B, 1 + B * nb, bs))
    ctx = PageCtx(block_table=jax.ShapeDtypeStruct((B, nb), jnp.int32),
                  lengths=jax.ShapeDtypeStruct((B,), jnp.int32),
                  n_new=jax.ShapeDtypeStruct((B,), jnp.int32),
                  reset=jax.ShapeDtypeStruct((B,), jnp.bool_))
    shapes = set()
    for path, w in jax.tree_util.tree_flatten_with_path(
            sb.params_shapes)[0]:
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        if keys[0] in ("embed", "head") or (
                keys[0] in ("prefix", "cycles") and keys[2] in ("attn",
                                                                "mlp")):
            shapes.update(w.shape[i:] for i in range(len(w.shape) - 1))

    def weight_converts(params):
        hlo = sb.serve_step.lower(
            params, jax.ShapeDtypeStruct((B, S), jnp.int32), caches,
            ctx).as_text(dialect="hlo", debug_info=True)
        return [n for s in shapes for n in _instructions(
            hlo, "bf16[" + ",".join(map(str, s)) + "]", "convert")]

    fp32 = weight_converts(sb.params_shapes)
    assert fp32 and all("weight_cast" in n for n in fp32), fp32
    assert not weight_converts(jax.eval_shape(serve_weights,
                                              sb.params_shapes))
