"""Wall-clock microbenchmark of the JAX collective executors.

Runs on 8 forced host devices (launched by benchmarks.run with XLA_FLAGS
set).  CPU collective timings do not transfer to ICI, but the *relative*
cost of schedule variants (step count vs volume) and parity with the XLA
native psum are meaningful smoke-level signals.

Prints ``wall,<name>,<us_per_call>,1`` rows.
"""
import os
import sys
import time

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core.allreduce import allreduce_flat
from repro.core.schedule import build_generalized, build_ring, max_r
from repro.obs.log import data


def bench(fn, x, iters=30):
    out = fn(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(0)
    for m_elems, label in [(256, "1KB"), (262_144, "1MB"),
                           (8_388_608, "32MB")]:
        x = rng.standard_normal((n, m_elems)).astype(np.float32)
        for r in range(max_r(n) + 1):
            sched = build_generalized(n, r)
            f = jax.jit(shard_map(
                lambda v, s=sched: allreduce_flat(v[0], "data", s)[None],
                mesh=mesh, in_specs=P("data", None),
                out_specs=P("data", None)))
            us = bench(f, x)
            data(f"wall,gen_allreduce_{label}_r{r},{us:.1f},1")
        sched = build_ring(n)
        f = jax.jit(shard_map(
            lambda v, s=sched: allreduce_flat(v[0], "data", s)[None],
            mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))
        data(f"wall,ring_{label},{bench(f, x):.1f},1")
        g = jax.jit(shard_map(
            lambda v: jax.lax.psum(v[0], "data")[None],
            mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))
        data(f"wall,xla_psum_{label},{bench(g, x):.1f},1")


if __name__ == "__main__":
    main()
