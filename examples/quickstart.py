"""Quickstart: the generalized allreduce end to end.

1. Compile a schedule for an awkward process count (P = 7) and inspect it.
2. Verify it numerically with the numpy simulator.
3. Autotune the step count r for a fabric + message size (paper eq 37).
4. Run the real JAX executor on 8 virtual devices inside shard_map --
   including an *uneven* (ragged) message size that does not divide the
   device count, priced by true moved bytes.

Run:  PYTHONPATH=src python examples/quickstart.py
(no XLA_FLAGS needed -- this script forces 8 host devices itself)
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np


def main():
    from repro.core.schedule import (build_generalized, max_r,
                                     schedule_summary)
    from repro.core.simulator import simulate
    from repro.core.cost_model import (PAPER_10GE, TPU_V5E_ICI,
                                       optimal_r_analytic, optimal_r_search,
                                       tau_best_sota, tau_intermediate)

    # --- 1/2: compile + verify for prime-ish P -------------------------
    P = 7
    print(f"== schedules for P={P} (non-power-of-two) ==")
    for r in range(max_r(P) + 1):
        s = build_generalized(P, r)
        print(" ", schedule_summary(s))
    rng = np.random.default_rng(0)
    vecs = [rng.standard_normal(21) for _ in range(P)]
    res = simulate(build_generalized(P, 1), vecs)
    np.testing.assert_allclose(res[3], np.sum(vecs, axis=0), rtol=1e-12)
    print("  simulator: allreduce(P=7, r=1) == sum  OK")

    # --- 3: autotune r --------------------------------------------------
    print("\n== optimal step count r (paper eq. 37) ==")
    for fabric in (PAPER_10GE, TPU_V5E_ICI):
        for m in [425.0, 65536.0, 16.0 * 2**20]:
            ra = optimal_r_analytic(127, m, fabric)
            rs = optimal_r_search(127, m, fabric)
            t = tau_intermediate(127, m, rs, fabric)
            print(f"  {fabric.name:12s} m={m:>10.0f}B  r*={rs} "
                  f"(analytic {ra})  t={t*1e6:8.1f}us  "
                  f"best-SOTA={tau_best_sota(127, m, fabric)*1e6:8.1f}us")

    # --- 4: the real executor -------------------------------------------
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as Psp
    from jax import shard_map
    from repro.core.allreduce import allreduce_tree

    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    grads = {"w": rng.standard_normal((n, 40, 3)).astype(np.float32),
             "b": rng.standard_normal((n, 5)).astype(np.float32)}

    def sync(tree):
        local = jax.tree.map(lambda v: v[0], tree)
        out = allreduce_tree(local, "data", mean=True)  # autotuned r
        return jax.tree.map(lambda v: v[None], out)

    f = jax.jit(shard_map(sync, mesh=mesh, in_specs=Psp("data"),
                          out_specs=Psp("data")))
    out = f(grads)
    np.testing.assert_allclose(np.asarray(out["w"])[0],
                               grads["w"].mean(0), rtol=1e-4)
    print(f"\n== JAX executor on {n} devices: gradient-mean pytree "
          f"allreduce OK ==")

    # --- 5: uneven (ragged) sizes --------------------------------------
    from repro.core import ragged_sizes, ragged_step_units
    from repro.core.allreduce import all_gather_flat, reduce_scatter_flat
    from repro.core.schedule import build_generalized as bg

    m = 3 * n + 5                               # does not divide n
    sizes = ragged_sizes(m, n)
    print(f"\n== ragged: m={m} over P={n} splits as {sizes} ==")
    s = bg(n, 0)
    tx, _ = ragged_step_units(s, n + 1)         # m = P + 1: worst ratio
    padded = [st.n_tx * (-(-(n + 1) // n)) for st in s.steps]
    print(f"  per-step tx elements at m={n + 1}: true {list(tx)} vs "
          f"zero-padded {padded} -- the cost model charges the left")
    x = rng.integers(-1000, 1000, (n, m)).astype(np.int32)

    def rs_ag(v):
        shard = reduce_scatter_flat(v[0], "data")    # exact ragged shard
        return all_gather_flat(shard, "data", sizes=sizes)[None]

    g = jax.jit(shard_map(rs_ag, mesh=mesh, in_specs=Psp("data", None),
                          out_specs=Psp("data", None)))
    np.testing.assert_array_equal(np.asarray(g(x))[0], x.sum(0))
    print("  reduce-scatter -> allgatherv round trip == sum, bit-exact OK")


if __name__ == "__main__":
    main()
