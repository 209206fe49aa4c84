"""Worker executed in a subprocess with XLA_FLAGS forcing N host devices.

Runs a batch of multi-device checks and prints "ALL-OK" on success.
Keeping everything in one process amortizes JAX startup (~seconds).
"""
import os
import sys

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), \
    "must be launched with XLA_FLAGS=--xla_force_host_platform_device_count=N"

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from jax import shard_map  # noqa: E402
from repro.core.allreduce import (all_gather_flat, all_to_all_flat,  # noqa: E402
                                  allreduce_flat, allreduce_tree,
                                  hierarchical_allreduce,
                                  hierarchical_allreduce_flat, psum_tree,
                                  reduce_scatter_flat, tree_all_gather,
                                  tree_reduce_scatter)
from repro.core.schedule import (build_dual_root, build_generalized,  # noqa: E402
                                 build_ring, build_sorted_generalized,
                                 build_traff_rounds, max_r)
from repro.topology import Level, Topology, build_hierarchical  # noqa: E402
from repro.topology.fabric import TPU_DCN  # noqa: E402
from repro.core.cost_model import TPU_V5E_ICI  # noqa: E402


def check_allreduce_flat():
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(0)
    for m in [1, 5, n, 3 * n + 1, 257]:
        x = rng.standard_normal((n, m)).astype(np.float32)
        want = x.sum(0)
        scheds = [build_generalized(n, r) for r in range(max_r(n) + 1)]
        scheds.append(build_ring(n))
        if n & (n - 1) == 0:
            scheds.append(build_generalized(n, 0, "hypercube"))
            scheds.append(build_generalized(n, max_r(n), "hypercube"))
        for sched in scheds:
            f = jax.jit(shard_map(
                lambda v: allreduce_flat(v[0], "data", sched)[None],
                mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))
            out = np.asarray(f(x))
            for d in range(n):
                np.testing.assert_allclose(out[d], want, rtol=2e-5, atol=2e-5)
    print("ok allreduce_flat")


def check_vs_psum():
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(1)
    tree = {"w": rng.standard_normal((n, 33)).astype(np.float32),
            "b": rng.standard_normal((n, 7, 3)).astype(np.float32)}
    def ours(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = allreduce_tree(loc, "data", mean=True)
        return jax.tree.map(lambda v: v[None], out)
    def theirs(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = psum_tree(loc, "data", mean=True)
        return jax.tree.map(lambda v: v[None], out)
    fo = jax.jit(shard_map(ours, mesh=mesh, in_specs=P("data"), out_specs=P("data")))
    ft = jax.jit(shard_map(theirs, mesh=mesh, in_specs=P("data"), out_specs=P("data")))
    a, b = fo(tree), ft(tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=2e-5, atol=2e-5)
    print("ok vs_psum")


def check_rs_ag():
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(2)
    m = 4 * n
    x = rng.standard_normal((n, m)).astype(np.float32)
    want = x.sum(0)

    def f(v):
        shard = reduce_scatter_flat(v[0], "data")
        return shard[None]
    out = np.asarray(jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))(x))
    u = m // n
    for d in range(n):
        np.testing.assert_allclose(out[d], want[d*u:(d+1)*u], rtol=2e-5, atol=2e-5)

    def g(v):
        shard = reduce_scatter_flat(v[0], "data")
        return all_gather_flat(shard, "data")[None]
    out = np.asarray(jax.jit(shard_map(
        g, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))(x))
    for d in range(n):
        np.testing.assert_allclose(out[d], want, rtol=2e-5, atol=2e-5)
    print("ok rs_ag")


def check_multiaxis():
    devs = len(jax.devices())
    if devs % 2:
        print("ok multiaxis (skipped)")
        return
    n0, n1 = 2, devs // 2
    mesh = jax.make_mesh((n0, n1), ("pod", "data"))
    n = n0 * n1
    rng = np.random.default_rng(3)
    x = rng.standard_normal((n, 11)).astype(np.float32)
    want = x.sum(0)
    sched = build_generalized(n, 1)
    f = jax.jit(shard_map(
        lambda v: allreduce_flat(v.reshape(-1), ("pod", "data"), sched)[None],
        mesh=mesh, in_specs=P(("pod", "data"), None),
        out_specs=P(("pod", "data"), None)))
    out = np.asarray(f(x))
    for d in range(n):
        np.testing.assert_allclose(out[d], want, rtol=2e-5, atol=2e-5)
    print("ok multiaxis")


def check_tree_zero():
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(4)
    tree = {"a": rng.standard_normal((n, 13)).astype(np.float32),
            "b": rng.standard_normal((n, 2, 5)).astype(np.float32)}
    def f(t):
        loc = jax.tree.map(lambda v: v[0], t)
        shard, spec = tree_reduce_scatter(loc, "data", mean=True)
        back = tree_all_gather(shard, spec, "data")
        return jax.tree.map(lambda v: v[None], back)
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data")))(tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k])[0], tree[k].mean(0),
                                   rtol=2e-5, atol=2e-5)
    print("ok tree_zero")


def check_hierarchical():
    """Hierarchical allreduce over a ("pod", "data") mesh vs the numpy sum,
    for every outer r and several message sizes (incl. sizes that need
    padding), plus the autotuned pytree path."""
    devs = len(jax.devices())
    if devs % 2:
        print("ok hierarchical (skipped)")
        return
    shape = (2, devs // 2)
    names = ("pod", "data")
    mesh = jax.make_mesh(shape, names)
    n = devs
    topo = Topology((Level("pod", shape[0], TPU_DCN),
                     Level("ici", shape[1], TPU_V5E_ICI)),
                    name=f"test-{shape[0]}x{shape[1]}")
    rng = np.random.default_rng(5)
    for m in [1, 7, n, 3 * n + 1, 257]:
        x = rng.standard_normal((n, m)).astype(np.float32)
        want = x.sum(0)
        for r in range(max_r(shape[0]) + 1):
            hs = build_hierarchical(topo, r)
            f = jax.jit(shard_map(
                lambda v, h=hs: hierarchical_allreduce_flat(
                    v.reshape(-1), names, h)[None],
                mesh=mesh, in_specs=P(names, None),
                out_specs=P(names, None)))
            out = np.asarray(f(x))
            for d in range(n):
                np.testing.assert_allclose(out[d], want, rtol=2e-5,
                                           atol=2e-5)
    # autotuned pytree path (plan may resolve to flat or hierarchical)
    tree = {"w": rng.standard_normal((n, 33)).astype(np.float32),
            "b": rng.standard_normal((n, 7, 3)).astype(np.float32)}

    def g(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = hierarchical_allreduce(loc, names, topo, mean=True)
        return jax.tree.map(lambda v: v[None], out)

    out = jax.jit(shard_map(g, mesh=mesh, in_specs=P(names),
                            out_specs=P(names)))(tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k])[0], tree[k].mean(0),
                                   rtol=2e-5, atol=2e-5)
    print("ok hierarchical")


def check_ragged():
    """Ragged (uneven-shard) collectives on real devices.

    1. ``dp_grad_allreduce`` of an int32 pytree whose fused flat size is
       coprime with the device count must match ``psum`` bit-exactly
       (sums stay far below 2^24, so the f32 accumulation is exact).
    2. The ragged reduce-scatter owns the exact balanced chunk, and the
       allgatherv inverse reassembles the exact vector.
    3. A schedule compiled for the wrong P raises ShapeError (typed, not
       a stripped-under-``-O`` assert).
    """
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(11)
    from repro.core.allreduce import psum_tree
    from repro.core.schedule import ShapeError, ragged_offsets, ragged_sizes
    from repro.parallel.api import ParallelConfig, dp_grad_allreduce

    pc = ParallelConfig(dp_axes=("data",), dp=n)
    # leaf sizes chosen so the fused flat buffer (13 + 2*5 = 23 elems
    # at n=8, 23 % 8 != 0) rides the ragged split
    tree = {"a": rng.integers(-1000, 1000, (n, 13)).astype(np.int32),
            "b": rng.integers(-1000, 1000, (n, 2, 5)).astype(np.int32)}

    def ours(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = dp_grad_allreduce(loc, pc, mean=False)
        return jax.tree.map(lambda v: v[None], out)

    def theirs(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = psum_tree(loc, "data")
        return jax.tree.map(lambda v: v[None], out)

    a = jax.jit(shard_map(ours, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))(tree)
    b = jax.jit(shard_map(theirs, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))(tree)
    for k in tree:
        assert (np.asarray(a[k]) == np.asarray(b[k])).all(), k
        assert (np.asarray(a[k])[0] == tree[k].sum(0)).all(), k

    # ragged reduce-scatter + allgatherv round trip, exact shard contents
    for m in (1, n - 1, n + 1, 3 * n + 5, 257):
        x = rng.integers(-1000, 1000, (n, m)).astype(np.int32)
        want = x.sum(0)
        sizes = ragged_sizes(m, n)
        offs = ragged_offsets(sizes)

        def rs(v):
            return reduce_scatter_flat(v[0], "data")[None]
        shards = np.asarray(jax.jit(shard_map(
            rs, mesh=mesh, in_specs=P("data", None),
            out_specs=P("data", None)))(x))
        for d in range(n):
            assert (shards[d][:sizes[d]]
                    == want[offs[d]:offs[d] + sizes[d]]).all(), (m, d)
            assert (shards[d][sizes[d]:] == 0).all(), (m, d)

        def rt(v):
            shard = reduce_scatter_flat(v[0], "data")
            return all_gather_flat(shard, "data", sizes=sizes)[None]
        out = np.asarray(jax.jit(shard_map(
            rt, mesh=mesh, in_specs=P("data", None),
            out_specs=P("data", None)))(x))
        for d in range(n):
            assert (out[d] == want).all(), (m, d)

    # typed shape errors fire at trace time
    wrong = build_generalized(n + 1, 0)
    try:
        jax.jit(shard_map(
            lambda v: allreduce_flat(v[0], "data", wrong)[None],
            mesh=mesh, in_specs=P("data", None),
            out_specs=P("data", None)))(np.zeros((n, 8), np.float32))
    except ShapeError as e:
        assert e.expected == n + 1 and e.actual == n
    else:
        raise AssertionError("wrong-P schedule did not raise ShapeError")
    print("ok ragged")


def check_execplan():
    """The ExecPlan executor on real forced-host devices: integer inputs
    must reproduce the numpy sum *bit-exactly* for every bucket count,
    and the Pallas combine_n-routed path must match the chained-add path
    (same fp32 pairwise sums, one fused kernel call per pipeline tick).
    """
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(7)
    from repro.core.execplan import compile_plan
    for m in [1, 13, 257]:
        x = rng.integers(-1000, 1000, (n, m)).astype(np.int32)
        want = x.sum(0)
        scheds = [build_generalized(n, r) for r in range(max_r(n) + 1)]
        scheds.append(build_ring(n))
        for sched in scheds:
            for nb in (1, 2, 4):
                f = jax.jit(shard_map(
                    lambda v, s=sched, b=nb: allreduce_flat(
                        v[0], "data", s, n_buckets=b)[None],
                    mesh=mesh, in_specs=P("data", None),
                    out_specs=P("data", None)))
                out = np.asarray(f(x))
                for d in range(n):
                    assert (out[d] == want).all(), \
                        (m, sched.kind, sched.r, nb, d)
    # combine_n-routed steps == chained jnp.add, bit for bit.
    # check_vma=False: the kernel's interpret mode (this CPU path) does
    # not trace under the checker; the Mosaic path does (see
    # tests/test_tpu_compile.py and chip_smoke.py --chips 4).
    # The latency-optimal schedule batches several combines per tick into
    # one kernel call; ring additionally covers add-free (recv-only)
    # ticks in its all-gather half -- pallas must skip those, not crash.
    lat_opt = build_generalized(n, max_r(n))
    assert any(st.n_adds > 1 for st in compile_plan(lat_opt).steps)
    for sched in (lat_opt, build_ring(n)):
        x = rng.integers(-1000, 1000, (n, 257)).astype(np.int32)
        outs = {}
        for comb in ("pallas", "add"):
            f = jax.jit(shard_map(
                lambda v, s=sched, c=comb: allreduce_flat(
                    v[0], "data", s, n_buckets=2, combine=c)[None],
                mesh=mesh, in_specs=P("data", None),
                out_specs=P("data", None), check_vma=False))
            outs[comb] = np.asarray(f(x))
        assert (outs["pallas"] == outs["add"]).all()
        assert (outs["add"][0] == x.sum(0)).all()
    print("ok execplan")


def check_a2a():
    """Schedule-driven all-to-all on real devices: both plan kinds (and
    the cost-model "auto" pick) bit-equal to ``lax.all_to_all`` on int
    data, pipelined buckets included; non-divisible lengths raise the
    typed ShapeError instead of mis-permuting."""
    from jax import lax

    from repro.core.execplan import simulate_a2a
    from repro.core.schedule import ShapeError
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(21)
    for mult in (1, 3, 32):
        m = n * mult
        x = rng.integers(-1000, 1000, (n, m)).astype(np.int32)
        ref = None
        for kind in ("direct", "bruck", "auto"):
            for nb in (1, 2):
                f = jax.jit(shard_map(
                    lambda v, k=kind, b=nb: all_to_all_flat(
                        v[0], "data", kind=k, n_buckets=b)[None],
                    mesh=mesh, in_specs=P("data", None),
                    out_specs=P("data", None)))
                out = np.asarray(f(x))
                if ref is None:
                    g = jax.jit(shard_map(
                        lambda v: lax.all_to_all(
                            v[0].reshape(n, -1), "data", 0, 0).reshape(1, -1),
                        mesh=mesh, in_specs=P("data", None),
                        out_specs=P("data", None)))
                    ref = np.asarray(g(x))
                assert (out == ref).all(), (mult, kind, nb)
        sim = simulate_a2a([x[d] for d in range(n)], "direct")
        for d in range(n):
            assert (ref[d] == sim[d]).all(), (mult, d)
    try:
        jax.jit(shard_map(
            lambda v: all_to_all_flat(v[0], "data")[None],
            mesh=mesh, in_specs=P("data", None),
            out_specs=P("data", None)))(np.zeros((n, n + 1), np.int32))
    except ShapeError as e:
        assert e.actual == n + 1
    else:
        raise AssertionError("non-divisible all-to-all did not raise")
    print("ok a2a")


def check_maxreduce():
    """Non-sum monoids on real devices: max/min allreduce bit-exact vs
    lax.pmax/pmin on int32 (incl. values past 2**24, which an f32
    accumulation cast would corrupt), Pallas-vs-elementwise parity for
    the max kernel, mean == psum / P bit-exact on int-valued f32, and
    the dp_grad_allreduce(op="max") + grads_all_finite wiring."""
    from jax import lax

    from repro.parallel.api import (ParallelConfig, dp_grad_allreduce,
                                    grads_all_finite)
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(22)
    scheds = [build_generalized(n, r) for r in range(max_r(n) + 1)]
    scheds.append(build_ring(n))
    for m in (1, 13, 257):
        # values straddle 2**24 so any f32 round-trip would be caught
        x = rng.integers(-(1 << 28), 1 << 28, (n, m)).astype(np.int32)
        refs = {"max": x.max(0), "min": x.min(0)}
        for sched in scheds:
            for comb in ("max", "min"):
                for nb in (1, 2):
                    f = jax.jit(shard_map(
                        lambda v, s=sched, c=comb, b=nb: allreduce_flat(
                            v[0], "data", s, combine=c, n_buckets=b)[None],
                        mesh=mesh, in_specs=P("data", None),
                        out_specs=P("data", None)))
                    out = np.asarray(f(x))
                    assert (out == refs[comb][None]).all(), \
                        (m, sched.kind, sched.r, comb, nb)
        g = jax.jit(shard_map(
            lambda v: lax.pmax(v[0], "data")[None], mesh=mesh,
            in_specs=P("data", None), out_specs=P("data", None)))
        assert (np.asarray(g(x)) == refs["max"][None]).all()

    # pallas-routed max == elementwise max, bit for bit (check_vma=False:
    # interpret mode does not trace under the checker, as above)
    x = rng.integers(-1000, 1000, (n, 257)).astype(np.int32)
    sched = build_generalized(n, max_r(n))
    outs = {}
    for comb in ("max:pallas", "max"):
        f = jax.jit(shard_map(
            lambda v, c=comb: allreduce_flat(
                v[0], "data", sched, combine=c, n_buckets=2)[None],
            mesh=mesh, in_specs=P("data", None),
            out_specs=P("data", None), check_vma=False))
        outs[comb] = np.asarray(f(x))
    assert (outs["max:pallas"] == outs["max"]).all()
    assert (outs["max"][0] == x.max(0)).all()

    # mean == psum / P bit-exact on integer-valued f32
    xf = x.astype(np.float32)
    f = jax.jit(shard_map(
        lambda v: jnp_stack_pair(
            allreduce_flat(v[0], "data", sched, combine="mean"),
            lax.psum(v[0], "data") / n),
        mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))
    got = np.asarray(f(xf))
    assert (got[0] == got[1]).all()

    # dp_grad_allreduce(op=) + the max-allreduce non-finite detector
    pc = ParallelConfig(dp_axes=("data",), dp=n)
    tree = {"a": rng.integers(-(1 << 28), 1 << 28, (n, 13)).astype(np.int32)}

    def ours(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = dp_grad_allreduce(loc, pc, mean=False, op="max")
        return jax.tree.map(lambda v: v[None], out)

    a = jax.jit(shard_map(ours, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))(tree)
    assert (np.asarray(a["a"])[0] == tree["a"].max(0)).all()

    grads = {"w": rng.standard_normal((n, 7)).astype(np.float32)}
    bad = {"w": grads["w"].copy()}
    bad["w"][n - 1, 3] = np.inf     # one non-finite value on ONE rank

    def finite(t):
        loc = jax.tree.map(lambda v: v[0], t)
        return grads_all_finite(loc, pc)[None]

    f = jax.jit(shard_map(finite, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))
    assert np.asarray(f(grads)).all()          # every rank: all finite
    assert not np.asarray(f(bad)).any()        # every rank saw the inf

    # affine bookends act ONCE over the hierarchical composition: premul
    # scales by f (never f^n_levels) and mean divides by the full P
    if n % 2 == 0:
        from repro.core.monoid import premul_sum
        from repro.topology import Level, Topology
        from repro.topology.fabric import TPU_DCN

        names = ("pod", "data")
        hmesh = jax.make_mesh((2, n // 2), names)
        topo = Topology((Level("pod", 2, TPU_DCN),
                         Level("ici", n // 2, TPU_V5E_ICI)),
                        name=f"maxreduce-2x{n // 2}")
        xf = rng.integers(-1000, 1000, (n, 37)).astype(np.float32)
        # premul by 0.5 is exact in f32 -> compare against numpy; mean's
        # divide-by-P is compiled by XLA as a reciprocal multiply (not
        # correctly rounded for non-power-of-two P), so its reference is
        # the in-program lax.psum(v)/P -- the same divide lax users get
        from jax import lax

        def hier(flat, c):
            return hierarchical_allreduce(flat, names, topo, r=0,
                                          mean=False, combine=c)

        def both(v):
            flat = v.reshape(-1)
            s = lax.psum(flat, names)
            import jax.numpy as jnp
            return jnp.stack([hier(flat, premul_sum(0.5)), 0.5 * s,
                              hier(flat, "mean"), s / n])[None]

        got = np.asarray(jax.jit(shard_map(
            both, mesh=hmesh, in_specs=P(names, None),
            out_specs=P(names, None, None)))(xf))
        for d in range(n):
            assert (got[d, 0] == 0.5 * xf.sum(0)).all(), d  # np-exact
            assert (got[d, 0] == got[d, 1]).all(), d        # == 0.5*psum
            assert (got[d, 2] == got[d, 3]).all(), d        # == psum / P
    print("ok maxreduce")


def jnp_stack_pair(a, b):
    import jax.numpy as jnp
    return jnp.stack([a, b])[None]


def check_moe_dispatch():
    """MoE forward under the three dispatch modes: the schedule-driven
    all-to-all path must match the GShard (lax.all_to_all) oracle
    bit-exactly (both are pure permutations of the same blocks), and
    the TP-sharded local path to fp32 exactness."""
    import jax.numpy as jnp  # noqa: F401

    from repro.models.config import ModelConfig, MoEConfig
    from repro.models.moe import ep_group_size, moe_apply
    from repro.parallel.api import ParallelConfig

    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    rng = np.random.default_rng(33)
    E, d, ff, k = 2 * n, 32, 48, 2    # experts split evenly for any n
    cfg = ModelConfig(name="t", family="moe", d_model=d, n_layers=1,
                      n_heads=4, n_kv_heads=4, d_ff=ff, vocab=128,
                      moe=MoEConfig(n_experts=E, top_k=k, d_expert=ff))
    p = {"router": {"w": rng.standard_normal((d, E)).astype(np.float32)},
         "experts": {
             "w1": 0.1 * rng.standard_normal((E, d, ff)).astype(np.float32),
             "w3": 0.1 * rng.standard_normal((E, d, ff)).astype(np.float32),
             "w2": 0.1 * rng.standard_normal((E, ff, d)).astype(np.float32)}}
    x = rng.standard_normal((n, 24, d)).astype(np.float32)

    outs = {}
    for disp in ("tp", "gshard", "schedule"):
        pc = ParallelConfig(dp_axes=("data",), dp=n, tp=1,
                            moe_dispatch=disp)
        assert ep_group_size(pc, E) == (1 if disp == "tp" else n)

        def f(xv, pp, pc=pc):
            y, aux = moe_apply(pp, xv, cfg, pc)
            return y, aux[None]

        g = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P("data", None, None), P()),
            out_specs=(P("data", None, None), P("data"))))
        y, aux = g(x, p)
        outs[disp] = np.asarray(y)
    assert (outs["gshard"] == outs["schedule"]).all(), \
        "schedule-driven dispatch != GShard oracle"
    np.testing.assert_allclose(outs["tp"], outs["gshard"],
                               rtol=1e-6, atol=1e-6)
    print("ok moe_dispatch")


def check_elastic_resize():
    """Elastic resize across prime dp counts (8 -> 7 -> 5): non-power-of
    -two survivor meshes are first-class for the generalized allreduce,
    so shrinking never pads or waits for spares.  Checks the zero1
    opt-state reset on layout change (the flat moment buffers are
    ``(dp * ceil(N/dp),)`` -- dp-dependent) and ``restore_latest`` both
    across a layout change and after a post-resize checkpoint."""
    import tempfile

    from repro.checkpoint.checkpoint import latest_steps
    from repro.data.pipeline import DataConfig
    from repro.models.config import ModelConfig
    from repro.runtime.elastic import ElasticConfig, ElasticRunner
    from repro.train.optimizer import OptConfig

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=160, vocab=256,
                      head_dim=16, act="swiglu")
    d = tempfile.mkdtemp(prefix="repro_elastic_resize_")
    runner = ElasticRunner(
        cfg, OptConfig(lr=1e-3, warmup_steps=2, total_steps=60),
        ElasticConfig(ckpt_dir=d, ckpt_every=4, param_mode="zero1"),
        DataConfig(seq_len=16, global_batch=8), mesh_shape=(8, 1))
    runner.run(4)                       # checkpoint lands at step 4 (dp=8)
    m8 = np.asarray(jax.device_get(runner.opt["m"]))
    assert m8.any(), "moments should be warm after 4 steps"

    # ---- shrink to a prime: dp=7 --------------------------------------
    runner.dc = DataConfig(seq_len=16, global_batch=14)
    runner.resize((7, 1), devices=jax.devices()[:7])
    assert runner.pc.dp == 7
    m7 = np.asarray(jax.device_get(runner.opt["m"]))
    assert m7.shape != m8.shape, "zero1 flat layout must change with dp"
    assert not m7.any(), "dp-dependent zero1 moments must reset on resize"
    assert int(runner.opt["step"]) > 0, "scalar step count survives"

    # ---- restore_latest across the layout change ----------------------
    # the newest checkpoint was written at dp=8: params (global arrays)
    # restore exactly; the incompatible zero1 buffers stay fresh.
    runner.ckpt.wait()
    step = runner.restore_latest()
    assert step == 4, step
    assert not np.asarray(jax.device_get(runner.opt["m"])).any()
    logs = runner.run(2)
    assert all(np.isfinite(r["loss"]) for r in logs)
    print("ok elastic_resize 8->7")

    # ---- shrink again: dp=5, then checkpoint + restore at dp=5 --------
    runner.dc = DataConfig(seq_len=16, global_batch=10)
    runner.resize((5, 1), devices=jax.devices()[:5])
    assert runner.pc.dp == 5
    logs = runner.run(2)                # steps 6,7; checkpoint at step 8
    logs += runner.run(1)
    runner.ckpt.wait()
    assert 8 in latest_steps(d), latest_steps(d)
    assert runner.restore_latest() == 8
    logs = runner.run(2)
    assert all(np.isfinite(r["loss"]) for r in logs)
    print("ok elastic_resize 8->7->5")


def check_serve():
    """Continuous-batching engine on a dp=2 x tp=2 mesh: batched decode
    on the paged cache is bit-identical to the single-request path, and
    the TP decode collectives route through ``autotune.choose()``
    against a measured tuning table -- the trace-time picks must report
    ``source="measured"`` and prefer the family the table says is
    faster (``traff_rounds`` in the fabricated ladder below)."""
    import tempfile

    from repro.launch.mesh import make_mesh, parallel_config_for
    from repro.models.config import ModelConfig
    from repro.models.model import init_params
    from repro.serve.engine import Engine, Request
    from repro.tuning import policy
    from repro.tuning.cache import (Measurement, TuningCache,
                                    current_fingerprint)

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=160, vocab=256,
                      head_dim=16, act="swiglu")
    # fabricate a measured ladder (64 B .. ~4 MiB, x4 spacing) where
    # traff_rounds always beats generalized(0): every decode-size query
    # interpolates in-range, so choose() must answer from the table
    fp = current_fingerprint()
    cache = TuningCache()
    for k in range(9):
        nb = 64 * 4 ** k
        cache.record(fp, Measurement(2, nb, "generalized", 0, 1, 9.0))
        cache.record(fp, Measurement(2, nb, "traff_rounds", 0, 1, 5.0))
    path = cache.save(os.path.join(
        tempfile.mkdtemp(prefix="repro_serve_tuning_"), "tuning.json"))
    os.environ["REPRO_TUNING_CACHE"] = str(path)
    policy.invalidate()
    try:
        mesh = make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
        pc = parallel_config_for(mesh, param_mode="dp", tuning=True)
        params, _ = init_params(cfg, pc, jax.random.PRNGKey(0))
        eng = Engine(cfg, pc, mesh, params, batch_slots=2, max_len=32,
                     prefill_chunk=8, block_size=4)
        rng = np.random.default_rng(3)
        reqs = [Request(prompt=rng.integers(0, cfg.vocab, n)
                        .astype(np.int32), max_new_tokens=4)
                for n in (3, 9, 5, 12, 7)]
        eng.generate(reqs)
        for r in reqs:
            assert r.done and len(r.out_tokens) == 4, r
        for m in eng.kv:
            m.check()
            assert m.n_used == 0
        choices = eng.decode_choices
        assert choices, "decode collectives must trace through choose()"
        ops = {op for op, _, _ in choices}
        assert ops == {"psum", "all_gather"}, ops
        for op, nbytes, c in choices:
            assert c.source == "measured", (op, nbytes, c)
        psum_kinds = {c.kind for op, _, c in choices if op == "psum"}
        assert psum_kinds == {"traff_rounds"}, psum_kinds
        # batched continuous decode == solo B=1 path, same compiled step
        solo = Engine(cfg, pc, mesh, params, batch_slots=1, max_len=32,
                      prefill_chunk=8, block_size=4, bundle=eng.bundle)
        for r in reqs:
            r2 = Request(prompt=r.prompt, max_new_tokens=4)
            solo.generate([r2])
            assert r2.out_tokens == r.out_tokens, \
                (len(r.prompt), r.out_tokens, r2.out_tokens)
    finally:
        os.environ.pop("REPRO_TUNING_CACHE", None)
        policy.invalidate()
    print("ok serve")


def check_conformance():
    """Acceptance sweep vs the real lax references, P in {2,3,5,6,7,8,16}
    on meshes over the first P of 16 forced host devices: max/min/mean
    allreduce (the traff_rounds and dual_root families included) and
    both all-to-all kinds, divisible and ragged sizes, each bit-exact vs
    lax.pmax / lax.pmin / lax.psum / lax.all_to_all."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 16:
        print("ok conformance (skipped: needs 16 devices)")
        return
    rng = np.random.default_rng(42)
    for n in (2, 3, 5, 6, 7, 8, 16):
        mesh = Mesh(np.array(devs[:n]), ("data",))
        for m in (3 * n, 3 * n + 1, 1, max(n - 1, 1)):
            x = rng.integers(-1000, 1000, (n, m)).astype(np.int32)
            r = max_r(n) if m % n else 0
            sched = build_generalized(n, r)
            # the skew-sorted kind under an adversarial relabeling: same
            # compiled structure replayed on permuted devices, must stay
            # bit-exact vs lax.psum on the real mesh
            order = tuple(np.roll(np.arange(n)[::-1], 1).tolist())
            sorted_sched = build_sorted_generalized(n, r, order)
            traff = build_traff_rounds(n)
            dual = build_dual_root(n)
            nb = 2 if m > n else 1
            a2a = m % n == 0

            def f(v, s=sched, ss=sorted_sched, tr=traff, du=dual,
                  nb=nb, n=n, a2a=a2a):
                vi = v[0]
                vf = vi.astype(jnp.float32)
                outs = [
                    allreduce_flat(vi, "data", s, combine="sum",
                                   n_buckets=nb),
                    lax.psum(vi, "data"),
                    allreduce_flat(vi, "data", s, combine="max"),
                    lax.pmax(vi, "data"),
                    allreduce_flat(vi, "data", s, combine="min"),
                    lax.pmin(vi, "data"),
                    allreduce_flat(vf, "data", s, combine="mean"),
                    lax.psum(vf, "data") / n,
                    allreduce_flat(vi, "data", ss, combine="sum",
                                   n_buckets=nb),
                    allreduce_flat(vi, "data", tr, combine="sum",
                                   n_buckets=nb),
                    allreduce_flat(vi, "data", du, combine="sum",
                                   n_buckets=nb),
                    allreduce_flat(vi, "data", tr, combine="max"),
                    allreduce_flat(vi, "data", du, combine="min",
                                   n_buckets=1),
                ]
                if a2a:
                    outs += [
                        all_to_all_flat(vi, "data", kind="direct"),
                        all_to_all_flat(vi, "data", kind="bruck"),
                        lax.all_to_all(vi.reshape(n, -1), "data", 0,
                                       0).reshape(-1),
                    ]
                return [o[None] for o in outs]

            n_out = 16 if a2a else 13
            g = jax.jit(shard_map(
                f, mesh=mesh, in_specs=P("data", None),
                out_specs=[P("data", None)] * n_out))
            outs = [np.asarray(o) for o in g(x)]
            pairs = [("sum", 0, 1), ("max", 2, 3), ("min", 4, 5),
                     ("mean", 6, 7), ("sorted_sum", 8, 1),
                     ("traff_sum", 9, 1), ("dual_sum", 10, 1),
                     ("traff_max", 11, 3), ("dual_min", 12, 5)]
            if a2a:
                pairs += [("a2a_direct", 13, 15), ("a2a_bruck", 14, 15)]
            for name, i, j in pairs:
                assert (outs[i] == outs[j]).all(), (n, m, name)
            assert (outs[0][0] == x.sum(0)).all(), (n, m)
            assert (outs[2][0] == x.max(0)).all(), (n, m)
        print(f"ok conformance P={n}")
    print("ok conformance")


def check_overlap():
    """Backward-overlapped bucketed gradient sync: the bit-exactness
    gate of the overlap design.  For dense, scan-stacked (deep cycles)
    and MoE archs on the 8-device mesh, three train-step arms run 3
    steps from identical initial state:

    * ``whole``    -- overlap off (one post-backward tree allreduce);
    * ``post``     -- reverse-layer buckets synced after the backward;
    * ``backward`` -- the same buckets dispatched in-backward via the
      ``custom_vjp`` markers.

    post and backward run *identical* per-bucket collectives over
    identical leaf lists -- only dispatch timing differs -- so their
    fp32 params must match bit-for-bit every step.  whole-vs-bucketed
    changes the element->chunk assignment (different fp32 association),
    so it is held to allclose, not bit equality."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.models.config import ModelConfig, MoEConfig
    from repro.models.model import init_params
    from repro.parallel.api import ParallelConfig
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.step import make_train_step

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n, 1), ("data", "model"))
    configs = {
        "dense": ModelConfig(name="t", family="dense", n_layers=2,
                             d_model=64, n_heads=4, n_kv_heads=2,
                             d_ff=160, vocab=256, head_dim=16,
                             act="swiglu"),
        "scan": ModelConfig(name="t", family="dense", n_layers=6,
                            d_model=48, n_heads=4, n_kv_heads=4,
                            d_ff=96, vocab=128, head_dim=12,
                            act="swiglu"),
        "moe": ModelConfig(name="t", family="moe", n_layers=2,
                           d_model=32, n_heads=4, n_kv_heads=4,
                           d_ff=48, vocab=128,
                           moe=MoEConfig(n_experts=2 * n, top_k=2,
                                         d_expert=48)),
    }
    oc = OptConfig(lr=1e-3)
    rng = np.random.default_rng(7)
    for arch, cfg in configs.items():
        tok = rng.integers(0, cfg.vocab, (n, 16)).astype(np.int32)
        lab = rng.integers(0, cfg.vocab, (n, 16)).astype(np.int32)
        batch = {"tokens": tok, "labels": lab}
        arms = {"whole": dict(overlap_bucket_bytes=None),
                "post": dict(overlap_bucket_bytes=32 << 10,
                             overlap_dispatch="post"),
                "backward": dict(overlap_bucket_bytes=32 << 10,
                                 overlap_dispatch="backward")}
        state = {}
        for name, kw in arms.items():
            pc = ParallelConfig(dp=n, tp=1, param_mode="dp", **kw)
            bundle = make_train_step(cfg, pc, mesh, oc, donate=False)
            params, _ = init_params(cfg, pc, jax.random.PRNGKey(0))
            opt = init_opt_state(params, pc=pc, specs=bundle.specs)
            losses = []
            for _ in range(3):
                params, opt, metrics = bundle.train_step(params, opt,
                                                         batch)
                losses.append(float(metrics["loss"]))
            state[name] = (jax.device_get(params), losses)
        p_bwd, l_bwd = state["backward"]
        p_post, l_post = state["post"]
        p_whole, _ = state["whole"]
        for (pa, pb) in zip(jax.tree.leaves(p_bwd),
                            jax.tree.leaves(p_post)):
            assert pa.dtype == jnp.float32, pa.dtype
            assert (np.asarray(pa) == np.asarray(pb)).all(), \
                f"{arch}: backward vs post params not bit-identical"
        assert l_bwd == l_post, (arch, l_bwd, l_post)
        for (pa, pw) in zip(jax.tree.leaves(p_bwd),
                            jax.tree.leaves(p_whole)):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pw),
                                       rtol=2e-5, atol=2e-5)
        print(f"ok overlap {arch}")
    print("ok overlap")


def check_grad_interleave():
    """Satellite regression for the fsdp hybrid re-assembly in
    sync_grads_dp: a grads tree whose *tree-flatten order interleaves*
    fsdp-sharded and dp-replicated leaves must come back with every
    leaf matched to its own ParamSpec -- sharded leaves divided by dp
    (their VJP already reduce-scattered a DP sum), replicated leaves
    allreduced to the DP mean, and no cross-pairing between the two."""
    from jax.sharding import Mesh

    from repro.parallel.api import ParallelConfig, ParamSpec
    from repro.train.step import sync_grads_dp

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    pc = ParallelConfig(dp_axes=("data",), dp=n, tp=1, param_mode="fsdp")
    rng = np.random.default_rng(11)
    # alphabetical flatten order a,b,c,d,e interleaves the two kinds
    specs = {"a": ParamSpec(),                 # replicated
             "b": ParamSpec(fsdp_dim=0),       # sharded
             "c": {"w": ParamSpec(),           # replicated (nested)
                   "x": ParamSpec(fsdp_dim=1)},
             "d": ParamSpec(fsdp_dim=0),
             "e": ParamSpec()}
    shapes = {"a": (3,), "b": (2, 5), "c": {"w": (4,), "x": (2, 2)},
              "d": (6,), "e": (2, 3)}
    full = jax.tree.map(
        lambda shp: rng.standard_normal((n,) + shp).astype(np.float32),
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def f(g):
        g = jax.tree.map(lambda v: v[0], g)
        out = sync_grads_dp(g, specs, pc)
        return jax.tree.map(lambda v: v[None], out)

    pspecs = jax.tree.map(lambda _: P("data"), shapes,
                          is_leaf=lambda x: isinstance(x, tuple))
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(pspecs,),
                          out_specs=pspecs))
    out = jax.device_get(g(full))
    flat_out, _ = jax.tree.flatten(out)
    flat_in, _ = jax.tree.flatten(full)
    flat_specs = [specs["a"], specs["b"], specs["c"]["w"],
                  specs["c"]["x"], specs["d"], specs["e"]]
    for got, x, sp in zip(flat_out, flat_in, flat_specs):
        if sp.fsdp_dim is not None:
            want = x / n                  # per-device sum -> mean
        else:
            want = np.broadcast_to(x.mean(0), x.shape)  # DP mean
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    print("ok grad_interleave")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    checks = dict(allreduce=check_allreduce_flat, psum=check_vs_psum,
                  rsag=check_rs_ag, multiaxis=check_multiaxis,
                  zero=check_tree_zero, hier=check_hierarchical,
                  execplan=check_execplan, ragged=check_ragged,
                  a2a=check_a2a, maxreduce=check_maxreduce,
                  moe=check_moe_dispatch, conformance=check_conformance,
                  elastic_resize=check_elastic_resize, serve=check_serve,
                  overlap=check_overlap,
                  grad_interleave=check_grad_interleave)
    if which == "all":
        for fn in checks.values():
            fn()
    else:
        checks[which]()
    print("ALL-OK")
