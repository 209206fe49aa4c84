"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler ships with jaxlib and compiles for a topology that is
described rather than attached, so these tests refuse what the chip's
compiler would refuse (VMEM overflow, unaligned tiles, a kernel that
cannot trace under ``shard_map``'s checker) at no chip time.  Nothing
runs: results and timings come only from ``python chip_smoke.py`` on the
chip.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.allreduce import allreduce_flat
from repro.core.schedule import build_generalized
from repro.kernels import ops as kernel_ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_combine import combine_n
from repro.kernels.rmsnorm import rmsnorm

# 64 MiB of fp32 gradients: the gradient-bucket size of a data-parallel step
BUCKET_F32 = 16 * 1024 * 1024
# the same bucket in bf16, plus a ragged tail that is no multiple of 128
BUCKET_BF16 = 32 * 1024 * 1024 + 37


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype,n", [(jnp.float32, BUCKET_F32),
                                     (jnp.bfloat16, BUCKET_BF16)])
def test_combine_n_compiles(one_chip, dtype, n):
    hlo = _hlo(lambda s: combine_n(s, accum_dtype=jnp.float32),
               _sds((2, n), dtype, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_compiles_at_granite_width(one_chip, dtype):
    hlo = _hlo(rmsnorm, _sds((4096, 4096), dtype, one_chip),
               _sds((4096,), dtype, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("sq,sk", [(2048, 2048),    # granite prefill
                                   (1, 4096)])      # decode on a 4k cache
def test_flash_attention_compiles_at_granite_shapes(one_chip, sq, sk):
    q = _sds((1, 32, sq, 128), jnp.bfloat16, one_chip)   # 32 q heads
    kv = _sds((1, 8, sk, 128), jnp.bfloat16, one_chip)   # 8 kv heads
    hlo = _hlo(lambda q, k, v: flash_attention(q, k, v, causal=sq > 1),
               q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_execplan_pallas_combine_under_default_checker(topo, monkeypatch):
    """A 4-chip ExecPlan allreduce of a 64 MiB fp32 gradient bucket routed
    through the Pallas combine, inside a ``jax.shard_map`` that keeps its
    default ``check_vma=True``: the kernel must trace under the checker
    and reach the compiled HLO.  At this size the executor's flat row
    layout matters: the TPU compiler's time for row slices of 2-D
    buffers grows with their size, to about a minute at this one."""
    # the described chip is not the default backend: steer the one
    # interpret/Mosaic switch the way it reads on a TPU
    monkeypatch.setattr(kernel_ops, "interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    sched = build_generalized(4, 1)
    f = jax.shard_map(
        lambda v: allreduce_flat(v[0], "data", sched, combine="pallas")[None],
        mesh=mesh, in_specs=P("data", None), out_specs=P("data", None))
    x = _sds((4, BUCKET_F32), jnp.float32,
             NamedSharding(mesh, P("data", None)))
    hlo = _hlo(f, x)
    assert "tpu_custom_call" in hlo
    assert "collective-permute" in hlo
