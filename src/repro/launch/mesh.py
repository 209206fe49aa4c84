"""Mesh construction for single-pod and multi-pod deployments.

Production target: TPU v5e pods of 256 chips (16x16).  The single-pod
mesh is ("data", "model") = (16, 16); the multi-pod mesh adds a leading
"pod" axis: (2, 16, 16) = 512 chips.  Data parallelism runs over
("pod", "data") hierarchically: the ParallelConfig carries a
two-level :class:`repro.topology.Topology` (DCN across pods, ICI
inside), so gradient sync composes per-level schedules -- reduce-scatter
on ICI, the generalized allreduce on DCN, all-gather on ICI -- instead
of flattening (pod, data) into one cyclic group whose every shift is
gated by a DCN hop.

All functions build meshes lazily so importing this module never touches
JAX device state (required by the dry-run's XLA_FLAGS bootstrap).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def _axis_kw(n: int) -> dict:
    from jax.sharding import AxisType
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """General mesh helper (smoke tests, elastic re-meshing)."""
    import jax
    from jax.sharding import Mesh
    if devices is not None:
        arr = np.asarray(devices).reshape(tuple(shape))
        return Mesh(arr, tuple(axes), **_axis_kw(len(axes)))
    return jax.make_mesh(tuple(shape), tuple(axes), **_axis_kw(len(axes)))


def parallel_config_for(mesh, *, param_mode: str = "fsdp",
                        grad_r=None, collective_impl: str = "xla",
                        topology=None, tuning: bool = False):
    """Derive the static ParallelConfig from a mesh.

    ``topology`` overrides the fabric hierarchy attached for gradient
    sync (e.g. ``repro.topology.gpu_cluster(...)``); by default a mesh
    with a "pod" axis gets the v5e multi-pod preset (DCN + ICI) sized to
    the mesh.  On hierarchical meshes the autotuner reads per-level
    alpha/beta/gamma from this topology -- not from the flat ``fabric``
    argument of the train-step builder, which only governs single-level
    DP meshes.

    ``tuning=True`` opts gradient-sync schedule choice into the measured
    tuning table (:mod:`repro.tuning`) populated by
    ``python benchmarks/run.py tune``; without a compatible measurement
    the analytic model still decides, so the flag is always safe.
    """
    from repro.parallel.api import ParallelConfig
    from repro.topology.fabric import v5e_multipod
    names = tuple(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    if "pod" in names:
        dp_axes: Tuple[str, ...] = ("pod", "data")
        dp = sizes["pod"] * sizes["data"]
        if topology is None:
            topology = v5e_multipod(pods=sizes["pod"],
                                    chips_per_pod=sizes["data"])
    else:
        dp_axes = ("data",)
        dp = sizes["data"]
    tp = sizes.get("model", 1)
    return ParallelConfig(dp_axes=dp_axes, dp=dp, tp=tp,
                          param_mode=param_mode, grad_r=grad_r,
                          collective_impl=collective_impl,
                          topology=topology, tuning=tuning)
