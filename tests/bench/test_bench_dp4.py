"""The ``granite-8b-train.dp4`` cell at test size: its traffic, and a
whole run on four forced host devices, the ExecPlan gradient allreduce
included."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import harness as H

_RUN = """
import sys, time
sys.path.insert(0, {tests!r})
import bench_tiny, harness as H, jax
r = H.run_cell(bench_tiny.cell("granite-8b-train.dp4"), 2**33 + 9, 1.0,
               False, time.perf_counter(), devices=jax.devices()[:4])
print("CORRECT", r["correct"], "ATTEMPTED", r["attempted"])
"""


def test_dp4_batches_are_one_row_per_chip_and_seeded():
    m = H.load_cell("granite-8b-train.dp4").traffic
    gen = H.traffic_kind(m)
    t0, l0 = gen.batch(m, 2**33 + 3, 0, 49152)
    t1, _ = gen.batch(m, 2**33 + 3, 0, 49152)
    assert t0.shape == (4, 4096) and np.array_equal(t0, t1)
    assert np.array_equal(t0[:, 1:], l0[:, :-1])
    assert len({row.tobytes() for row in t0}) == 4


def test_a_dp4_run_is_correct_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _RUN.format(
            tests=os.path.dirname(os.path.abspath(__file__)))],
        env=env, capture_output=True, text=True, timeout=600)
    assert "CORRECT True" in out.stdout, out.stderr[-3000:]
