"""Measured-autotuning subsystem: cache persistence, fingerprint gating,
corrupt-file recovery, nearest-size interpolation, and the
measurement-beats-model wiring of ``autotune.choose``."""

import json

import pytest

from repro.core.autotune import choose
from repro.core.cost_model import HOST_CPU
from repro.tuning import (
    Fingerprint,
    Measurement,
    TuningCache,
    best_measured,
    current_fingerprint,
    policy,
)
from repro.tuning import cache as cache_mod

FP = Fingerprint(
    platform="cpu",
    device_kind="cpu",
    device_count=8,
    jax_version="0.0.test",
    package_version="0.0.test",
)
OTHER_FP = Fingerprint(
    platform="tpu",
    device_kind="v5e",
    device_count=256,
    jax_version="0.0.test",
    package_version="0.0.test",
)


def meas(nbytes, kind, r, b, us, P=8):
    return Measurement(P=P, nbytes=nbytes, kind=kind, r=r, n_buckets=b, us=us)


@pytest.fixture
def tuned_env(tmp_path, monkeypatch):
    """Point the tuning subsystem at a throwaway cache file and reset all
    in-process caches on entry and exit."""
    path = tmp_path / "tuning.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    monkeypatch.delenv("REPRO_TUNING", raising=False)
    policy.invalidate()
    yield path
    policy.invalidate()


# ---------------------------------------------------------------------------
#  cache persistence
# ---------------------------------------------------------------------------


def test_cache_roundtrip(tuned_env):
    c = TuningCache.load(tuned_env)
    c.record(FP, meas(1 << 20, "generalized", 1, 2, 123.4))
    c.record(FP, meas(1 << 20, "ring", 0, 1, 456.7))
    saved = c.save()
    assert saved == tuned_env and tuned_env.exists()
    # atomic write leaves no temp droppings
    assert list(tuned_env.parent.glob("*.tmp")) == []

    back = TuningCache.load(tuned_env)
    assert back.n_measurements == 2
    assert sorted(back.lookup(FP, 8), key=lambda m: m.us) == sorted(
        c.lookup(FP, 8), key=lambda m: m.us
    )


def test_record_replaces_same_grid_point(tuned_env):
    c = TuningCache.load(tuned_env)
    c.record(FP, meas(1 << 20, "ring", 0, 1, 100.0))
    c.record(FP, meas(1 << 20, "ring", 0, 1, 50.0))
    assert c.n_measurements == 1
    assert c.lookup(FP, 8)[0].us == 50.0


def test_fingerprint_mismatch_invalidates(tuned_env):
    c = TuningCache.load(tuned_env)
    c.record(FP, meas(1 << 20, "ring", 0, 1, 100.0))
    c.save()
    back = TuningCache.load(tuned_env)
    assert back.lookup(OTHER_FP, 8) == []
    assert policy.lookup(8, 1 << 20, fingerprint=OTHER_FP) is None
    assert policy.lookup(8, 1 << 20, fingerprint=FP) is not None


@pytest.mark.parametrize(
    "content",
    [
        "not json at all {",
        '{"version": 1, "entries": {"x": {"fingerpr',  # truncated mid-write
        '{"version": 99, "entries": {}}',  # future schema
        '{"version": 1, "entries": {"k": {"fingerprint": {"bogus": 1},'
        ' "measurements": []}}}',  # wrong shape
        "[]",  # wrong top-level type
    ],
)
def test_corrupt_cache_recovers_empty(tuned_env, content):
    tuned_env.write_text(content)
    c = TuningCache.load(tuned_env)
    assert c.n_measurements == 0
    # the corrupt file was quarantined, so the next save starts clean
    assert not tuned_env.exists()
    assert tuned_env.with_suffix(".json.corrupt").exists()
    c.record(FP, meas(1 << 20, "ring", 0, 1, 1.0))
    c.save()
    assert TuningCache.load(tuned_env).n_measurements == 1


def test_cache_version_field_written(tuned_env):
    c = TuningCache.load(tuned_env)
    c.record(FP, meas(1 << 20, "ring", 0, 1, 1.0))
    c.save()
    raw = json.loads(tuned_env.read_text())
    assert raw["version"] == cache_mod.SCHEMA_VERSION


# ---------------------------------------------------------------------------
#  policy: nearest-size interpolation
# ---------------------------------------------------------------------------


def test_interpolation_picks_crossing_winner():
    # candidate A wins at 64 KiB, candidate B wins at 4 MiB; the crossover
    # sits between, so the interpolated argmin flips with the query size
    rows = [
        meas(64 << 10, "generalized", 3, 1, 10.0),
        meas(4 << 20, "generalized", 3, 1, 500.0),
        meas(64 << 10, "ring", 0, 1, 100.0),
        meas(4 << 20, "ring", 0, 1, 120.0),
    ]
    small = best_measured(rows, 80 << 10)
    big = best_measured(rows, 3 << 20)
    assert (small.kind, small.r) == ("generalized", 3)
    assert big.kind == "ring"
    # measured cost is interpolated, not copied from an endpoint
    mid = best_measured(rows, 512 << 10)
    assert 10e-6 < mid.cost < 500e-6
    assert mid.source == "measured"


def test_extrapolation_bounded():
    rows = [meas(64 << 10, "ring", 0, 1, 10.0)]
    # within 4x of the only measured size: nearest measurement answers
    assert best_measured(rows, 128 << 10) is not None
    # far outside: the table has no opinion
    assert best_measured(rows, 1 << 30) is None
    assert best_measured(rows, 1 << 10) is None


# ---------------------------------------------------------------------------
#  choose() wiring: measurement-backed vs analytic fallback
# ---------------------------------------------------------------------------


def _flip_cache(path, nbytes=1 << 20):
    """Write a synthetic cache whose winner differs from the model pick."""
    model = choose(8, nbytes, HOST_CPU, tune=False)
    flipped_kind = "ring" if model.kind != "ring" else "generalized"
    flipped_r = 0 if model.kind != "ring" else 2
    c = TuningCache.load(path)
    fp = current_fingerprint()
    for size in (nbytes // 4, nbytes * 4):
        c.record(fp, meas(size, flipped_kind, flipped_r, 2, us=10.0))
        c.record(fp, meas(size, model.kind, model.r, model.n_buckets, us=900.0))
    c.save()
    policy.invalidate()
    return model, flipped_kind, flipped_r


def test_synthetic_cache_flips_winner(tuned_env):
    model, fkind, fr = _flip_cache(tuned_env)
    tuned = choose(8, 1 << 20, HOST_CPU, tune=True)
    assert tuned.source == "measured"
    assert (tuned.kind, tuned.r, tuned.n_buckets) == (fkind, fr, 2)
    assert (tuned.kind, tuned.r) != (model.kind, model.r)
    # tune=False keeps the analytic answer
    again = choose(8, 1 << 20, HOST_CPU, tune=False)
    assert again.source == "model"
    assert (again.kind, again.r) == (model.kind, model.r)


def test_choose_falls_back_when_cache_empty(tuned_env):
    assert not tuned_env.exists()
    ch = choose(8, 1 << 20, HOST_CPU, tune=True)
    assert ch.source == "model"


def test_choose_falls_back_outside_measured_range(tuned_env):
    _flip_cache(tuned_env)
    far = choose(8, 1 << 30, HOST_CPU, tune=True)
    assert far.source == "model"


def test_allow_ring_respected_when_tuned(tuned_env):
    # the cache says ring is fastest, but the caller excluded ring: the
    # measured answer must honor the schedule-family restriction
    c = TuningCache.load(tuned_env)
    fp = current_fingerprint()
    for size in (256 << 10, 4 << 20):
        c.record(fp, meas(size, "ring", 0, 1, us=1.0))
        c.record(fp, meas(size, "generalized", 1, 1, us=5.0))
    c.save()
    policy.invalidate()
    ch = choose(8, 1 << 20, HOST_CPU, allow_ring=False, tune=True)
    assert ch.source == "measured"
    assert ch.kind == "generalized"
    assert choose(8, 1 << 20, HOST_CPU, allow_ring=True, tune=True).kind == "ring"


def test_env_var_opt_in(tuned_env, monkeypatch):
    _flip_cache(tuned_env)
    # default (no env, tune=None) stays analytic
    assert choose(8, 1 << 20, HOST_CPU).source == "model"
    monkeypatch.setenv("REPRO_TUNING", "1")
    assert choose(8, 1 << 20, HOST_CPU).source == "measured"


def test_choose_collective_consults_policy(tuned_env):
    from repro.topology import MULTI_POD_2X256, choose_collective, v5e_pod

    _flip_cache(tuned_env)
    flat = choose_collective(v5e_pod(8), 1 << 20, tune=True)
    assert flat.source == "measured"
    assert flat.kind in ("flat-ring", "flat-generalized")
    # the model's verdict is untouched without tuning
    assert choose_collective(v5e_pod(8), 1 << 20, tune=False).source == "model"
    # multi-level fabrics have no compatible flat measurement: model decides
    hier = choose_collective(MULTI_POD_2X256, 1 << 20, tune=True)
    assert hier.source == "model"


@pytest.mark.xdist_group("subprocess")
def test_tuned_choice_executes_correctly(tuned_env, tmp_path):
    """End to end: a measured Choice coming out of the cache drives the
    real shard_map executor and still reduces correctly (2 forced host
    devices; the synthetic cache pins an off-model candidate)."""
    import os
    import subprocess
    import sys

    import jax

    from repro.tuning.cache import _package_version

    fp = Fingerprint(
        platform="cpu",
        device_kind="cpu",
        device_count=2,
        jax_version=jax.__version__,
        package_version=_package_version(),
    )
    c = TuningCache.load(tuned_env)
    for size in (64 << 10, 1 << 20):
        c.record(fp, meas(size, "ring", 0, 1, us=1.0, P=2))
    c.save()

    prog = """
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.allreduce import allreduce_tree
from repro.core.autotune import choose

ch = choose(2, 256 << 10, tune=True)
assert ch.source == "measured" and ch.kind == "ring", ch
mesh = jax.make_mesh((2,), ("data",))
x = np.random.default_rng(0).standard_normal((2, 65536)).astype(np.float32)
fn = jax.jit(shard_map(
    lambda v: allreduce_tree(v[0], "data", tune=True)[None],
    mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))
ref = jax.jit(shard_map(
    lambda v: lax.psum(v, "data"), mesh=mesh,
    in_specs=P("data", None), out_specs=P(None, None)))
np.testing.assert_allclose(np.asarray(fn(x))[0], np.asarray(ref(x))[0],
                           rtol=1e-6, atol=1e-6)
print("TUNED_EXEC_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_TUNING_CACHE"] = str(tuned_env)
    # the child doesn't go through pytest's pythonpath handling
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True, text=True
    )
    assert res.returncode == 0, res.stderr[-4000:]
    assert "TUNED_EXEC_OK" in res.stdout


def test_measure_grid_prunes_tiny_buckets():
    from repro.tuning import candidate_grid

    grid = candidate_grid(8, 64 << 10, smoke=True)
    assert all(b == 1 for _, _, b in grid)  # 8 KiB chunks: no pipelining
    grid_big = candidate_grid(8, 4 << 20, smoke=False)
    assert {b for _, _, b in grid_big} == {1, 2, 4}
    kinds = {(k, r) for k, r, _ in grid_big}
    assert ("ring", 0) in kinds and ("generalized", 0) in kinds


# ---------------------------------------------------------------------------
#  schema v2: per-rep timings, noise, arrival skew
# ---------------------------------------------------------------------------


def test_measurement_v2_fields_roundtrip(tuned_env):
    m = Measurement(
        P=8,
        nbytes=1 << 20,
        kind="generalized",
        r=1,
        n_buckets=2,
        us=100.0,
        reps_us=(110.0, 100.0, 130.0),
        noise=0.3,
        skew_us=42.5,
    )
    c = TuningCache.load(tuned_env)
    c.record(FP, m)
    c.save()
    back = TuningCache.load(tuned_env).lookup(FP, 8)[0]
    assert back.reps_us == (110.0, 100.0, 130.0)
    assert back.noise == 0.3
    assert back.skew_us == 42.5
    assert json.loads(tuned_env.read_text())["version"] == 2


def test_cache_v1_file_loads_with_defaults(tuned_env):
    """A v1 cache (pre reps/noise/skew) must load, not quarantine."""
    c = TuningCache.load(tuned_env)
    c.record(FP, meas(1 << 20, "ring", 0, 1, 77.0))
    c.save()
    raw = json.loads(tuned_env.read_text())
    raw["version"] = 1
    for entry in raw["entries"].values():
        for m in entry["measurements"]:
            for k in ("reps_us", "noise", "skew_us"):
                m.pop(k, None)
    tuned_env.write_text(json.dumps(raw))
    back = TuningCache.load(tuned_env)
    assert back.n_measurements == 1
    m = back.lookup(FP, 8)[0]
    assert m.us == 77.0
    assert m.reps_us is None and m.noise == 0.0 and m.skew_us is None
    # re-saving migrates the file to the current schema
    back.save()
    assert json.loads(tuned_env.read_text())["version"] == cache_mod.SCHEMA_VERSION


def test_unstable_cells_flags_noisy_measurements():
    from repro.tuning.policy import NOISE_THRESHOLD, unstable_cells

    quiet = Measurement(
        P=8, nbytes=1 << 20, kind="ring", r=0, n_buckets=1, us=100.0, noise=0.05
    )
    noisy = Measurement(
        P=8,
        nbytes=1 << 20,
        kind="generalized",
        r=2,
        n_buckets=2,
        us=50.0,
        reps_us=(50.0, 80.0),
        noise=0.6,
    )
    noisier = Measurement(
        P=8, nbytes=64 << 10, kind="ring", r=0, n_buckets=1, us=10.0, noise=0.9
    )
    out = unstable_cells([quiet, noisy, noisier])
    assert [c["noise"] for c in out] == [0.9, 0.6]  # worst first
    assert out[1]["kind"] == "generalized" and out[1]["reps_us"] == [50.0, 80.0]
    assert unstable_cells([quiet]) == []
    assert 0.0 < NOISE_THRESHOLD < 1.0


# ---------------------------------------------------------------------------
#  arrival deltas: persistence and the skew-aware choose() feed
# ---------------------------------------------------------------------------


def test_deltas_roundtrip_and_arrival_deltas(tuned_env):
    deltas = (0.0, 12.0, 3.0, 250.0, 1.0, 0.5, 9.0, 40.0)
    m = Measurement(
        P=8,
        nbytes=1 << 20,
        kind="generalized",
        r=1,
        n_buckets=1,
        us=100.0,
        skew_us=250.0,
        deltas_us=deltas,
    )
    c = TuningCache.load(tuned_env)
    c.record(FP, m)
    c.save()
    assert TuningCache.load(tuned_env).lookup(FP, 8)[0].deltas_us == deltas
    policy.invalidate()
    # nearest-size answer, within the extrapolation cap
    assert policy.arrival_deltas(8, 1 << 20, fingerprint=FP) == deltas
    assert policy.arrival_deltas(8, 2 << 20, fingerprint=FP) == deltas
    # beyond the cap / wrong operator / wrong P: no opinion
    assert policy.arrival_deltas(8, 1 << 30, fingerprint=FP) is None
    assert policy.arrival_deltas(8, 1 << 20, op="max", fingerprint=FP) is None
    assert policy.arrival_deltas(4, 1 << 20, fingerprint=FP) is None


def test_arrival_deltas_ignores_rows_without_profile(tuned_env):
    c = TuningCache.load(tuned_env)
    c.record(FP, meas(1 << 20, "ring", 0, 1, 50.0))  # scalar-only row
    c.save()
    policy.invalidate()
    assert policy.arrival_deltas(8, 1 << 20, fingerprint=FP) is None


def test_skewed_cells_flags_heavy_skew():
    from repro.tuning.policy import SKEW_THRESHOLD_US, skewed_cells

    calm = Measurement(
        P=8, nbytes=1 << 20, kind="ring", r=0, n_buckets=1, us=100.0, skew_us=5.0
    )
    unprobed = meas(1 << 20, "generalized", 0, 1, 90.0)
    skewed = Measurement(
        P=8,
        nbytes=1 << 20,
        kind="generalized",
        r=2,
        n_buckets=1,
        us=50.0,
        skew_us=400.0,
        deltas_us=(0.0,) * 7 + (400.0,),
    )
    worse = Measurement(
        P=8, nbytes=64 << 10, kind="ring", r=0, n_buckets=1, us=10.0, skew_us=900.0
    )
    out = skewed_cells([calm, unprobed, skewed, worse])
    assert [c["skew_us"] for c in out] == [900.0, 400.0]  # worst first
    assert out[1]["deltas_us"] == [0.0] * 7 + [400.0]
    assert skewed_cells([calm, unprobed]) == []
    assert SKEW_THRESHOLD_US > 0


# ---------------------------------------------------------------------------
#  CI family-coverage gate (benchmarks/check_regression.py --families)
# ---------------------------------------------------------------------------


def _tuning_payload(path, kinds_per_row):
    """Write a minimal results/tuning.json-shaped payload."""
    payload = {
        "results": [
            {
                "label": f"row{i}",
                "measurements": [
                    {"P": 8, "nbytes": 1 << 20, "kind": k, "r": 0, "n_buckets": 1}
                    for k in kinds
                ],
            }
            for i, kinds in enumerate(kinds_per_row)
        ]
    }
    path.write_text(json.dumps(payload))
    return path


def test_family_gate_passes_and_fails(tmp_path):
    """The --families gate holds the measured family set: a doctored
    baseline carrying a family the current run never measures must exit
    2 (MISWIRED) and name the missing family; full coverage passes."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
    from check_regression import load_families, main

    base = _tuning_payload(
        tmp_path / "base.json",
        [["generalized", "ring"], ["traff_rounds", "dual_root"]],
    )
    cur_ok = _tuning_payload(
        tmp_path / "cur_ok.json",
        [["generalized", "ring", "traff_rounds", "dual_root", "extra_kind"]],
    )
    assert load_families(base) == {"generalized", "ring", "traff_rounds", "dual_root"}

    verdict = tmp_path / "verdict.json"
    argv = ["--families", "--baseline", str(base), "--json", str(verdict)]
    # full coverage (extra current-only families are fine): pass
    assert main(argv + ["--current", str(cur_ok)]) == 0
    assert json.loads(verdict.read_text())["verdict"] == "OK"

    # doctored current drops dual_root from the candidate grid: MISWIRED
    cur_bad = _tuning_payload(
        tmp_path / "cur_bad.json", [["generalized", "ring", "traff_rounds"]]
    )
    assert main(argv + ["--current", str(cur_bad)]) == 2
    out = json.loads(verdict.read_text())
    assert out["verdict"] == "MISWIRED"
    assert out["missing_families"] == ["dual_root"]

    # a baseline that measures nothing is a mis-wired gate, not a pass
    empty = _tuning_payload(tmp_path / "empty.json", [])
    assert main(["--families", "--baseline", str(empty), "--current", str(cur_ok)]) == 2


def test_committed_tuning_table_has_competing_families():
    """The committed table the CI gate treats as source of truth must
    itself measure every family in the candidate grid, and at least two
    distinct families must win cells (the point of the competition)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
    from check_regression import load_families

    path = os.path.join(os.path.dirname(__file__), "..", "results", "tuning.json")
    assert load_families(path) >= {"generalized", "ring", "traff_rounds", "dual_root"}
    with open(path) as f:
        payload = json.load(f)
    winners = {row["measured_winner"]["kind"] for row in payload["results"]}
    assert len(winners) >= 2, winners


def test_choose_uses_persisted_deltas_when_tuned(tuned_env):
    """A heavy arrival profile persisted by the tuning grid flips a tuned
    choose() onto the skew timeline even when the caller passes no live
    deltas; without tuning the same query stays analytic."""
    from repro.core.cost_model import TPU_V5E_ICI

    fp = current_fingerprint()
    c = TuningCache.load(tuned_env)
    c.record(
        fp,
        Measurement(
            P=8,
            nbytes=512,
            kind="generalized",
            r=3,
            n_buckets=1,
            us=30.0,
            itemsize=4,
            skew_us=300.0,
            deltas_us=(0.0,) * 7 + (300.0,),
        ),
    )
    c.save()
    policy.invalidate()
    ch = choose(8, 512, TPU_V5E_ICI, tune=True)
    assert ch.source == "skew"
    assert choose(8, 512, TPU_V5E_ICI, tune=False).source == "model"


# ---------------------------------------------------------------------------
#  class boundaries at the extrapolation edge + overlap-hinted queries
# ---------------------------------------------------------------------------


def test_out_of_range_query_with_only_wrong_op_neighbors_is_none():
    # the sum-op class was measured only at 16 KiB; a 4 MiB sum query is
    # 256x past it.  The max-op class has a 1 MiB neighbor within the 4x
    # window -- it must NOT answer the sum query: class filtering happens
    # before size bracketing, so the analytic model decides (None)
    rows = [
        Measurement(P=8, nbytes=16 << 10, kind="ring", r=0, n_buckets=1, us=10.0),
        Measurement(
            P=8, nbytes=1 << 20, kind="ring", r=0, n_buckets=1, us=90.0, op="max"
        ),
    ]
    near_max = best_measured(rows, 4 << 20, op="max")
    assert near_max is not None and near_max.source == "measured"
    assert best_measured(rows, 4 << 20, op="sum") is None
    # same guard for the element-ragged class: a ragged query (8193 f32
    # elements over P=8, well inside the sum row's 4x size window)
    # cannot borrow the divisible-geometry neighbor
    assert best_measured(rows, 8193 * 4, itemsize=4, op="sum") is None


def test_overlap_hinted_query_bypasses_measured_table(tuned_env):
    # the table would answer (and flip the winner) for a plain query...
    _flip_cache(tuned_env)
    assert choose(8, 1 << 20, HOST_CPU, tune=True).source == "measured"
    # ...but the grid times standalone collectives with no compute
    # running, so an overlap-hinted query is never answered from it:
    # both the policy layer and the tuned choose() fall back to the
    # model's exposed-cost ranking
    assert policy.lookup(8, 1 << 20, compute_overlap_us=1e3) is None
    hinted = choose(8, 1 << 20, HOST_CPU, tune=True, compute_overlap_us=1e3)
    assert hinted.source == "model"
    raw = choose(8, 1 << 20, HOST_CPU, tune=False).cost
    assert 0.0 <= hinted.cost <= raw
