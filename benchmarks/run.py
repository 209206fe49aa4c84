"""Benchmark harness: one function per paper figure + microbenchmarks.

Prints ``name,us_per_call,derived`` CSV rows (cost-model times are derived
quantities; wall-clock rows come from the 8-virtual-device microbench).

Figures reproduced from the paper (all cost-model driven, validated by the
schedule compiler's exact per-step accounting):
  fig1   -- ratio tau_proposed / tau_best_sota over (P, m)
  fig7   -- small messages,  P=127: proposed vs RD / RH / OpenMPI policy
  fig8   -- large messages,  P=127
  fig9   -- medium messages, P=127: proposed vs RH
  fig10  -- proposed r-sweep at P=127 (bw-opt .. lat-opt envelope)
  fig11  -- vs P at m=425 B (the profiling study's average message)
  fig12  -- vs P at m=9 KB
plus:
  sched  -- compiled-schedule step/traffic counts vs closed forms
  wall   -- real wall-clock of the JAX executor on 8 host devices

Modes (first positional arg): ``figures`` (default), ``executor
[--smoke] [--trace] [--out PATH] [--op sum|max|a2a ...]`` (executor
wallclock comparison incl. max-monoid and all-to-all rows ->
results/executor.json; ``--trace`` additionally runs the instrumented
per-tick replay and writes a Chrome trace + metrics snapshot +
predicted-vs-measured model-error report, see docs/observability.md;
``--overlap`` reroutes to the backward-overlap benchmark: full train
steps differing only in gradient-sync dispatch -> results/overlap.json
with gated ``speedup_overlap`` / ``exposed_ratio`` rows, see
docs/architecture.md "Overlap"),
``tune [--smoke] [--out PATH] [--cache PATH]`` (measured autotuning
grid, sum + max operators -> persistent tuning cache +
results/tuning.json), ``chaos [--smoke] [--trace] [--out PATH]``
(deterministic fault scenarios on the multi-process runtime mesh ->
results/chaos.json; exact recovery_steps rows, gated lower-is-better),
``serve [--smoke] [--trace] [--out PATH]`` (continuous-batching serving
on a dp=2 x tp=2 mesh of 8 simulated CPU devices -> results/serving.json;
p50/p99 TTFT/latency + tokens/sec per offered QPS, gated as
dimensionless ratios vs a same-host solo baseline, see docs/serving.md).

Protocol CSV rows go to stdout via ``repro.obs.log.data``; diagnostics
go to stderr as logfmt lines filtered by ``REPRO_LOG``.

Every mode here runs on the host CPU only: the spawned workers force
``JAX_PLATFORMS=cpu`` (8 forced host devices where a mesh is needed), so
their times are XLA CPU backend times and never device metrics.  Until
an on-chip benchmark mode exists (ROADMAP S1), the chip is reached only
through ``python chip_smoke.py``.
"""
from __future__ import annotations

import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.cost_model import (PAPER_10GE, optimal_r_search,  # noqa: E402
                                   schedule_cost, tau_best_sota,
                                   tau_intermediate, tau_openmpi_policy,
                                   tau_recursive_doubling,
                                   tau_recursive_halving, tau_ring)
from repro.core.schedule import (build_generalized, build_ring,  # noqa: E402
                                 max_r, n_steps_log, schedule_summary)
from repro.obs.log import data, get_logger  # noqa: E402

F = PAPER_10GE

log = get_logger("benchmarks.run")


def _row(name, us, derived=1):
    data(f"{name},{us:.3f},{derived}")


def fig1_ratio_heatmap():
    """Expected tau_proposed / tau_best over P and m (paper Fig. 1)."""
    for P in [15, 31, 63, 127, 255, 511, 1000]:
        for m in [64, 425, 4096, 65536, 1 << 20, 1 << 24]:
            r = optimal_r_search(P, float(m), F)
            ratio = tau_intermediate(P, float(m), r, F) / \
                tau_best_sota(P, float(m), F)
            _row(f"fig1,P={P},m={m},ratio={ratio:.3f}",
                 tau_intermediate(P, float(m), r, F) * 1e6)


def fig7_small_msgs():
    P = 127
    for m in [16, 64, 256, 425, 1024, 4096, 10240]:
        m = float(m)
        r = optimal_r_search(P, m, F)
        _row(f"fig7,m={m:.0f},proposed(r={r})",
             tau_intermediate(P, m, r, F) * 1e6)
        _row(f"fig7,m={m:.0f},openmpi", tau_openmpi_policy(P, m, F) * 1e6)
        _row(f"fig7,m={m:.0f},recursive_halving",
             tau_recursive_halving(P, m, F) * 1e6)


def fig8_large_msgs():
    P = 127
    for m in [1 << 18, 1 << 20, 1 << 22, 1 << 24, 1 << 26]:
        m = float(m)
        r = optimal_r_search(P, m, F)
        _row(f"fig8,m={m:.0f},proposed(r={r})",
             tau_intermediate(P, m, r, F) * 1e6)
        _row(f"fig8,m={m:.0f},ring", tau_ring(P, m, F) * 1e6)
        _row(f"fig8,m={m:.0f},recursive_halving",
             tau_recursive_halving(P, m, F) * 1e6)


def fig9_medium_msgs():
    P = 127
    for m in [16384, 32768, 65536, 131072]:
        m = float(m)
        r = optimal_r_search(P, m, F)
        _row(f"fig9,m={m:.0f},proposed(r={r})",
             tau_intermediate(P, m, r, F) * 1e6)
        _row(f"fig9,m={m:.0f},recursive_halving",
             tau_recursive_halving(P, m, F) * 1e6)


def fig10_r_sweep():
    P = 127
    for m in [425.0, 8192.0, 131072.0]:
        for r in range(n_steps_log(P) + 1):
            _row(f"fig10,m={m:.0f},r={r}",
                 tau_intermediate(P, m, r, F) * 1e6)


def fig11_vs_P_small():
    m = 425.0
    for P in [8, 16, 17, 31, 32, 33, 63, 64, 65, 100, 127, 128, 129, 200]:
        r = optimal_r_search(P, m, F)
        _row(f"fig11,P={P},proposed(r={r})",
             tau_intermediate(P, m, r, F) * 1e6)
        _row(f"fig11,P={P},recursive_doubling",
             tau_recursive_doubling(P, m, F) * 1e6)


def fig12_vs_P_9kb():
    m = 9.0 * 1024
    for P in [16, 32, 33, 64, 100, 127, 128, 200, 256, 300]:
        r = optimal_r_search(P, m, F)
        _row(f"fig12,P={P},proposed(r={r})",
             tau_intermediate(P, m, r, F) * 1e6)
        _row(f"fig12,P={P},ring", tau_ring(P, m, F) * 1e6)
        _row(f"fig12,P={P},recursive_halving",
             tau_recursive_halving(P, m, F) * 1e6)


def sched_table():
    """Exact compiled-schedule accounting vs the paper's closed forms."""
    for P in [7, 8, 12, 127]:
        for r in range(max_r(P) + 1):
            s = schedule_summary(build_generalized(P, r))
            _row(f"sched,P={P},r={r},steps={s['steps']},"
                 f"sent={s['units_sent']},reduced={s['units_reduced']}",
                 schedule_cost(build_generalized(P, r), 425.0, F) * 1e6)
        rg = schedule_summary(build_ring(P))
        _row(f"sched,P={P},ring,steps={rg['steps']},sent={rg['units_sent']}",
             schedule_cost(build_ring(P), 425.0, F) * 1e6)
        from repro.core.schedule import build_bruck_all_gather
        bk = schedule_summary(build_bruck_all_gather(P))
        _row(f"sched,P={P},bruck_allgather,steps={bk['steps']},"
             f"sent={bk['units_sent']}",
             schedule_cost(build_bruck_all_gather(P), 425.0, F) * 1e6)


def _spawn_8dev(script: str, extra_args=(), timeout=1800):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script, *extra_args], env=env,
                          capture_output=True, text=True, timeout=timeout)


def wallclock_8dev():
    """Real wall-clock of the JAX ppermute executor on 8 host devices."""
    _worker_bench("wallclock_worker.py", "wall", (), timeout=900)


def _worker_bench(script_name: str, prefix: str, extra, timeout=1800) -> None:
    """Spawn an 8-host-device benchmark worker, echo its ``prefix,``
    rows, and fail loudly on a non-zero exit."""
    script = os.path.join(os.path.dirname(__file__), script_name)
    res = _spawn_8dev(script, extra, timeout=timeout)
    if res.returncode != 0:
        log.error("worker_failed", worker=script_name,
                  stderr=res.stderr[-2000:])
        raise SystemExit(1)
    # echo the worker's protocol rows; forward its (REPRO_LOG-filtered)
    # stderr diagnostics untouched
    if res.stderr:
        sys.stderr.write(res.stderr)
    for line in res.stdout.strip().splitlines():
        if line.startswith(prefix + ","):
            data(line)


def executor_bench(smoke: bool = False,
                   out: str = "results/executor.json",
                   ops=(), trace: bool = False) -> None:
    """Old per-row replay vs ExecPlan vs pipelined ExecPlan wallclock on
    8 simulated CPU devices (the perf trajectory's BENCH datapoint);
    writes ``results/executor.json``.  ``--op {sum,max,a2a}``
    (repeatable) restricts the benchmark families: ``max`` rows run the
    executors under the max monoid, ``a2a`` rows time the
    schedule-driven all-to-all against ``lax.all_to_all``.  ``--trace``
    additionally runs the instrumented per-tick replay over the bench
    grid and writes ``trace_executor_*.json`` /
    ``metrics_executor_*.json`` / ``model_error_*.md`` next to
    ``--out``."""
    extra = ["--out", out] + (["--smoke"] if smoke else [])
    for op in ops:
        extra += ["--op", op]
    if trace:
        extra += ["--trace"]
    _worker_bench("executor_worker.py", "executor", extra)


def overlap_bench(smoke: bool = False,
                  out: str = "results/overlap.json") -> None:
    """Backward-overlapped gradient sync benchmark on 8 simulated CPU
    devices: three full train steps differing only in
    ``ParallelConfig.overlap_dispatch`` (skip = compute baseline, post =
    serialized bucketed sync, backward = custom_vjp in-backward
    dispatch), reduced to the gated ``speedup_overlap`` (floor) and
    ``exposed_ratio`` (lower-is-better) rows plus the informational
    roofline model overlay; writes ``results/overlap.json``."""
    extra = ["--out", out] + (["--smoke"] if smoke else [])
    _worker_bench("overlap_worker.py", "overlap", extra, timeout=3600)


def tune_bench(smoke: bool = False, out: str = "results/tuning.json",
               cache: str = None) -> None:
    """Measured autotuning: time the (kind x r x n_buckets x size) grid on
    8 simulated CPU devices, record it into the persistent tuning cache
    (``REPRO_TUNING_CACHE`` / the user cache dir), and write a summary to
    ``results/tuning.json``.  After this, ``choose(..., tune=True)`` (or
    ``REPRO_TUNING=1``) answers from measurements instead of the model."""
    extra = ["--out", out] + (["--smoke"] if smoke else [])
    if cache:
        extra += ["--cache", cache]
    _worker_bench("tune_worker.py", "tune", extra, timeout=3600)


def serve_bench(smoke: bool = False, out: str = "results/serving.json",
                trace: bool = False) -> None:
    """Continuous-batching serving benchmark on a dp=2 x tp=2 mesh of 8
    simulated CPU devices: one deterministic request mix served at each
    offered QPS level, reporting p50/p99 TTFT/latency and tokens/sec
    plus the dimensionless ratios vs a same-host solo (one-request-at-a-
    time) baseline that check_regression.py gates
    (``tokens_per_s_ratio`` floor, ``p99_ttft_ratio`` /
    ``p99_latency_ratio`` ceilings).  Writes ``results/serving.json``;
    ``--trace`` saves the engine.tick Chrome trace + metrics snapshot
    next to it."""
    extra = ["--out", out] + (["--smoke"] if smoke else []) \
        + (["--trace"] if trace else [])
    _worker_bench("serve_worker.py", "serve", extra)


def chaos_bench(smoke: bool = False, out: str = "results/chaos.json",
                trace: bool = False) -> None:
    """Deterministic fault scenarios on the real coordinator/worker
    process mesh (kill -> recover at P-1, torn checkpoint fallback,
    delay -> skew telemetry); writes ``results/chaos.json`` whose exact
    ``recovery_steps`` rows are gated by check_regression.py
    (lower-is-better).  No forced host devices needed: the runtime mesh
    is OS processes over TCP."""
    script = os.path.join(os.path.dirname(__file__), "chaos_worker.py")
    extra = ["--out", out] + (["--smoke"] if smoke else []) \
        + (["--trace"] if trace else [])
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run([sys.executable, script, *extra], env=env,
                         capture_output=True, text=True, timeout=1800)
    if res.returncode != 0:
        log.error("worker_failed", worker="chaos_worker.py",
                  stderr=res.stderr[-2000:])
        raise SystemExit(1)
    if res.stderr:
        sys.stderr.write(res.stderr)
    for line in res.stdout.strip().splitlines():
        if line.startswith("chaos,"):
            data(line)


def figures() -> None:
    data("name,us_per_call,derived")
    fig1_ratio_heatmap()
    fig7_small_msgs()
    fig8_large_msgs()
    fig9_medium_msgs()
    fig10_r_sweep()
    fig11_vs_P_small()
    fig12_vs_P_9kb()
    sched_table()
    if os.environ.get("SKIP_WALLCLOCK") != "1":
        wallclock_8dev()


def _opt(argv, flag, default):
    return argv[argv.index(flag) + 1] if flag in argv else default


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    mode = next((a for a in argv if not a.startswith("-")), "figures")
    if mode == "figures":
        figures()
    elif mode == "executor":
        if "--overlap" in argv:
            overlap_bench(smoke="--smoke" in argv,
                          out=_opt(argv, "--out", "results/overlap.json"))
            return
        ops = tuple(argv[i + 1] for i, a in enumerate(argv)
                    if a == "--op" and i + 1 < len(argv))
        executor_bench(smoke="--smoke" in argv,
                       out=_opt(argv, "--out", "results/executor.json"),
                       ops=ops, trace="--trace" in argv)
    elif mode == "tune":
        tune_bench(smoke="--smoke" in argv,
                   out=_opt(argv, "--out", "results/tuning.json"),
                   cache=_opt(argv, "--cache", None))
    elif mode == "chaos":
        chaos_bench(smoke="--smoke" in argv,
                    out=_opt(argv, "--out", "results/chaos.json"),
                    trace="--trace" in argv)
    elif mode == "serve":
        serve_bench(smoke="--smoke" in argv,
                    out=_opt(argv, "--out", "results/serving.json"),
                    trace="--trace" in argv)
    else:
        raise SystemExit(
            f"unknown mode {mode!r} "
            "(figures | executor | tune | chaos | serve)")


if __name__ == "__main__":
    main()
