"""What every cell shares: the manifest, the device, the set-up clock,
the traced window, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to one configuration, one traffic mix or one per-layer
metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json``    sizes, source, cuts, limits, mode
* ``bench/modes/<mode>.py``          builds and drives the program
* ``bench/traffic/<traffic>.json``   the mix's parameters; its ``kind``
  names the generator ``bench/traffic/<kind>.py``
* ``bench/metrics/<metric>.py``      one reducer per per-layer metric

A mode's ``run(ctx)`` returns an :class:`Outcome`; :func:`run_cell`
turns it into the result line the benchmark prints.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------- manifest
def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (its name may hold dots
    or dashes, as a metric's or a configuration's does)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: str = ROOT) -> Cell:
    man = load_manifest(root)
    by_name = {w["name"]: w for w in man["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]),
                end_to_end=[m for m in man["end_to_end"]
                            if reports(m, name)],
                per_layer=[m for m in man["per_layer"] if reports(m, name)])


def traffic_kind(traffic: dict):
    return load_module(os.path.join(BENCH, "traffic",
                                    traffic["kind"] + ".py"),
                       "bench_traffic_" + traffic["kind"])


# ---------------------------------------------------------------- device
def chips(n: int):
    """The first ``n`` TPU devices; :class:`NoChip` where there are none
    or too few.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's devices are {devs[0].platform}, not tpu "
                     f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; a kind missing from
    ``bench/peaks.json`` is an error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["kinds"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(has {sorted(table['kinds'])})")
    return table["kinds"][kind]


def memory_peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def seed_key(seed: int):
    """A JAX key from any whole-number seed (seeds may exceed 32
    bits): the seed is hashed to 31 bits first."""
    import jax
    import numpy as np
    s = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(s >> 1))


# ---------------------------------------------------------------- clocks
class CompileClock:
    """Seconds XLA spent compiling (or loading from its persistent
    cache) and persistent-cache hits, from JAX's monitoring events, with
    the host time of each compile so that compiles inside the measured
    window can be counted."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        self.at: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.at.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.at)


class Tracer:
    """The profiler for a ``--trace 1`` run: host spans around the
    harness's calls into each layer, and one traced stretch of the
    window.  With tracing off every span is a no-op."""

    def __init__(self, on: bool, cell: str):
        self.on = on
        self.dir = os.path.join(TRACE_DIR, cell)
        self.path: Optional[str] = None
        self._outer = None

    def span(self, name: str):
        if not self.on:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        jax.profiler.start_trace(self.dir)
        self._outer = jax.profiler.TraceAnnotation("bench.traced")
        self._outer.__enter__()

    def stop(self) -> None:
        if not self.on or self._outer is None:
            return
        import glob

        import jax
        self._outer.__exit__(None, None, None)
        self._outer = None
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError(f"expected one trace under {self.dir}, "
                               f"found {found}")
        self.path = found[0]


# ---------------------------------------------------------------- outcome
@dataclass
class Check:
    """One number compared for ``correct``: passes while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def judge(config: dict, readings: Dict[str, float]) -> List[Check]:
    """Each number the configuration's ``correct`` names, beside its
    limit there."""
    return [Check(k, readings[k], lim)
            for k, lim in config["correct"].items()]


@dataclass
class Outcome:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    # what the per-layer reducers need besides the trace
    facts: Dict[str, Any] = field(default_factory=dict)
    # lines for standard error, before the checks
    notes: List[str] = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(c.ok for c in self.checks)


@dataclass
class RunCtx:
    cell: Cell
    seed: int
    seconds: float
    devices: list
    clock: Any
    tracer: Tracer
    t_process: float
    log: Callable[[str], None]


def free_device_memory() -> None:
    """Drop what the program left on the device before the reference
    runs (the caller has deleted its own references)."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------- main
def _finite(x: float) -> Optional[float]:
    """A reading for the JSON line: no number where none was read."""
    return x if x == x and abs(x) != float("inf") else None


def _prefix(devs) -> str:
    import jax
    d = devs[0]
    return f"[{d.platform} {d.device_kind} x{jax.device_count()}]"


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_process: float, *, devices=None) -> dict:
    """Set up, measure and check one cell (a name or a :class:`Cell`);
    returns the result object.  ``devices`` skips the look for a chip
    (the harness's tests pass the CPU and a test-sized cell)."""
    if isinstance(cell, str):
        cell = load_cell(cell)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise NoChip(f"no program beside the benchmark ({SRC}/repro)")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import jax
    devs = devices if devices is not None else chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    prefix = _prefix(devs)

    def log(msg: str) -> None:
        print(f"{prefix} {cell.name}: {msg}", file=sys.stderr, flush=True)

    log(f"seed={seed} seconds={seconds} trace={int(trace)} "
        f"compile_cache={cache_dir}")
    clock = CompileClock()
    tracer = Tracer(trace, cell.name)
    mode = load_module(os.path.join(BENCH, "modes",
                                    cell.config["mode"] + ".py"),
                       "bench_mode_" + cell.config["mode"])
    ctx = RunCtx(cell=cell, seed=seed, seconds=seconds, devices=devs,
                 clock=clock, tracer=tracer, t_process=t_process, log=log)
    out: Outcome = mode.run(ctx)
    for note in out.notes:
        log(note)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": out.memory_peak_bytes}
    result: Dict[str, Any] = {"correct": out.correct,
                              "attempted": out.attempted,
                              "failed": out.failed}
    if trace:
        import xplane
        metrics, busy, breakdown = xplane.reduce_cell(
            cell, tracer.path, out.facts, log)
        device["busy_s"] = busy[0]
        device["window_s"] = busy[1]
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = breakdown
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in out.end_to_end.items()
                             if k in units}
        result["device"] = device
    result["checks"] = {c.name: {"value": _finite(c.value),
                                 "limit": c.limit} for c in out.checks}
    for c in out.checks:
        log(f"check {c.name}={c.value!r} limit={c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return result
