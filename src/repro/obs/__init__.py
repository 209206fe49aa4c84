"""Observability layer: tracing, metrics, structured logging, telemetry.

The collective stack can *verify* itself (symbolic simulator, numpy
oracles, conformance harness) and *time* itself end to end (tuning
grid, executor bench), but until this package it could not say where a
schedule's time goes.  ``repro.obs`` adds the missing instrumentation:

* :mod:`repro.obs.trace`    -- one span/counter recorder with two
  sinks: Chrome-trace (Perfetto-loadable) JSON export, switched on with
  ``enable()``, and the JAX profiler's host plane, on whenever a
  ``jax.profiler`` session runs; a near-zero-cost no-op while neither
  is on;
* :mod:`repro.obs.metrics`  -- structured counters and histograms
  (bytes moved, combine FLOPs, request latency p50/p99) with a JSON
  snapshot format committed under ``results/``;
* :mod:`repro.obs.log`      -- a small structured logger (level via the
  ``REPRO_LOG`` env var) replacing bare prints in the benchmark
  drivers and workers;
* :mod:`repro.obs.skew`     -- per-device arrival-pattern telemetry
  (Proficz, arXiv:1804.05349): the measurement half of PAP-aware
  schedules;
* :mod:`repro.obs.instrument` -- opt-in blocking per-tick replay of an
  :class:`~repro.core.execplan.ExecPlan` that times every send and
  combine phase on real devices;
* :mod:`repro.obs.validate` -- predicted-vs-measured reports overlaying
  the alpha-beta-gamma cost model's per-tick predictions on measured
  timelines, emitting a per-(kind, r, n_buckets, size) model-error
  table.

Import discipline: everything here sits *above* ``repro.core`` (it may
import the cost model and plans) but below nothing -- core modules only
ever call the tracer through the cheap global accessors, never the
other way around, and importing ``repro.obs`` must not import jax.
"""
from . import log, metrics, trace  # noqa: F401
from .log import get_logger  # noqa: F401
from .metrics import get_metrics  # noqa: F401
from .trace import counter, get_tracer, span  # noqa: F401
