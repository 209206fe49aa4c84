"""Persistent on-disk tuning cache.

One JSON file holds every measurement this machine has ever taken, grouped
under a *backend fingerprint* (platform, device kind/count, jax version,
package version).  A measurement taken on 8 forced-host CPU devices says
nothing about a v5e pod, nor one taken under another jax release, so
lookups only see entries whose fingerprint matches the running backend
exactly; stale entries are kept on disk (they become live again when the
matching backend returns) but never consulted.

File handling rules:

* **location** -- ``REPRO_TUNING_CACHE`` env var when set, else
  ``$XDG_CACHE_HOME/repro-allreduce/tuning.json`` (``~/.cache`` fallback);
* **atomic writes** -- serialized to a temp file in the same directory and
  ``os.replace``d into place, so readers never observe a half-written
  table;
* **corrupt-file recovery** -- a truncated / garbage / wrong-schema file
  is moved aside to ``<path>.corrupt`` and treated as empty instead of
  raising; tuning degrades to the analytic model, it never breaks a run.
"""

from __future__ import annotations

import json
import os
import tempfile
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

SCHEMA_VERSION = 2
# Older schemas this reader still understands.  v1 rows lack the
# per-rep timings / noise / arrival-skew telemetry added in v2; they
# load with the v1 defaults (no rep detail, noise 0, no skew) and are
# rewritten as v2 on the next save.
COMPAT_VERSIONS = (1, SCHEMA_VERSION)


def _package_version() -> str:
    try:
        from importlib.metadata import version

        return version("repro-allreduce")
    except Exception:
        return "unknown"


@dataclass(frozen=True)
class Fingerprint:
    """Identity of the backend a measurement was taken on."""

    platform: str
    device_kind: str
    device_count: int
    jax_version: str
    package_version: str

    def key(self) -> str:
        return (
            f"{self.platform}|{self.device_kind}|{self.device_count}"
            f"|{self.jax_version}|{self.package_version}"
        )


def current_fingerprint() -> Fingerprint:
    """Fingerprint of the running backend (jax-free fallback: ``nojax``)."""
    try:
        import jax

        devs = jax.devices()
        return Fingerprint(
            platform=jax.default_backend(),
            device_kind=devs[0].device_kind if devs else "unknown",
            device_count=len(devs),
            jax_version=jax.__version__,
            package_version=_package_version(),
        )
    except Exception:
        return Fingerprint(
            platform="nojax",
            device_kind="none",
            device_count=0,
            jax_version="none",
            package_version=_package_version(),
        )


def default_cache_path() -> Path:
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return Path(env)
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache"
    )
    return Path(base) / "repro-allreduce" / "tuning.json"


@dataclass(frozen=True)
class Measurement:
    """One timed candidate: schedule family x pipelining x message size.

    ``itemsize`` records the element width of the benchmarked buffer
    (the grid runner times f32, so 4): raggedness is an *element*-count
    property, and a lookup must not answer a query whose element-ragged
    classification differs from what was measured.  ``op`` records the
    combine operator the grid timed ("sum" / "max" / ...): wallclock is
    op-specific in principle (different kernels, different fusion), so a
    lookup only consults measurements taken under its own operator.
    Entries written before either field existed load with the benchmark
    defaults (f32 sum grid).

    Schema v2 adds measurement-quality telemetry: ``reps_us`` keeps every
    rep's own best-of-iters figure (``us`` stays their minimum), ``noise``
    is the relative rep-to-rep spread ``(max - min) / min`` -- the figure
    :func:`repro.tuning.policy.unstable_cells` thresholds -- and
    ``skew_us`` is the per-device arrival skew the grid's probe observed
    around this measurement (None where not probed).  v1 rows load with
    all three absent/zero.

    ``deltas_us`` keeps the probe's full per-device arrival profile (one
    microsecond delta per device, min at 0) behind the scalar
    ``skew_us``: :func:`repro.tuning.policy.arrival_deltas` feeds it to
    the skew-aware path of :func:`repro.core.autotune.choose`.  Additive
    on schema v2 -- rows written before it existed load with ``None``.
    """

    P: int
    nbytes: int
    kind: str  # schedule family: "generalized" | "ring" | "traff_rounds" | ...
    r: int
    n_buckets: int
    us: float  # best-of-reps wallclock per call
    itemsize: int = 4  # element width of the measured buffer (f32 grid)
    op: str = "sum"  # combine operator the candidate was timed under
    reps_us: Optional[tuple] = None  # per-rep best-of-iters wallclocks
    noise: float = 0.0  # (max - min) / min over reps_us
    skew_us: Optional[float] = None  # device arrival skew near this cell
    deltas_us: Optional[tuple] = None  # per-device arrival deltas (probe)

    @property
    def ragged(self) -> bool:
        """Element count of the measured message does not divide P."""
        return (self.nbytes // max(self.itemsize, 1)) % self.P != 0

    @classmethod
    def from_dict(cls, d: dict) -> "Measurement":
        reps = d.get("reps_us")
        skew = d.get("skew_us")
        deltas = d.get("deltas_us")
        return cls(
            P=int(d["P"]),
            nbytes=int(d["nbytes"]),
            kind=str(d["kind"]),
            r=int(d["r"]),
            n_buckets=int(d["n_buckets"]),
            us=float(d["us"]),
            itemsize=int(d.get("itemsize", 4)),
            op=str(d.get("op", "sum")),
            reps_us=tuple(float(x) for x in reps) if reps else None,
            noise=float(d.get("noise", 0.0)),
            skew_us=float(skew) if skew is not None else None,
            deltas_us=tuple(float(x) for x in deltas) if deltas else None,
        )


@dataclass
class TuningCache:
    """In-memory view of the on-disk tuning table.

    >>> import os, tempfile
    >>> fp = Fingerprint("cpu", "host", 8, "0.9.0", "1.0.0")
    >>> cache = TuningCache()
    >>> cache.record(fp, Measurement(8, 1024, "generalized", 2, 1, 42.0))
    >>> cache.n_measurements
    1
    >>> path = cache.save(os.path.join(tempfile.mkdtemp(), "t.json"))
    >>> TuningCache.load(path).lookup(fp, 8)[0].us
    42.0
    """

    entries: Dict[str, dict] = field(default_factory=dict)
    path: Optional[Path] = None

    # ------------------------------------------------------------ loading
    @classmethod
    def load(cls, path: Optional[os.PathLike] = None) -> "TuningCache":
        """Load the cache at ``path`` (default: :func:`default_cache_path`).

        Any failure to read a well-formed schema-compatible table -- the
        file missing, truncated, non-JSON, or written by a different
        schema version -- yields an *empty* cache; corrupt files are moved
        aside to ``<path>.corrupt`` so the next save starts clean.
        """
        p = Path(path) if path is not None else default_cache_path()
        if not p.exists():
            return cls(path=p)
        try:
            with open(p) as f:
                raw = json.load(f)
            if not isinstance(raw, dict) or raw.get("version") not in COMPAT_VERSIONS:
                raise ValueError(f"unsupported tuning-cache schema in {p}")
            entries = raw["entries"]
            for ent in entries.values():
                Fingerprint(**ent["fingerprint"])  # validate shape
                for m in ent["measurements"]:
                    Measurement.from_dict(m)
        except Exception:
            _quarantine(p)
            return cls(path=p)
        return cls(entries=entries, path=p)

    # ------------------------------------------------------------ writing
    def record(self, fp: Fingerprint, meas: Measurement) -> None:
        """Insert/overwrite one measurement under ``fp``.

        Re-measuring the same candidate at the same size replaces the old
        number -- the table keeps one (latest) figure per grid point.
        """
        ent = self.entries.setdefault(
            fp.key(), {"fingerprint": asdict(fp), "measurements": []}
        )
        ident = _row_ident(asdict(meas))
        ent["measurements"] = [m for m in ent["measurements"] if _row_ident(m) != ident]
        ent["measurements"].append(asdict(meas))

    def save(self, path: Optional[os.PathLike] = None) -> Path:
        """Atomically write the table (temp file + ``os.replace``)."""
        p = Path(path) if path is not None else (self.path or default_cache_path())
        p.parent.mkdir(parents=True, exist_ok=True)
        payload = {"version": SCHEMA_VERSION, "entries": self.entries}
        fd, tmp = tempfile.mkstemp(dir=p.parent, prefix=p.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, p)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return p

    # ------------------------------------------------------------ queries
    def lookup(self, fp: Fingerprint, P: int) -> List[Measurement]:
        """All measurements for ``P`` devices under exactly ``fp``."""
        ent = self.entries.get(fp.key())
        if ent is None:
            return []
        out = [Measurement.from_dict(m) for m in ent["measurements"]]
        return [m for m in out if m.P == P]

    @property
    def n_measurements(self) -> int:
        return sum(len(e["measurements"]) for e in self.entries.values())


def _row_ident(m: dict) -> tuple:
    """Grid-point identity of one measurement row (operator included)."""
    return (m["P"], m["nbytes"], m["kind"], m["r"], m["n_buckets"], m.get("op", "sum"))


def _quarantine(p: Path) -> None:
    try:
        os.replace(p, p.with_suffix(p.suffix + ".corrupt"))
    except OSError:
        pass
