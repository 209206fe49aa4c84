"""Measured-tuning worker: populate the tuning cache on 8 host devices.

Launched by ``benchmarks/run.py tune`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the candidate
grid is timed on the same virtual-device fabric the multi-device tests
use.  All measuring logic lives in :mod:`repro.tuning.measure`; this is
only the subprocess entry point.
"""

import argparse
import os
import sys

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.tuning.measure import run_tuning  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True, help="summary JSON path")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument(
        "--cache",
        default=None,
        help="tuning cache path (default: REPRO_TUNING_CACHE or the "
        "user cache dir)",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run_tuning(smoke=args.smoke, out=args.out, cache_path=args.cache)


if __name__ == "__main__":
    main()
