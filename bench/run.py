"""One run of one benchmark cell on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks the served tokens or
trained steps against the plain reference, and prints as its last line
of standard output one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its
limit).  Set-up readings and the checks go to standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles a stretch of the window and reports its per-layer metrics.
Exits with 3, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not beside the
benchmark.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
