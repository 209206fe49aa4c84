"""Record a small profiler trace on the chip, for the trace reduction's
tests.

    python bench/record_trace.py OUT_DIR

Runs three rounds of a bf16 matmul and the Pallas ``combine_n`` kernel,
each round inside a ``bench.round`` annotation with a 2 ms host pause
between the two programs, under ``jax.profiler``; copies the
``.xplane.pb`` to ``OUT_DIR/small.xplane.pb`` and prints every plane and
line with its first events.  Exits non-zero where JAX finds no TPU.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv) -> int:
    out_dir = argv[0] if argv else "."
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    from repro.kernels.fused_combine import combine_n

    mm = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    stack = jnp.ones((2, 1 << 20), jnp.float32)
    mm(a, a).block_until_ready()
    combine_n(stack).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out_dir)
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.round"):
            mm(a, a).block_until_ready()
            time.sleep(0.002)
            combine_n(stack).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    dest = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dest)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for e in evs[:4]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      {k: v for k, v in e.stats})
    print(os.path.getsize(dest), "bytes at", dest)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
