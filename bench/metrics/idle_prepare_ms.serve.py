"""Mean device-idle time between consecutive serve-step executions (as
host_gap_ms.serve counts it) that lies inside the engine's
``engine.admit``, ``engine.build`` and ``engine.dispatch`` spans: the
host preparing the chip's next tick."""
import os

import harness

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    return _p.idle_in_ms(tr, "engine.admit", "engine.build",
                         "engine.dispatch")
