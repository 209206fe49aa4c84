"""Mean device-idle time between consecutive serve-step executions (as
host_gap_ms.serve counts it) that lies inside the engine's
``engine.fetch`` and ``engine.sample`` spans: the host taking the
logits of the last tick and sampling from them."""
import os

import harness

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    return _p.idle_in_ms(tr, "engine.fetch", "engine.sample")
