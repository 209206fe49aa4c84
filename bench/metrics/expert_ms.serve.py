"""Mean device time of one serve-step execution at S = prefill_chunk
spent in the expert layers' ``experts`` scope (gathering each held
expert's rows, its grouped products, and the weighted combine) or in
the grouped-product kernels themselves.  The tick's S is the ``s`` arg
of the engine tick that dispatched it."""
import os

import harness

_m = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_mla_moe.py"), "bench_metric_mla_moe")


def reduce(tr, facts):
    dec = _m.chunk_ticks(tr)
    ns = [_m.experts_ns(tr, tr.devices[0], e) for e, _ in dec]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / 1e6
