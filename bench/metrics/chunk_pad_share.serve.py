"""Share of the token slots of the window's chunk ticks (S =
prefill_chunk) that carry no token: sum of the ticks' ``pad_slots`` over
the sum of B x S, from the args of each ``engine.tick`` span."""
import os

import harness

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    chunks = [t.args for t in _p.ticks(tr) if t.args["s"] > 1]
    slots = sum(a["pad_slots"] + a["tokens"] for a in chunks)
    if not slots:
        return None
    return 100.0 * sum(a["pad_slots"] for a in chunks) / slots
