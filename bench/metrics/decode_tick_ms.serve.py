"""Mean device time of one serve-step execution at S = 1 (every live
slot decoding)."""
import os

import harness

_t = harness.load_module(os.path.join(os.path.dirname(__file__), "_ticks.py"),
                         "bench_metric_ticks")


def reduce(tr, facts):
    return _t.mean_ms(tr, facts, "decode")
