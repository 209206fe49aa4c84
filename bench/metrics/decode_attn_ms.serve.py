"""Mean device time of one serve-step execution at S = 1 spent in
operations under the ``attn`` scope and not under ``weight_cast``: the
attention over the paged KV view (QKV and output matmuls included).
The tick's S is the ``s`` arg of the engine tick that dispatched it."""
import os

import harness

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    return _p.kind_ms(tr, "decode", "attn", without="weight_cast")
