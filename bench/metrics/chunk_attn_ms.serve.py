"""Mean device time of one serve-step execution at S = prefill_chunk
spent in operations under the ``attn`` scope and not under
``weight_cast``.  The tick's S is the ``s`` arg of the engine tick that
dispatched it."""
import os

import harness

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    return _p.kind_ms(tr, "chunk", "attn", without="weight_cast")
