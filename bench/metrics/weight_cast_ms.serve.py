"""Mean device time of one serve-step execution spent in operations
under the ``weight_cast`` scope: the fp32 -> bf16 casts of the
weights that every tick makes again."""
import os

import harness
import xplane

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    return _p.scope_ms(tr, xplane.executions(tr, tr.devices[0]),
                       "weight_cast")
