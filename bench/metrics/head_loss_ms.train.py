"""Mean device time of one train-step execution spent in operations
under the ``head`` scope, forward and backward: the vocabulary
projection and the cross entropy over it."""
import os

import harness
import xplane

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    return _p.scope_ms(tr, xplane.executions(tr, tr.devices[0]), "head")
