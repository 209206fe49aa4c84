"""Model FLOP/s utilisation of the train step: the operations the
forward and backward passes need per token times tokens per second,
over chips times the bf16 peak.  The rate is the steady one of the
traced steps: first to last step start on the first chip."""
import harness
import xplane


def reduce(tr, facts):
    ex = xplane.executions(tr, tr.devices[0])
    if len(ex) < 2:
        return None
    steps_per_s = (len(ex) - 1) / ((ex[-1][1] - ex[0][1]) / 1e9)
    peak = harness.peaks(facts["device_kind"])["bf16_flops_per_s"]
    return 100.0 * (facts["flops_per_token"] * facts["tokens_per_step"]
                    * steps_per_s / (facts["chips"] * peak))
