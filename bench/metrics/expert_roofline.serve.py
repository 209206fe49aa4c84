"""Share of the held experts' roofline at S = prefill_chunk: the least
time of the grouped products at each chunk tick's expected load (the
tick's ``tokens`` x k / E rows per held expert;
``_mla_moe.expert_min_s``), summed over the window's chunk ticks, over
the device time they spent in the expert layer (``expert_ms.serve``'s
reading)."""
import os

import harness

_m = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_mla_moe.py"), "bench_metric_mla_moe")


def reduce(tr, facts):
    dec = _m.chunk_ticks(tr)
    if not dec or "config" not in facts:
        return None
    peak = harness.peaks(facts["device_kind"])
    spent = sum(_m.experts_ns(tr, tr.devices[0], e) for e, _ in dec) / 1e9
    if not spent:
        return None
    least = sum(_m.expert_min_s(facts["config"], a["tokens"], peak)
                for _, a in dec)
    return 100.0 * least / spent
