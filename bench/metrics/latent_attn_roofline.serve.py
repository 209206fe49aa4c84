"""Share of the latent attention's roofline at S = prefill_chunk: the
least time of each chunk tick's latent attention, the larger of the
live latent cache's bytes (the tick's ``kv_tokens``) over the HBM
bandwidth and the absorbed products' operations (its ``attn_pairs``)
over the bf16 peak (``_mla_moe.latent_min_s``), summed over the
window's chunk ticks, over their device time under the ``latent_attn``
scope."""
import os

import harness

_m = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_mla_moe.py"), "bench_metric_mla_moe")
_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")


def reduce(tr, facts):
    dec = [(e, a) for e, a in _m.chunk_ticks(tr)
           if "kv_tokens" in a and "attn_pairs" in a]
    if not dec or "config" not in facts:
        return None
    peak = harness.peaks(facts["device_kind"])
    spent = sum(_p.scope_ns(tr, tr.devices[0], e, "latent_attn",
                            without="weight_cast") for e, _ in dec) / 1e9
    if not spent:
        return None
    least = sum(_m.latent_min_s(facts["config"], a["kv_tokens"],
                                a["attn_pairs"], peak)
                for _, a in dec)
    return 100.0 * least / spent
