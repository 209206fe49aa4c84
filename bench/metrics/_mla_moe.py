"""Shape arithmetic of the DeepSeek-V2 serve cell's roofline shares,
and the device time of its expert layer, read from the trace.

The least time of a piece of work is the larger of its bytes over the
chip's HBM bandwidth and its operations over its bf16 peak
(``bench/peaks.json``); a share is that least time over the measured
device time.  Only the configuration file's keys (``facts["config"]``)
enter the arithmetic, never the program.
"""
import os

import harness
import xplane

_p = harness.load_module(os.path.join(os.path.dirname(__file__),
                                      "_program.py"), "bench_metric_program")

BF16 = 2


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def expert_min_s(c: dict, tokens: int, peak: dict) -> float:
    """Least time of one tick's held-expert grouped products over every
    expert layer, at the expected load: each of the tick's ``tokens``
    tokens picks ``k`` of the ``E`` published experts, so a held expert
    gets ``tokens * k / E`` rows and is picked at all with probability
    ``1 - (1 - k / E) ** tokens``.  Bytes: the picked experts' bf16
    weights (three matrices of d x f) and the rows read and written (d
    each); operations: three products of 2 d f per row."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    E, k = c["published"]["n_routed_experts"], c["num_experts_per_tok"]
    held = c["n_routed_experts"]
    rows = tokens * k / E * held
    picked = held * (1.0 - (1.0 - k / E) ** tokens)
    nbytes = (picked * 3 * d * f + rows * 2 * d) * BF16
    ops = rows * 3 * 2 * d * f
    return expert_layers(c) * max(nbytes / peak["hbm_bytes_per_s"],
                                  ops / peak["bf16_flops_per_s"])


def latent_min_s(c: dict, kv_tokens: int, attn_pairs: int,
                 peak: dict) -> float:
    """Least time of one tick's latent attention over every layer, the
    larger of two terms.  Bytes: the live cache, ``kv_tokens`` latents
    of ``kv_lora_rank + qk_rope_head_dim`` bf16 values a layer, read
    once.  Operations: in the absorbed form each of the tick's
    ``attn_pairs`` query-key pairs (a new token and a position of its
    causal prefix) costs every head a score over the whole latent and
    rope key and a value product over the latent, ``2 x (2 x
    kv_lora_rank + qk_rope_head_dim)`` a head.  A decode row reads its
    slot's latents for one query and is bound by the bytes; a prefilling
    row of a chunk tick reads them for up to ``prefill_chunk`` queries
    and is bound by the operations."""
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nbytes = kv_tokens * (r + rope) * BF16
    ops = attn_pairs * c["num_attention_heads"] * 2 * (2 * r + rope)
    return c["num_hidden_layers"] * max(nbytes / peak["hbm_bytes_per_s"],
                                        ops / peak["bf16_flops_per_s"])


def _in_experts(names, op: str) -> bool:
    """Under the ``experts`` scope, or one of the grouped-product kernels
    that XLA names ``ragged-dot*`` in place of the scope."""
    inst = _p.instruction(op)
    tokens = _p.scope_tokens(names.get(inst, ""))
    if "weight_cast" in tokens:
        return False
    return "experts" in tokens or inst.startswith("ragged-dot")


def experts_ns(tr, dev, execution) -> int:
    module, lo, hi = execution
    names = _p._program_scopes(tr, module)
    return sum(t for op, t in xplane.self_times(_p.ops_in(dev, lo, hi))
               if _in_experts(names, op))


def chunk_ticks(tr):
    """(execution, its tick's args) of the window's chunk ticks (S =
    prefill_chunk): the cell's working tick, since decode rows ride in
    them while any slot prefills (PERF.md, section 6)."""
    if not _p.ticks(tr):
        return []
    ex = xplane.executions(tr, tr.devices[0])
    return [(e, t.args) for e, t in zip(ex, _p.tick_of(tr, ex))
            if t.args["s"] > 1]
