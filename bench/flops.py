"""Operations the model needs, from shapes alone.

The dense-block arithmetic is ``benchmarks/analytic.py``'s
``block_fwd_flops`` for an attention block with a gated MLP on one
device (tp = 1), kept here so that no later change to the program can
change the yardstick.  Matrix products only: the norms and the
elementwise work that analytic.py adds as a minor term are not model
operations.  Causal attention is charged its average context, S / 2.
"""
from __future__ import annotations


def block_fwd_flops(c: dict, tokens: int, seq: int) -> float:
    """Forward operations of one decoder layer over ``tokens`` tokens in
    sequences of ``seq``."""
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    kv = c["num_key_value_heads"] * hd
    f = c["intermediate_size"]
    fl = 2 * tokens * d * (h * hd)             # q
    fl += 2 * tokens * d * kv * 2              # k, v
    fl += 4 * tokens * h * (seq / 2) * hd      # scores + pv, causal
    fl += 2 * tokens * (h * hd) * d            # out projection
    fl += 3 * 2 * tokens * d * f               # gated MLP
    return fl


def head_fwd_flops(c: dict, tokens: int) -> float:
    return 2 * tokens * c["hidden_size"] * c["vocab_size"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (twice the forward) operations per trained
    token; recomputation is not counted."""
    fwd = (c["num_hidden_layers"] * block_fwd_flops(c, seq, seq)
           + head_fwd_flops(c, seq))
    return 3 * fwd / seq
