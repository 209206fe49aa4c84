"""Check what the engine served against the model's plain full forward.

The engine computes each token's logits on a paged cache, in prefill
chunks and in batches whose shapes depend on what else is being served,
so its reductions run in another order than one full pass over the
sequence would take.  Its tokens are therefore not compared for bit
equality with any other run.  :func:`served_token_margins` instead
scores every token the engine chose under one full, cache-free forward
pass over the request's prompt plus its generated tokens.  A margin of 0
means the engine chose what the reference would have chosen; a margin
up to the caller's tolerance is a near tie that rounding may break
either way.  An engine that attends to the wrong cache entries picks
tokens the reference scores far below its best, and fails.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from repro.models.config import ModelConfig
from repro.models.model import decode_step, param_shapes
from repro.parallel.api import ParallelConfig

# sequences are padded to a multiple of this many tokens, so requests of
# nearby lengths share one compiled reference; causal attention keeps
# the padding from reaching the positions that are scored
_PAD = 64


def gumbel_noise(seed: int, uid: int, step: int, vocab: int) -> np.ndarray:
    """The engine's Gumbel-max draw for token ``step`` of request ``uid``:
    keyed by the request's identity only, never by its slot or batch."""
    g = np.random.default_rng(np.random.SeedSequence([seed, uid, step]))
    return -np.log(-np.log(g.random(vocab)))


@partial(jax.jit, static_argnames=("cfg",))
def _full_logits(params, tokens, *, cfg: ModelConfig):
    pc = ParallelConfig(dp=1, tp=1)
    _, specs = param_shapes(cfg, pc)
    # no caches: one plain pass, every position scored
    logits, _ = decode_step(params, specs, tokens, None, None, cfg, pc,
                            logits_len=tokens.shape[1])
    return logits[0]                                    # (S, V)


def reference_logits(cfg: ModelConfig, params, tokens) -> np.ndarray:
    """(len(tokens), vocab) fp32 next-token logits of one full, cache-free
    forward pass over ``tokens`` on one device.  ``params`` are the
    global (unsharded) parameters of :func:`repro.models.model.init_params`.
    """
    n = len(tokens)
    padded = np.zeros(-(-n // _PAD) * _PAD, np.int32)
    padded[:n] = tokens
    return np.asarray(_full_logits(params, padded[None],
                                   cfg=cfg))[:n]


def served_token_margins(cfg: ModelConfig, params, prompt, out_tokens, *,
                         temperature: float = 0.0, seed: int = 0,
                         uid: int = 0) -> np.ndarray:
    """For each token the engine generated after ``prompt``: how far the
    reference's score of that token falls below the reference's best
    score at its position, in logit units (>= 0).

    Greedy (``temperature <= 0``) scores are the logits.  Sampled scores
    add the engine's own Gumbel draw (:func:`gumbel_noise`, keyed by
    ``seed``, ``uid`` and the token index) at ``temperature``, so the
    margin is again 0 exactly where the reference draws the same token.
    """
    prompt = np.asarray(prompt, np.int32)
    out = np.asarray(out_tokens, np.int32)
    seq = np.concatenate([prompt, out[:-1]])
    logits = reference_logits(cfg, params, seq)[len(prompt) - 1:]
    margins = np.empty(len(out))
    for i, (row, tok) in enumerate(zip(logits, out)):
        if temperature > 0:
            row = row + temperature * gumbel_noise(seed, uid, i, row.size)
        margins[i] = row.max() - row[tok]
    return margins
