"""Training input: the global batch of each step, made from the seed.

Step ``k`` of seed ``s`` draws its token ids from ``(s, k)`` alone, so
any step can be made again (the reference does) and no two steps share
a row.  Labels are the next token of each row.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def rows(mix: dict) -> int:
    return mix["dp"] * mix["rows_per_chip"]


def batch(mix: dict, seed: int, step: int, vocab: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), step]))
    toks = rng.integers(0, vocab, (rows(mix), mix["seq_len"] + 1),
                        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:].copy()
