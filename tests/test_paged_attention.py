"""The serve path's paged attention against its oracle.

:func:`repro.models.attention.paged_attention` groups the query heads of
each KV head and reads the gathered blocks in bf16;
:func:`repro.kernels.ref.flash_attention_ref` over :func:`paged_view`
repeats K/V per query head and attends in f32.  Same masks, same rows:
only bf16 rounding of the probabilities and of the output separates
them.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.models.attention import PagedKV, paged_attention, paged_view

HQ, HD, BS, NBM = 8, 32, 4, 5
# written length of each slot: an idle slot, one token, a partial and a
# full view of NBM * BS = 20 positions
KV_VALID = (0, 1, 9, 20)
# the output is bf16 (8 mantissa bits) and so is each probability the
# value product reads: a unit-scale output moves by a few 2^-9 steps
TOL = 2 ** -6


@pytest.mark.parametrize("window", [None, 3])
@pytest.mark.parametrize("group", [1, 4, HQ], ids=["mha", "gqa4", "mqa"])
@pytest.mark.parametrize("s", [1, 4])
def test_paged_attention_matches_the_oracle(s, group, window):
    hkv = HQ // group
    B = len(KV_VALID)
    n_blocks = 1 + B * NBM + 3
    rng = np.random.default_rng(100 * s + 10 * group + (window or 0))
    pool_k = jnp.asarray(rng.standard_normal((n_blocks, hkv, BS, HD)),
                         jnp.bfloat16)
    pool_v = jnp.asarray(rng.standard_normal((n_blocks, hkv, BS, HD)),
                         jnp.bfloat16)
    # every slot's blocks scattered over the pool (block 0 is garbage)
    table = 1 + rng.permutation(n_blocks - 1)[:B * NBM].reshape(B, NBM)
    kv_valid = np.asarray(KV_VALID, np.int32)
    n_new = np.minimum(kv_valid, s)
    n_new[1] = min(1, s)                   # a padded row when s > 1
    lengths = kv_valid - n_new
    q_pos = lengths[:, None] + np.arange(s, dtype=np.int32)[None, :]
    q = jnp.asarray(rng.standard_normal((B, HQ, s, HD)), jnp.bfloat16)
    block_table = jnp.asarray(table, jnp.int32)

    got = paged_attention(q, pool_k, pool_v, block_table,
                          jnp.asarray(kv_valid), jnp.asarray(q_pos), window)
    k_view, v_view = paged_view(PagedKV(pool_k, pool_v), block_table)
    want = ref.flash_attention_ref(q, k_view, v_view, causal=True,
                                   window=window,
                                   kv_valid=jnp.asarray(kv_valid),
                                   q_positions=jnp.asarray(q_pos))

    assert got.shape == want.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    assert (got[0] == 0).all()             # the idle slot attends to nothing
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               atol=TOL, rtol=TOL)
