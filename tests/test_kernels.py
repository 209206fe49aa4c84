"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_combine import combine_n, fused_combine
from repro.kernels.rmsnorm import rmsnorm


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


# -------------------------------------------------------------- fused_combine
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [1, 127, 4096, 130_000])
def test_fused_combine(n, dtype):
    rng = np.random.default_rng(n)
    a = _rand(rng, (n,), dtype)
    b = _rand(rng, (n,), dtype)
    got = fused_combine(a, b, interpret=True, block=8 * 1024)
    want = ref.fused_combine_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-6)


@pytest.mark.parametrize("k", [2, 3, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_combine_n(k, dtype):
    rng = np.random.default_rng(k)
    s = _rand(rng, (k, 9_001), dtype)
    got = combine_n(s, interpret=True, block=2 * 1024)
    want = ref.combine_n_ref(s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_combine_fp32_accum_beats_bf16_chain():
    """The kernel accumulates in fp32: summing many near-cancelling bf16
    values must be more accurate than a bf16 chain."""
    rng = np.random.default_rng(0)
    k, n = 7, 1024
    s = (rng.standard_normal((k, n)) * 100).astype(np.float32)
    sb = jnp.asarray(s, jnp.bfloat16)
    got = np.asarray(combine_n(sb, interpret=True, block=1024), np.float32)
    exact = s.astype(np.float64).sum(0)
    chain = sb[0]
    for i in range(1, k):
        chain = (chain + sb[i]).astype(jnp.bfloat16)
    err_kernel = np.abs(got - exact).mean()
    err_chain = np.abs(np.asarray(chain, np.float32) - exact).mean()
    assert err_kernel <= err_chain * 1.05


# -------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 128), (3, 5, 256), (1, 384), (1000, 64)])
def test_rmsnorm(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = _rand(rng, shape, dtype)
    w = _rand(rng, shape[-1:], dtype)
    got = rmsnorm(x, w, interpret=True, block_rows=16)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


# -------------------------------------------------------------- flash attn
def _attn_case(B, Hq, Hkv, Sq, Skv, D, causal, window, dtype,
               bq=16, bk=32, seed=0):
    rng = np.random.default_rng(seed)
    q = _rand(rng, (B, Hq, Sq, D), dtype)
    k = _rand(rng, (B, Hkv, Skv, D), dtype)
    v = _rand(rng, (B, Hkv, Skv, D), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    rtol, atol = (4e-2, 4e-2) if dtype == jnp.bfloat16 else (2e-5, 2e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("dtype,rows", [(jnp.float32, 128),
                                        (jnp.bfloat16, 192)])
def test_rmsnorm_block_rows_fit_vmem_at_granite_width(dtype, rows):
    """The default row block is sized from width and dtype: a multiple of
    8 whose tiles fit the VMEM budget (the fixed 256 rows did not fit
    at d = 4096 in fp32)."""
    from repro.kernels.rmsnorm import _VMEM_BUDGET, block_rows_for
    got = block_rows_for(4096, dtype)
    assert got == rows and got % 8 == 0
    assert got * 4096 * (4 * jnp.dtype(dtype).itemsize + 8) <= _VMEM_BUDGET
    assert block_rows_for(64, dtype) == 256      # narrow rows: the cap
    assert block_rows_for(1 << 20, dtype) == 8   # very wide: the floor


@pytest.mark.parametrize("call", ["kv_valid", "q_positions", "return_lse",
                                  "combine_2d"])
def test_pallas_ops_refuse_what_the_kernel_cannot_do(call):
    """Asked for the Pallas kernel where it has no such mode, the ops
    layer raises instead of quietly running the XLA reference."""
    from repro.kernels import ops
    q = jnp.zeros((1, 2, 1, 16), jnp.float32)
    kv = jnp.zeros((1, 2, 8, 16), jnp.float32)
    with pytest.raises(ValueError):
        if call == "kv_valid":
            ops.attention(q, kv, kv, causal=False, impl="pallas",
                          kv_valid=jnp.array([4]))
        elif call == "q_positions":
            ops.attention(q, kv, kv, impl="pallas",
                          q_positions=jnp.array([[3]]))
        elif call == "return_lse":
            ops.attention(q, kv, kv, impl="pallas", return_lse=True)
        else:
            ops.combine(jnp.zeros((2, 8)), jnp.zeros((2, 8)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_selfattn(dtype):
    _attn_case(2, 4, 4, 64, 64, 32, True, None, dtype)


def test_flash_gqa():
    _attn_case(1, 8, 2, 48, 48, 16, True, None, jnp.float32)


def test_flash_mqa():
    _attn_case(2, 4, 1, 33, 33, 16, True, None, jnp.float32)


def test_flash_sliding_window():
    _attn_case(1, 2, 2, 96, 96, 16, True, 17, jnp.float32)


def test_flash_decode_offset():
    """Sq=1 decode against a long cache."""
    _attn_case(2, 4, 2, 1, 95, 16, True, None, jnp.float32, bq=1, bk=32)


def test_flash_decode_window():
    _attn_case(1, 2, 1, 1, 130, 16, True, 24, jnp.float32, bq=1, bk=32)


def test_flash_noncausal_encoder():
    _attn_case(2, 4, 4, 40, 40, 16, False, None, jnp.float32)


def test_flash_ragged_blocks():
    """Sequence lengths that don't divide the block sizes."""
    _attn_case(1, 2, 2, 37, 37, 16, True, None, jnp.float32, bq=16, bk=16)


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_flash_property(data):
    B = data.draw(st.integers(1, 2))
    Hkv = data.draw(st.sampled_from([1, 2]))
    g = data.draw(st.sampled_from([1, 2, 4]))
    S = data.draw(st.integers(2, 70))
    D = data.draw(st.sampled_from([8, 16]))
    causal = data.draw(st.booleans())
    window = data.draw(st.sampled_from([None, 5, 16]))
    if not causal:
        window = None
    _attn_case(B, Hkv * g, Hkv, S, S, D, causal, window, jnp.float32,
               bq=16, bk=16, seed=S * D)
