"""The yardstick's arithmetic: model operations per token and the table
of peaks."""
from __future__ import annotations

import os
import sys

import pytest

import bench_tiny  # noqa: F401
import flops
import harness as H

TRAIN = H.load_cell("granite-8b-train.dp1").config


def test_train_flops_are_6n_plus_attention():
    """Granite-8b at one layer and 4,096-token rows: 6 N per token for
    the N weights of the matrix products (layer and head, not the
    embedding lookup), plus causal attention's 3 x 4 h hd S / 2."""
    d, f, v, h, hd, kv = 4096, 14336, 49152, 32, 128, 8 * 128
    layer = d * h * hd + 2 * d * kv + h * hd * d + 3 * d * f
    n = layer + d * v
    assert layer == 218_103_808 and n == 419_430_400
    S = 4096
    want = 6 * n + 3 * 4 * h * hd * (S / 2)
    assert flops.train_flops_per_token(TRAIN, S) == pytest.approx(want,
                                                                   rel=1e-12)


def test_block_flops_match_the_analytic_model():
    """The copy agrees with ``benchmarks/analytic.py`` for a dense
    granite block at tp = 1, less the norms' minor term it adds."""
    sys.path.insert(0, os.path.join(H.ROOT, "benchmarks"))
    import analytic
    from repro.configs import get_config
    cfg = get_config("granite_8b")
    B, S = 2, 4096
    want = analytic.block_fwd_flops(cfg, "attn", B, S, 1) \
        - 2 * 8 * B * S * cfg.d_model
    assert flops.block_fwd_flops(TRAIN, B * S, S) == pytest.approx(want)


def test_peaks_have_a_source_and_v5e():
    table = H.load_json(os.path.join(H.BENCH, "peaks.json"))
    assert "TPU v5e" in table["source"]
    p = H.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_a_kind_missing_from_the_peaks_is_an_error():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        H.peaks("cpu")
