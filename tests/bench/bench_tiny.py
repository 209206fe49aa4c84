"""Test-sized cells of the benchmark: the real configuration and
traffic files with every width, length and rate scaled down, so that a
whole run (set-up, window, reference check) fits on the CPU."""
from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness as H  # noqa: E402

_WIDTHS = {"hidden_size": 64, "intermediate_size": 224,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "vocab_size": 256}
_PROGRAM = {"d_model": 64, "d_ff": 224, "n_heads": 4, "n_kv_heads": 2,
            "head_dim": 16, "vocab": 256}


def cell(name: str, **traffic) -> H.Cell:
    """The manifest's cell ``name`` at test size."""
    real = H.load_cell(name)
    c = copy.deepcopy(real.config)
    layers = 2 if c["mode"] == "serve" else 1
    c.update(_WIDTHS, num_hidden_layers=layers)
    c["program"]["overrides"].update(_PROGRAM, n_layers=layers)
    t = copy.deepcopy(real.traffic)
    if c["mode"] == "serve":
        c["engine"].update(batch_slots=4, max_len=256, prefill_chunk=32)
        # the test size's own limit, between its sound runs on the CPU
        # (at most 0.0164 over seeds 1-12) and its float8 control (at
        # least 0.061)
        c["correct"] = {"max_logit_gap": 0.035}
        t.update(rate_rps=4.0,
                 prompt=dict(t["prompt"], median=40, min=8, max=150),
                 output=dict(t["output"], median=10, min=2, max=60))
    else:
        t.update(seq_len=64, rows_per_chip=2)
        # width 64 rounds far more than 4096 relative to its scale: the
        # test size's own limits, between its sound runs on the CPU
        # (loss 1.2e-4, gradient 5.7e-4, update 1.05e-3) and its float8
        # control (4.5e-4, 1.1e-2, 3.3e-3)
        c["correct"] = {"loss_gap": 2.5e-4, "grad_norm_gap": 3e-3,
                        "update_norm_gap": 2.5e-3}
    t.update(traffic)
    return H.Cell(name=name, config=c, traffic=t,
                  chips=t.get("dp", 1), end_to_end=real.end_to_end,
                  per_layer=[])
