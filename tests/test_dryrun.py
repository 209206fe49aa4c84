"""Dry-run smoke: one real cell compiles on the production mesh in a
subprocess (512 virtual devices), producing memory/cost/collective
records.  The full 80-cell sweep is `python -m repro.launch.dryrun --all`
(results archived in results/dryrun/)."""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
@pytest.mark.xdist_group("subprocess")
def test_dryrun_single_cell(tmp_path):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # dryrun sets its own
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "hubert_xlarge", "--shape", "train_4k",
         "--singlepod-only", "--out", str(tmp_path)],
        env={**env, "PYTHONPATH": "src"},
        capture_output=True, text=True, timeout=900, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert res.returncode == 0, res.stdout + res.stderr
    rec = json.load(open(tmp_path / "hubert_xlarge__train_4k__16x16.json"))
    assert rec["status"] == "ok"
    assert rec["memory"]["temp_size_in_bytes"] > 0
    assert rec["cost"].get("flops", 0) > 0
    kinds = {c["kind"] for c in rec["collectives"]["summary"]}
    # TP sequence-parallel boundaries must show up as real collectives
    assert kinds & {"all-gather", "reduce-scatter", "all-reduce"}


def test_skip_rules_against_assignment():
    """The 44-cell grid (11 architectures x 4 shapes) resolves to 36
    runnable cells: the assignment's 33, and deepseek_v2_lite's three
    (full attention, so no long_500k)."""
    from repro.configs import ARCHS, get_config
    from repro.models.config import SHAPES, shape_applicable
    runnable, skipped = 0, []
    for a in ARCHS:
        for s in SHAPES.values():
            ok, why = shape_applicable(get_config(a), s)
            if ok:
                runnable += 1
            else:
                skipped.append((a, s.name, why))
    assert runnable == 36
    assert len(skipped) == 8
    assert ("hubert_xlarge" not in {a for a, _, _ in skipped}) is False
