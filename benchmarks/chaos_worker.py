"""Chaos benchmark worker: deterministic fault scenarios on the real
multi-process coordinator/worker mesh (see repro.runtime).

Each scenario is a REPRO_FAULTS-style spec run end to end: spawn P
worker processes over TCP, inject the fault, and record what the
recovery actually cost.  Because the faults fire at exact (kind, rank,
step) coordinates and training is deterministic, the resulting
``recovery_steps`` (steps of work re-executed = at_step - restored_step)
is an exact, hardware-independent quantity -- the chaos analog of the
executor benchmark's dimensionless speedup ratios -- and is gated by
``check_regression.py --keys recovery_steps,recovered`` (recovery_steps
is *lower*-is-better).

Rows: ``chaos,<label>,recovery_steps=..,new_P=..,wall_s=..``.
Writes ``--out`` (default results/chaos.json); ``--trace`` additionally
saves the coordinator's per-step Chrome trace (coord.step /
coord.recover / coord.checkpoint spans with skew counters) next to it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.obs import trace as obs_trace  # noqa: E402
from repro.obs.log import data, get_logger  # noqa: E402
from repro.runtime.coordinator import Coordinator, CoordinatorConfig  # noqa: E402

log = get_logger("benchmarks.chaos")

# (label, smoke?, config kwargs) -- every scenario is deterministic:
# same spec, same recovery arc, every run, every host.
SCENARIOS = (
    ("kill_p4", True, dict(
        P=4, n_steps=8, ckpt_every=2, faults="kill:rank=2,step=5")),
    ("kill_before_ckpt_p3", False, dict(
        P=3, n_steps=3, ckpt_every=50, faults="kill:rank=0,step=1")),
    ("torn_ckpt_p3", True, dict(
        P=3, n_steps=8, ckpt_every=2,
        faults="ckpt_torn:step=4;kill:rank=1,step=5")),
    ("delay_skew_p3", False, dict(
        P=3, n_steps=4, ckpt_every=50,
        faults="delay:rank=1,step=2,us=40000")),
)


def run_scenario(label: str, spec: dict, ckpt_root: str) -> dict:
    spec = dict(spec)
    n_steps = spec.pop("n_steps")
    cfg = CoordinatorConfig(ckpt_dir=os.path.join(ckpt_root, label),
                            dim=8, batch=4, lr=0.2, step_timeout_s=60.0,
                            **spec)
    t0 = time.perf_counter()
    with Coordinator(cfg) as c:
        recs = c.run(n_steps)
    wall_s = time.perf_counter() - t0
    row = {
        "label": label,
        "P": cfg.P,
        "n_steps": n_steps,
        "faults": cfg.faults,
        "wall_s": round(wall_s, 3),
        "final_loss": recs[-1]["loss"],
        "max_skew_us": round(max(r["skew_us"] for r in recs), 1),
        "steps_completed": len(c.final_losses()),
    }
    if c.recoveries:
        rec = c.recoveries[0]
        row.update({
            # exact + deterministic: gated lower-is-better
            "recovery_steps": float(rec.recovery_steps),
            "recovered": 1.0 if len(c.final_losses()) == n_steps else 0.0,
            "new_P": rec.new_P,
            "restored_step": rec.restored_step,
        })
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="results/chaos.json")
    ap.add_argument("--smoke", action="store_true",
                    help="run only the smoke subset (CI PR gate)")
    ap.add_argument("--trace", action="store_true",
                    help="save the coordinator Chrome trace next to --out")
    ap.add_argument("--ckpt-root", default=None,
                    help="checkpoint scratch dir (default: a tmp dir)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    ckpt_root = args.ckpt_root
    if ckpt_root is None:
        import tempfile
        ckpt_root = tempfile.mkdtemp(prefix="repro_chaos_")
    if args.trace:
        obs_trace.enable(clear=True)

    rows = []
    for label, in_smoke, spec in SCENARIOS:
        if args.smoke and not in_smoke:
            continue
        row = run_scenario(label, spec, ckpt_root)
        rows.append(row)
        parts = [f"recovery_steps={row.get('recovery_steps', '-')}",
                 f"new_P={row.get('new_P', '-')}",
                 f"wall_s={row['wall_s']}"]
        data(f"chaos,{label}," + ",".join(parts))
        if "recovery_steps" in row and not row["recovered"]:
            log.error("chaos_incomplete", label=label,
                      steps=row["steps_completed"], want=row["n_steps"])
            return 1

    mode = "smoke" if args.smoke else "full"
    payload = {"benchmark": "chaos", "mode": mode, "results": rows}
    out_dir = os.path.dirname(os.path.abspath(args.out)) or "."
    os.makedirs(out_dir, exist_ok=True)
    if args.trace:
        tracer = obs_trace.get_tracer()
        trace_path = tracer.save(
            os.path.join(out_dir, f"trace_chaos_{mode}.json"),
            process_name=f"chaos-bench-{mode}")
        obs_trace.disable()
        payload["trace_path"] = os.path.basename(trace_path)
        data(f"chaos,trace,{os.path.basename(trace_path)},"
             f"{tracer.n_events}")
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    data(f"chaos,WROTE,{args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
