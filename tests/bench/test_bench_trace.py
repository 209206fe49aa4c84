"""The reduction from a profiler trace to per-layer metrics: interval
arithmetic, each reducer on a hand-made timeline, and the loader on a
small trace recorded on a v5e chip (``data/small.xplane.pb``, made by
``bench/record_trace.py``)."""
from __future__ import annotations

import os

import pytest

import bench_tiny  # noqa: F401
import harness as H
import xplane as X

MS = 1_000_000          # ns


def _metric(name):
    return H.load_module(os.path.join(H.BENCH, "metrics", name + ".py"),
                         "bench_metric_" + name.replace(".", "_"))


def test_union_gaps_and_minus():
    ivs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 40, 45)]
    assert X.union(ivs, 0, 50) == [(0, 15), (20, 30), (40, 45)]
    assert X.union(ivs, 8, 42) == [(8, 15), (20, 30), (40, 42)]
    assert X.covered(ivs, 0, 50) == 30
    assert X.gaps(ivs, 0, 50) == [(15, 20), (30, 40), (45, 50)]
    assert X.minus([(0, 30)], [(5, 10), (20, 25)]) == 20
    assert X.minus([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert X.minus([(0, 10)], []) == 10


def _trace():
    """One chip, window [0, 100 ms]: three executions of the step
    program, a small program between, and the harness's spans."""
    mods = [("jit_step(7)", 0, 20 * MS), ("jit_step(8)", 30 * MS, 60 * MS),
            ("jit_getitem(3)", 62 * MS, 63 * MS),
            ("jit_step(7)", 70 * MS, 90 * MS)]
    ops = [("fusion.1", 0, 12 * MS), ("collective-permute-start.2",
                                      12 * MS, 15 * MS),
           ("combine_n", 15 * MS, 18 * MS), ("fusion.3", 17 * MS, 20 * MS),
           ("fusion.4", 30 * MS, 60 * MS), ("slice.1", 62 * MS, 63 * MS),
           ("fusion.5", 70 * MS, 80 * MS),
           ("collective-permute-done.2", 80 * MS, 90 * MS)]
    spans = [("bench.traced", 0, 100 * MS),
             ("bench.engine_step", 20 * MS, 35 * MS),
             ("bench.submit", 21 * MS, 22 * MS),
             ("bench.wait_arrival", 60 * MS, 70 * MS)]
    return X.Trace(devices=[X.Device(0, ops, mods)], spans=spans,
                   window=(0, 100 * MS))


def test_program_and_executions():
    tr = _trace()
    dev = tr.devices[0]
    assert X.main_program(tr, dev) == "jit_step"
    assert [iv[1] for iv in X.executions(tr, dev)] == [0, 30 * MS, 70 * MS]


def test_span_at_is_the_innermost_open_span():
    tr = _trace()
    assert X.span_at(tr, 21 * MS + 1) == "submit"
    assert X.span_at(tr, 25 * MS) == "engine_step"
    assert X.span_at(tr, 95 * MS) == "host"


def test_idle_share_and_breakdown():
    tr = _trace()
    # busy: 0-20, 30-60, 62-63, 70-90 = 71 ms of 100
    assert _metric("device_idle_share.serve").reduce(tr, {}) == \
        pytest.approx(29.0)
    b = X.breakdown(tr)
    assert b["device_ops"][0] == ["fusion.4", pytest.approx(0.030)]
    assert b["idle_gaps"][0] == ["engine_step", pytest.approx(0.010)]
    assert ["wait_arrival", pytest.approx(0.007)] in b["idle_gaps"]


def test_host_gap_and_ticks():
    tr = _trace()
    # idle between executions: 20-30 (10 ms); 60-70 less 62-63 (9 ms)
    assert _metric("host_gap_ms.serve").reduce(tr, {}) == pytest.approx(9.5)
    facts = {"tick_kinds": ["decode", "chunk", "decode"]}
    assert _metric("decode_tick_ms.serve").reduce(tr, facts) == \
        pytest.approx(20.0)
    assert _metric("chunk_tick_ms.serve").reduce(tr, facts) == \
        pytest.approx(30.0)
    # a tick list that does not match the executions reads nothing
    assert _metric("chunk_tick_ms.serve").reduce(
        tr, {"tick_kinds": ["chunk"]}) is None


def test_train_reducers():
    tr = _trace()
    facts = {"flops_per_token": 1e9, "tokens_per_step": 1000, "chips": 1,
             "device_kind": "TPU v5 lite"}
    # 2 steps in 70 ms of step starts
    want = 100 * 1e9 * 1000 * (2 / 0.070) / 197e12
    assert _metric("mfu.train").reduce(tr, facts) == pytest.approx(want)
    # busy 0-20, 30-60, 62-63, 70-90 of the 100 ms window
    assert _metric("device_idle_share.train").reduce(tr, facts) == \
        pytest.approx(29.0)


def test_nested_operations():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 40, 50),
           ("copy.1", 120, 130)]
    assert dict(X.self_times(ops)) == {"while.1": 70, "fusion.1": 20,
                                       "fusion.2": 10, "copy.1": 10}
    assert [iv[0] for iv in X.leaves(ops)] == ["fusion.1", "fusion.2",
                                               "copy.1"]


SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_recorded_trace_loads():
    """Three rounds of a bf16 matmul and the Pallas combine_n kernel on
    one v5e chip, each round in a ``bench.round`` span."""
    tr = X.load(SMALL)
    assert [d.index for d in tr.devices] == [0]
    dev = tr.devices[0]
    assert len(dev.ops) == 15 and len(dev.modules) == 6
    assert [n for n, _, _ in tr.spans] == ["bench.round"] * 3
    assert X.main_program(tr, dev) == "jit__lambda"
    # the window is the ops' extent, and the first execution of the
    # matmul program begins 3 ns before its first op: two lie inside
    assert len(X.executions(tr, dev)) == 2


def test_recorded_trace_by_hand():
    """Hand-checked against the events as the profiler printed them:
    per round the matmul program runs copy-start (13 ns), copy-done
    (~11.6 us), the matmul fusion (~91.5 us); the combine program a copy
    fusion (~12.5 us) and ``combine_n.1`` (6928, 7051 and 7020 ns)."""
    tr = X.load(SMALL)
    dev = tr.devices[0]
    kernel = [iv for iv in dev.ops if iv[0].startswith("%combine_n.1 ")]
    assert [e - s for _, s, e in kernel] == [6928, 7051, 7020]
    assert tr.window == (41962000, 53484374 + 7020)
    busy_by_hand = sum([13, 11650, 91582, 12570, 6928,
                        13, 11523, 91496, 12433, 7051,
                        13, 11534, 91500, 12494, 7020])
    # copy-done and the fusion after it touch, copy-start ends before
    # copy-done starts: busy is the plain sum
    assert X.busy_ns(tr, dev) == busy_by_hand
    idle = _metric("device_idle_share.serve").reduce(tr, {})
    assert idle == pytest.approx(100 * (1 - busy_by_hand
                                        / (53491394 - 41962000)))
    # the longest idle stretch is round 2's host pause between its two
    # programs: from the matmul fusion's end (46081254 + 91496) to the
    # copy fusion's start (49751477), inside the bench.round span
    assert X.breakdown(tr)["idle_gaps"][0] == [
        "round", pytest.approx((49751477 - 46081254 - 91496) / 1e9)]
