"""The per-layer metrics that read the program's own spans and named
scopes: the instruction -> ``op_name`` join on a trace recorded on a
v5e chip (``data/small.xplane.pb``), and each reducer on a hand-made
timeline with the program's spans and scope map attached."""
from __future__ import annotations

import os

import pytest

import bench_tiny  # noqa: F401
import harness as H
import xplane as X

MS = 1_000_000          # ns
SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
P = H.load_module(os.path.join(H.BENCH, "metrics", "_program.py"),
                  "bench_metric_program")


def _metric(name):
    return H.load_module(os.path.join(H.BENCH, "metrics", name + ".py"),
                         "bench_metric_" + name.replace(".", "_"))


def test_recorded_trace_joins_instructions_to_op_names():
    with open(SMALL, "rb") as f:
        raw = f.read()
    plain = P.read_scopes(raw, inherit=False)
    matmul = plain["jit__lambda(90411522721506892)"]
    assert matmul["fusion"] == "jit(<lambda>)/dot_general"
    # XLA's copy of an argument into fast memory has no metadata of its
    # own; it takes the name of the matmul it feeds
    assert matmul["copy-start"] == matmul["copy-done"] == ""
    scopes = P.read_scopes(raw)
    matmul = scopes["jit__lambda(90411522721506892)"]
    assert matmul["copy-start"] == matmul["copy-done"] == \
        "jit(<lambda>)/dot_general"
    assert matmul["a.1"] == "a"         # a parameter keeps its own
    assert scopes["jit_combine_n(14716005667872774720)"]["combine_n.1"] \
        == "jit(combine_n)/pallas_call"
    # every operation of every execution is found in its program's map
    tr = X.load(SMALL)
    dev = tr.devices[0]
    for module, lo, hi in dev.modules:
        for op, _, _ in P.ops_in(dev, lo, hi):
            assert P.instruction(op) in scopes[module], op


def test_unnamed_instructions_take_a_neighbours_name():
    insts = [[1, "p.1", "parameter", "", []],
             [2, "convert.2", "convert", "", [1]],
             [3, "copy.3", "copy", "", [2]],
             [4, "fusion.4", "fusion", "jit(f)/attn/dot_general", [3]],
             [5, "tuple.5", "tuple", "", [4, 6]],
             [6, "copy.6", "copy", "", [1]],
             [7, "add.7", "add", "", [8]],
             [8, "mul.8", "multiply", "jit(f)/mlp/mul", []]]
    P._inherit(insts)
    names = {row[1]: row[3] for row in insts}
    # a chain of copies and converts takes the name of what it feeds
    assert names["convert.2"] == names["copy.3"] == "jit(f)/attn/dot_general"
    # parameters and tuples neither lend nor take a name
    assert names["p.1"] == names["tuple.5"] == names["copy.6"] == ""
    # with no named user, the name of what feeds it
    assert names["add.7"] == "jit(f)/mlp/mul"


def test_scope_tokens_are_whole_names():
    toks = P.scope_tokens("jit(step)/transpose(jvp(head))/dot_general")
    assert {"head", "transpose", "jvp", "dot_general"} <= toks
    assert "head" not in P.scope_tokens("jit(step)/headroom/add")
    assert P.instruction("%fusion.3 = bf16[8]{0} fusion(%p)") == "fusion.3"
    assert P.instruction("fusion.3") == "fusion.3"


def _span(name, a, b, **args):
    return P.Span(name, a * MS, b * MS, args)


def _tick(a, b, s, tokens, pad, leaves):
    """One engine tick [a, b] ms with its leaf spans (name, start, end)."""
    return [_span("engine.tick", a, b, s=s, rows=2, queued=0, tokens=tokens,
                  pad_slots=pad, kv_blocks_used=4)] + \
        [_span("engine." + n, x, y) for n, x, y in leaves]


def _serve_trace():
    """One chip, window [0, 100 ms]: three serve-step executions, a
    decode tick (5-20 ms), a chunk tick (30-60) and a decode tick
    (70-90), each dispatched by its engine tick."""
    mods = [("jit_step(7)", 5 * MS, 20 * MS), ("jit_step(8)", 30 * MS, 60 * MS),
            ("jit_step(7)", 70 * MS, 90 * MS)]
    ops = [("%fusion.1 = attn", 5 * MS, 12 * MS),
           ("%convert.2 = cast", 12 * MS, 15 * MS),
           ("%fusion.3 = mlp", 15 * MS, 20 * MS),
           ("%fusion.4 = attn", 30 * MS, 50 * MS),
           ("%convert.5 = cast", 50 * MS, 60 * MS),
           ("%fusion.1 = attn", 70 * MS, 80 * MS),
           ("%fusion.6 = head", 80 * MS, 90 * MS)]
    spans = (_tick(1, 22, 1, 3, 5, [("admit", 1, 2), ("build", 2, 3),
                                    ("dispatch", 3, 4), ("fetch", 4, 21),
                                    ("sample", 21, 22)])
             + _tick(22, 62, 16, 40, 88, [("admit", 22, 23),
                                          ("build", 23, 27),
                                          ("dispatch", 27, 29),
                                          ("fetch", 29, 61),
                                          ("sample", 61, 62)])
             + _tick(62, 92, 1, 2, 6, [("admit", 62, 63), ("build", 63, 67),
                                       ("dispatch", 68, 69),
                                       ("fetch", 69, 91),
                                       ("sample", 91, 92)]))
    body = "jit(step)/while/body/"
    scopes = {"jit_step(7)": {
        "fusion.1": body + "attn/dot_general",
        "convert.2": body + "attn/weight_cast/convert_element_type",
        "fusion.3": body + "mlp/dot_general",
        "fusion.6": "jit(step)/head/dot_general"},
        "jit_step(8)": {
        "fusion.4": body + "attn/dot_general",
        "convert.5": body + "mlp/weight_cast/convert_element_type"}}
    tr = X.Trace(devices=[X.Device(0, ops, mods)],
                 spans=[("bench.traced", 0, 100 * MS)], window=(0, 100 * MS))
    tr.program = P.Program(spans=sorted(spans, key=lambda sp: sp.start),
                           scopes=scopes)
    return tr


def test_idle_split_between_prepare_and_emit():
    tr = _serve_trace()
    # idle 20-30 ms: fetch 20-21, sample 21-22, admit, build, dispatch
    # 22-29, the chunk tick's fetch 29-30; idle 60-70 ms: fetch 60-61,
    # sample 61-62, admit and build 62-67, nothing 67-68, dispatch
    # 68-69, fetch 69-70
    assert _metric("idle_prepare_ms.serve").reduce(tr, {}) == \
        pytest.approx((7 + 6) / 2)
    assert _metric("idle_emit_ms.serve").reduce(tr, {}) == \
        pytest.approx((3 + 3) / 2)
    # the split lies inside what host_gap_ms.serve counts
    assert _metric("host_gap_ms.serve").reduce(tr, {}) == pytest.approx(10)


def test_chunk_pad_share_reads_the_chunk_ticks():
    assert _metric("chunk_pad_share.serve").reduce(_serve_trace(), {}) == \
        pytest.approx(100 * 88 / 128)


def test_attention_by_tick_kind_leaves_out_the_weight_cast():
    tr = _serve_trace()
    # decode: 7 ms (5-12; the cast 12-15 is left out) and 10 ms (70-80)
    assert _metric("decode_attn_ms.serve").reduce(tr, {}) == \
        pytest.approx(8.5)
    assert _metric("chunk_attn_ms.serve").reduce(tr, {}) == \
        pytest.approx(20.0)


def test_weight_cast_per_execution():
    # 3 ms, 10 ms and none, over three executions
    assert _metric("weight_cast_ms.serve").reduce(_serve_trace(), {}) == \
        pytest.approx(13 / 3)


def test_kinds_come_from_the_dispatching_tick():
    tr = _serve_trace()
    ex = X.executions(tr, tr.devices[0])
    assert [t.args["s"] for t in P.tick_of(tr, ex)] == [1, 16, 1]


def test_kinds_survive_a_host_clock_ahead_of_the_device():
    """The profiler may put an execution up to about a millisecond
    before the dispatch that made it: pairs go by order."""
    tr = _serve_trace()
    for sp in tr.program.spans:
        if sp.name == "engine.dispatch":
            sp.start, sp.end = sp.start + 5 * MS // 2, sp.end + 5 * MS // 2
    ex = X.executions(tr, tr.devices[0])
    assert [t.args["s"] for t in P.tick_of(tr, ex)] == [1, 16, 1]
    assert _metric("chunk_attn_ms.serve").reduce(tr, {}) == \
        pytest.approx(20.0)


def test_nothing_to_read_and_failed_joins():
    tr = _serve_trace()
    # a program without spans or scopes, as before the engine had them
    tr.program = P.Program()
    for name in ("idle_prepare_ms.serve", "idle_emit_ms.serve",
                 "chunk_pad_share.serve", "decode_attn_ms.serve",
                 "chunk_attn_ms.serve"):
        assert _metric(name).reduce(tr, {}) is None, name
    # an execution no dispatch precedes is a join that failed
    tr = _serve_trace()
    tr.program.spans = [sp for sp in tr.program.spans
                        if not (sp.name == "engine.dispatch"
                                and sp.start < 5 * MS)]
    with pytest.raises(RuntimeError):
        _metric("decode_attn_ms.serve").reduce(tr, {})
    # so is a program the trace holds no HLO for
    tr = _serve_trace()
    del tr.program.scopes["jit_step(8)"]
    with pytest.raises(RuntimeError):
        _metric("weight_cast_ms.serve").reduce(tr, {})


def test_head_loss_counts_forward_and_backward():
    mods = [("jit_train(3)", 0, 40 * MS), ("jit_train(3)", 50 * MS, 90 * MS)]
    ops = [("%fusion.1 = f", 0, 10 * MS), ("%fusion.2 = b", 10 * MS, 16 * MS),
           ("%add.3 = x", 16 * MS, 40 * MS),
           ("%fusion.1 = f", 50 * MS, 60 * MS),
           ("%fusion.2 = b", 60 * MS, 64 * MS),
           ("%add.3 = x", 64 * MS, 90 * MS)]
    tr = X.Trace(devices=[X.Device(0, ops, mods)], spans=[],
                 window=(0, 100 * MS))
    tr.program = P.Program(scopes={"jit_train(3)": {
        "fusion.1": "jit(step)/head/while/body/dot_general",
        "fusion.2": "jit(step)/transpose(jvp(head))/dot_general",
        "add.3": "jit(step)/headroom/add"}})
    assert _metric("head_loss_ms.train").reduce(tr, {}) == \
        pytest.approx((16 + 14) / 2)
    tr.program.scopes["jit_train(3)"] = {"add.3": "jit(step)/add"}
    assert _metric("head_loss_ms.train").reduce(tr, {}) is None
