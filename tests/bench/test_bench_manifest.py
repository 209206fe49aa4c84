"""The benchmark's manifest, its files found by name, and its traffic
generators."""
from __future__ import annotations

import math
import os
import re
from statistics import NormalDist

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import harness as H

MAN = H.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = {w["name"]: w for w in MAN["workloads"]}
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"][0] == "python3"
    for p in MAN["paths"]:
        assert os.path.isdir(os.path.join(H.ROOT, p))
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    for word in MAN["command"][1:]:
        assert any(word.startswith(p + "/") for p in MAN["paths"])


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for k in ("why", "layer", "source"):
        if isinstance(entry.get(k), str):
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
            assert "\t" not in entry[k]


def test_entries_have_just_their_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                         "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                         "source", "layer", "moves"}


def test_names_are_unique():
    for group in (MAN["configs"], MAN["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_config_has_a_cell():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


def test_four_chip_cells_are_few():
    n4 = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert n4 <= max(1, len(MAN["workloads"]) // 2)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reports_enough(cell):
    e2e = [m["name"] for m in MAN["end_to_end"] if H.reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(H.reports(m, cell) for m in MAN["per_layer"])


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_moves_is_reported_where_listed(metric):
    moved = {m["name"]: m for m in MAN["end_to_end"]}[metric["moves"]]
    for cell in metric.get("workloads", list(CELLS)):
        assert cell in CELLS
        assert H.reports(moved, cell), (metric["name"], cell)


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert all(1 <= len(x) <= 200 for x in layers)
    assert {m["name"] for m in MAN["per_layer"]
            if "roofline" in m["name"]} <= {
        m["name"] for m in MAN["per_layer"] if m["unit"] == "%"}


def test_run_seconds_fits_the_full_check():
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_are_found_by_name(cell):
    c = H.load_cell(cell)
    assert c.config["name"] == CELLS[cell]["config"]
    assert os.path.isfile(os.path.join(H.BENCH, "modes",
                                       c.config["mode"] + ".py"))
    assert os.path.isfile(os.path.join(H.BENCH, "configs",
                                       c.config["reference"] + ".py"))
    kind = H.traffic_kind(c.traffic)
    assert hasattr(kind, "schedule") or hasattr(kind, "batch")
    for m in c.per_layer:
        mod = H.load_module(os.path.join(H.BENCH, "metrics",
                                         m["name"] + ".py"),
                            "bench_metric_" + m["name"].replace(".", "_"))
        assert callable(mod.reduce)


# a width: hidden, intermediate, latent, state or projection sizes, head
# sizes, expansion factors, experts per token
WIDTH = re.compile(r"(_size|_dim|_rank|_width|_factor)$|^head_|expand"
                   r"|per_tok")


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_matches_manifest(conf):
    c = H.load_json(os.path.join(H.ROOT, conf["file"]))
    assert c["name"] == conf["name"] and c["source"] == conf["source"]
    assert c["reduced"] == conf["reduced"]
    assert len(conf["reduced"]) <= 16
    for key in conf["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert conf["file"].startswith("bench/configs/")


# ---------------------------------------------------------------- traffic
SERVE_MIXES = sorted({w["traffic"] for w in MAN["workloads"]
                      if H.load_cell(w["name"]).config["mode"] == "serve"})


def _mix(name):
    return H.load_json(os.path.join(H.BENCH, "traffic", name + ".json"))


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_open_loop_is_deterministic_by_seed(mix):
    m = _mix(mix)
    gen = H.traffic_kind(m)
    a = gen.schedule(m, 2**33 + 1, 40, 49152, tail_s=60)
    b = gen.schedule(m, 2**33 + 1, 40, 49152, tail_s=60)
    c = gen.schedule(m, 7, 40, 49152, tail_s=60)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed offers the same gaps and lengths in another order
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    for w in (True, False):
        x = [r for r in a if r.in_window == w]
        y = [r for r in c if r.in_window == w]
        assert sorted(len(r.prompt) for r in x) == \
            sorted(len(r.prompt) for r in y)
        assert sorted(r.max_new for r in x) == sorted(r.max_new for r in y)
    # the window's gaps are the mix's gap quantiles, whatever the order
    n = sum(r.in_window for r in a)
    g = np.round(gen.gaps(m["arrival"], m["rate_rps"], n), 9)
    for t in (a, c):
        d = np.round(np.diff([r.due_s for r in t[:n]]), 9)
        assert np.isin(d, g).all()


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_window_holds_the_stratified_quantiles(mix):
    """The window's requests are the mix's quantiles, whatever the rate
    and length: the same multiset of lengths as ``lengths`` gives."""
    m = _mix(mix)
    gen = H.traffic_kind(m)
    win = [r for r in gen.schedule(m, 1, 40, 49152) if r.in_window]
    n = len(win)
    assert n == round(m["rate_rps"] * 40)
    assert sorted(len(r.prompt) for r in win) == \
        sorted(gen.lengths(m["prompt"], n))
    assert sorted(r.max_new for r in win) == \
        sorted(gen.lengths(m["output"], n))
    assert win[-1].due_s < 40


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_lengths_and_gaps_follow_the_mix(mix):
    m = _mix(mix)
    gen = H.traffic_kind(m)
    n = 2000
    for side in ("prompt", "output"):
        d = m[side]
        x = gen.lengths(d, n)
        assert x.min() >= d["min"] and x.max() <= d["max"]
        assert abs(np.median(x) - d["median"]) <= 0.02 * d["median"] + 1
        # the share clipped at the top is the lognormal's tail beyond max
        tail = 1 - NormalDist().cdf(math.log(d["max"] / d["median"])
                                    / d["sigma"])
        assert abs(np.mean(x == d["max"]) - tail) < 0.01
    g = gen.gaps(m["arrival"], m["rate_rps"], n)
    assert abs(g.mean() - 1 / m["rate_rps"]) < 1e-9 / m["rate_rps"] + 1e-9
    cv = g.std() / g.mean()
    assert abs(cv - 1 / math.sqrt(m["arrival"]["shape"])) < 0.05
    sched = gen.schedule(m, 3, 60, 49152)
    assert max(len(r.prompt) + r.max_new for r in sched) <= 4096


@pytest.mark.parametrize("mix", ["dp1"])
def test_train_batches_are_seeded_and_distinct(mix):
    m = _mix(mix)
    gen = H.traffic_kind(m)
    t0, l0 = gen.batch(m, 2**33 + 3, 0, 49152)
    t1, _ = gen.batch(m, 2**33 + 3, 0, 49152)
    t2, _ = gen.batch(m, 2**33 + 3, 1, 49152)
    assert t0.shape == (m["dp"] * m["rows_per_chip"], m["seq_len"])
    assert np.array_equal(t0, t1) and not np.array_equal(t0, t2)
    assert np.array_equal(t0[:, 1:], l0[:, :-1])
    assert len({row.tobytes() for row in np.concatenate([t0, t2])}) == \
        2 * t0.shape[0]
