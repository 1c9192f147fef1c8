"""The reduction from a trace to numbers: the arithmetic on plain tuples, and
the same functions on a small trace recorded on one v5e chip
(benchmarks/testdata/small.xplane.pb: three bf16 matmuls and three tanh
passes, each under a benchmark span, with sleeps between them)."""

import os

import pytest

from benchmarks import trace_reduce as tr

from bench_tiny import ROOT

MS = 1e6  # ns


def test_union_and_gaps():
    busy = tr.union([(5, 9), (0, 3), (2, 4), (9, 10), (20, 21)])
    assert busy == [(0, 4), (5, 10), (20, 21)]
    assert tr.gaps(busy, 0, 25) == [(4, 5), (10, 20), (21, 25)]
    assert tr.gaps(busy, 1, 8) == [(4, 5)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_module_name_drops_the_fingerprint():
    assert tr.module_name("jit__train_epochs(1234567)") == "jit__train_epochs"
    assert tr.module_name("jit_f") == "jit_f"


def _planes():
    ops = [("fusion.1", 0 * MS, 2 * MS), ("fusion.2", 1 * MS, 2 * MS),
           ("copy.3", 10 * MS, 1 * MS)]
    mods = [("jit_step(11)", 0 * MS, 3 * MS), ("jit_step(11)", 10 * MS, 1 * MS)]
    host = [("bench.verb", 0 * MS, 20 * MS), ("bench.verb.persist", 4 * MS, 5 * MS),
            ("not.ours", 0, 1)]
    return [("/device:TPU:0", [(tr.OPS_LINE, ops), (tr.MODULES_LINE, mods)]),
            ("/host:CPU", [("main", host)]), ("/host:metadata", [])]


def test_reduce_busy_idle_per_name_and_gap_attribution():
    r = tr.reduce(_planes())
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(4e-3)       # union, not the 5 ms sum
    assert r["extent_s"] == pytest.approx(20e-3)
    assert r["op_s"]["fusion.2"] == pytest.approx(2e-3)
    assert r["module_s"] == {"jit_step": pytest.approx(4e-3)}
    assert r["module_runs"] == {"jit_step": 2}
    # idle: 3..10 ms (4..9 under the inner .persist span) and 11..20 ms
    assert r["idle_by_span_s"]["bench.verb.persist"] == pytest.approx(5e-3)
    assert r["idle_by_span_s"]["bench.verb"] == pytest.approx(11e-3)
    assert "host:unspanned" not in r["idle_by_span_s"]
    b = tr.breakdown(r)
    assert b["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert b["idle_gaps"][0] == ["bench.verb", pytest.approx(11e-3)]
    assert len(b["device_ops"]) <= 10


def test_two_chips_average_their_busy_time():
    planes = _planes() + [("/device:TPU:1", [(tr.OPS_LINE, [("a", 0, 2 * MS)])])]
    r = tr.reduce(planes)
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx(3e-3)


def test_recorded_chip_trace():
    path = os.path.join(ROOT, "benchmarks", "testdata", "small.xplane.pb")
    r = tr.reduce_file(path)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["extent_s"] < 1.0
    assert sum(r["op_s"].values()) >= r["busy_s"] * 0.999
    # six executable runs under six spans, as recorded
    assert sum(r["module_runs"].values()) == 6
    assert set(r["span_s"]) == {"bench.small.matmul", "bench.small.tanh"}
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(
        r["extent_s"] - r["busy_s"], rel=1e-6)
    assert r["idle_by_span_s"].get("host:unspanned", 0) > 0  # the sleeps
