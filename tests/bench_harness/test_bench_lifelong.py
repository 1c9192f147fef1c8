"""The lifelong-session cell (``seq-keye-vl2-30b-a3b.serve-lifelong``): its
configuration, traffic, cell file, readers and cost functions resolve from
the files as they stand; the real runner, generator and comparison run at a
small size on the CPU; ``correct`` is true on a sound path and false with
the mathematics changed (no selection, half the top-k) and with float8
weights. CPU only; nothing here asks for a chip.
"""

import ast
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import control, control_sessions, harness
from benchmarks.costs import indexer, sparse_attention
from benchmarks.runners import serve_lifelong, serve_sessions

import bench_tiny
import bench_tiny_sparse

REAL = bench_tiny_sparse.REAL
NEW_METRICS = ("sparse_attention_roofline", "indexer_roofline",
               "seq_attention_share_pct", "seq_sparse_selected_pct")
SHAPE = {"num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
         "num_hidden_layers": 4,
         "sa_config": {"indexer_num_heads": 16, "indexer_head_dim": 64,
                       "topk": 2048}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_sparse.make_root(str(tmp_path_factory.mktemp("sparse")))


# -- the files as they stand ------------------------------------------------------

def test_real_cell_resolves_with_every_reader_and_key():
    cell = harness.resolve_cell(REAL)
    runner = harness.load_runner(cell.kind)
    assert runner is serve_lifelong
    harness.check_keys("traffic", cell.traffic, runner.TRAFFIC_KEYS)
    harness.check_keys("config", cell.config, runner.CONFIG_KEYS)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"moe_experts_roofline", "seq_cache_reuse_pct", "seq_extend_ms",
            "batcher_slots_mean", "device_idle_pct.serve"} <= names
    assert not names & {"mla_attention_roofline", "scorer_roofline",
                        "retrieval_dispatch_p50_ms"}
    for name in names:
        assert callable(harness.load_reader(name))
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_p50_ms", "serve_within_limit_pct", "serve_qps", "setup_s"}
    t = cell.traffic   # the issue's traffic, letter for letter
    assert (t["pool"], t["length_median"], t["length_sigma"], t["length_min"],
            t["length_max"], t["retire_at"]) == (
        40, 12288, 0.5, 4096, 24576, 32768)
    assert (t["miss_share"], t["growth_mean"], t["growth_max"],
            t["session_zipf_s"], t["item_zipf_s"], t["num"]) == (
        0.05, 4, 16, 0.8, 1.0, 10)
    assert (t["connections"], t["prefill_connections"], t["warmup_seconds"],
            t["timeout_s"]) == (64, 4, 5.0, 10.0)
    assert t["rate_qps"] == pytest.approx(0.8 * t["knee_qps"])
    # the other sequence cell is as it was, and keeps its own attention reader
    other = harness.resolve_cell("seq-mistral-small4-ep4.serve-sessions")
    assert harness.load_runner(other.kind) is serve_sessions
    assert "mla_attention_roofline" in {m["name"] for m in other.per_layer}
    assert not set(NEW_METRICS) & {m["name"] for m in other.per_layer}


def test_configuration_keeps_every_published_width():
    cfg = harness.resolve_cell(REAL).config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(
        r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")["config"]
    differing = {k for k, v in published.items() if cfg.get(k) != v}
    assert differing == set(cfg["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert cfg["num_hidden_layers"] == 4
    assert cfg["max_position_embeddings"] == cfg["serve"]["max_len"] == 32768
    assert cfg["experts_held"] == cfg["num_experts"] == 128
    # the bytes the file states are the arithmetic of its own widths
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    sa, f = cfg["sa_config"], cfg["moe_intermediate_size"]
    attention = 2 * d * h * dh + 2 * d * kv * dh
    idx = d * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        + d * sa["indexer_head_dim"] + d * sa["indexer_num_heads"]
    layer = attention + idx + d * 128 + 128 * 3 * d * f + 2 * d + 2 * dh
    b = cfg["bytes"]
    assert (b["attention_params_per_layer"], b["indexer_params_per_layer"],
            b["layer_params"]) == (attention, idx, layer)
    assert b["weights_bytes"] == pytest.approx(
        4 * 2 * layer + 2 * 2 * cfg["vocab_size"] * d, rel=1e-3)
    per_token = 4 * (2 * kv * dh + 128) * 2 + 4
    assert b["cache_bytes_per_token"] == per_token == 9220
    assert b["cache_bytes"] == pytest.approx(
        per_token * cfg["serve"]["cache_tokens"], rel=1e-3)
    assert cfg["serve"]["cache_tokens"] == 22 * 32768


def test_cost_functions_count_what_the_equations_need():
    # a turn of 4 items on a 12,284-item session: each query scores what it
    # sees and attends 2048 of it
    turn = indexer.cost(12284, 4, SHAPE)
    scored = 4 * 12284 + 10
    assert indexer.rows_scored(12284, 4) == scored
    assert turn["ops"] == 2 * scored * 16 * 64 + 2 * scored * 16
    assert turn["bytes"] == 12288 * 64 * 2
    att = sparse_attention.cost(12284, 4, SHAPE)
    assert sparse_attention.rows_selected(12284, 4, 2048) == 4 * 2048
    assert att["ops"] == 2 * 2 * 4 * 2048 * 32 * 128
    assert att["bytes"] == 4 * 2048 * 2 * 4 * 128 * 2   # 16 MB of gathered rows
    # a cold 12,288-item session: the first 2048 queries attend all they see;
    # the rows are read once, not once a query
    miss = sparse_attention.cost(0, 12288, SHAPE)
    selected = 2048 * 2049 / 2 + (12288 - 2048) * 2048
    assert sparse_attention.rows_selected(0, 12288, 2048) == selected
    assert miss["ops"] == 2 * 2 * selected * 32 * 128
    assert miss["bytes"] == 12288 * 2048
    # the closed forms are the sums they stand for, and the program's own
    from incubator_predictionio_tpu.models.sparse_gqa import (
        rows_scored_selected,
    )
    for reused, new in ((0, 5), (3, 9), (2040, 20), (5000, 16), (0, 3000)):
        want = sum(min(i + 1, 2048) for i in range(reused, reused + new))
        assert sparse_attention.rows_selected(reused, new, 2048) == want
        assert rows_scored_selected(reused, new, 2048) == (
            indexer.rows_scored(reused, new), want)
    peaks = harness.load_peaks("TPU v5 lite")
    # a turn is bound by the bytes of its gathered rows, a miss by operations
    assert att["bytes"] / peaks["hbm_bytes_per_s"] \
        > att["ops"] / peaks["bf16_flops_per_s"]
    assert miss["bytes"] / peaks["hbm_bytes_per_s"] \
        < miss["ops"] / peaks["bf16_flops_per_s"]


def test_readers_on_a_recorded_trace():
    """``benchmarks/testdata/sparse_small.xplane.pb``: one turn (1x16) and one
    piece (1x2048) of the block at the cell's own widths, recorded on the v5e
    (my chip run, PR 30), with the scope map the program gave; the four new
    readers and the accepted expert reader read it, and a run with nothing to
    read reads as nothing."""
    from benchmarks import seq_trace, trace_reduce
    from benchmarks.layer_metrics import (
        indexer_roofline,
        seq_attention_share_pct,
        seq_sparse_selected_pct,
        sparse_attention_roofline,
    )

    data = os.path.join(bench_tiny.ROOT, "benchmarks", "testdata")
    with open(os.path.join(data, "sparse_small.scopes.json")) as f:
        stored = json.load(f)
    path = os.path.join(data, "sparse_small.xplane.pb")
    scopes = seq_trace.scope_seconds(path, stored["device_scopes"])
    assert set(scopes["scope_s"]) >= {
        "gqa_proj", "idx_score", "idx_select", "sparse_attn", "moe_router",
        "moe_experts"}
    reduced = trace_reduce.reduce_file(path)
    # the piece's key-tile loops are in the trace as `while` events around
    # their own operations: the scope map leaves control flow out, so the
    # scopes add up to no more than the device was busy (with the loops in
    # they came to 155 ms of 124)
    assert sum(scopes["scope_s"].values()) <= reduced["busy_s"]
    assert sum(scopes["scope_s"].values()) > 0.95 * reduced["busy_s"]
    assert not [op for found in stored["device_scopes"].values()
                for op in found if op.split(".")[0] in (
                    "while", "conditional", "call")]
    reused = np.asarray(stored["reused"])
    computed = np.asarray(stored["computed"])
    ev = {"trace": reduced, "seq_scope_s": scopes, "trace_window_s": 1.0,
          "device_scopes": stored["device_scopes"],
          "peaks": harness.load_peaks("TPU v5 lite"), "shape": SHAPE,
          "requests": {"due": np.zeros(len(reused)),
                       "ok": np.ones(len(reused), bool), "reused": reused,
                       "computed": computed},
          "metrics_before": {"pio_seq_index_rows_scored_total": 0.0,
                             "pio_seq_sparse_rows_selected_total": 0.0},
          "metrics_after": {"pio_seq_index_rows_scored_total": 400.0,
                            "pio_seq_sparse_rows_selected_total": 100.0}}
    att = sparse_attention_roofline.read(ev)
    idx = indexer_roofline.read(ev)
    share = seq_attention_share_pct.read(ev)
    assert 0.0 < att <= 100.0 and 0.0 < idx <= 100.0 and 0.0 < share <= 100.0
    under = sum(scopes["scope_s"][s] for s in seq_attention_share_pct.SCOPES)
    assert share == pytest.approx(100.0 * under / reduced["busy_s"])
    assert seq_sparse_selected_pct.read(ev) == 25.0
    for reader in (sparse_attention_roofline, indexer_roofline,
                   seq_attention_share_pct, seq_sparse_selected_pct):
        assert reader.read({}) is None
        # the latent block's cell: no such scopes, no such counters
        assert reader.read({**ev, "seq_scope_s": {
            "scope_s": {"mla_attn": 1.0}, "unscoped_s": 0.0,
            "module_runs": {}}, "metrics_after": {}, "metrics_before": {},
            "shape": {"hidden_size": 4096}}) is None


def test_benchmark_side_imports_nothing_of_the_program():
    for rel in ("benchmarks/reference/gqa_sparse_moe_ref.py",
                "benchmarks/seeded_gqa.py", "benchmarks/costs/indexer.py",
                "benchmarks/costs/sparse_attention.py"):
        with open(os.path.join(bench_tiny.ROOT, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.startswith("incubator_predictionio_tpu")
                           for n in names), (rel, names)


# -- the runner at a small size --------------------------------------------------------

def test_sound_run_is_correct_and_reports_its_layers(root):
    line = bench_tiny.run_cell(root, bench_tiny_sparse.CELL,
                               seed=2_147_483_659, seconds=3.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 75
    got = line["metrics"]
    for name in ("seq_match_ms", "seq_extend_ms", "seq_tokens_per_dispatch",
                 "seq_cache_reuse_pct", "moe_expert_load_max_over_mean",
                 "seq_sparse_selected_pct", "batcher_queue_wait_ms",
                 "deploy_restore_s", "deploy_warmup_s", "serve_p99_ms"):
        assert name in got, name
    # no device plane on the CPU: the device_trace readers say nothing
    for name in ("moe_experts_roofline", "sparse_attention_roofline",
                 "indexer_roofline", "seq_attention_share_pct"):
        assert name not in got
    assert 60.0 < got["seq_cache_reuse_pct"]["value"] < 95.0
    # sessions of 24-96 items under top-8: an eighth to a third is attended
    assert 8.0 < got["seq_sparse_selected_pct"]["value"] < 40.0
    assert line["device"]["platform"] == "cpu"  # a test, never a result


@pytest.mark.parametrize("name, fails", [
    ("sound", False), ("float8", True), ("dense", True), ("topk_half", True)])
def test_controls_fall_outside_the_limits_and_the_program_inside(
        root, name, fails):
    """The program with float8 weights, with no selection (every query
    attends to all it sees) and with half the top-k, each against the
    reference of the configuration as it stands."""
    cell = harness.resolve_cell(bench_tiny_sparse.CELL, root)
    saved, runner = dict(os.environ), control_sessions.ss
    control_sessions.ss = serve_lifelong
    try:
        got = control_sessions.numbers(
            cell, 9, jax.devices()[:1],
            lower={"float8": True, "sound": False}.get(name, name))
    finally:
        control_sessions.ss = runner
        os.environ.clear()
        os.environ.update(saved)
    assert bool(control.fails(cell, got)) == fails, got
