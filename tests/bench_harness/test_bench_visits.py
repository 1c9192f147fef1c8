"""The visitor-session cell (``seq-nemotron3-nano-ep2.serve-visits``): its
configuration, traffic, cell file, readers and cost functions resolve from
the files as they stand; the real runner, generator and comparison run at a
small size on the CPU; ``correct`` is true on a sound path and false with
float8 weights, with the skip term left out, and where the timed path is
broken underneath (too few state slots: the server recomputes what the
schedule says it holds). CPU only; nothing here asks for a chip.
"""

import ast
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import control, harness
from benchmarks.costs import moe_experts, moe_experts_relu2, ssm_scan
from benchmarks.runners import serve_lifelong, serve_sessions, serve_visits

import bench_tiny
import bench_tiny_ssm

REAL = bench_tiny_ssm.REAL
NEW_METRICS = ("ssm_scan_roofline", "moe_experts_relu2_roofline",
               "seq_ssm_share_pct", "seq_turn_sessions_mean")
SHAPE = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
         "ssm_state_size": 128, "n_groups": 8, "moe_intermediate_size": 1856,
         "moe_shared_expert_intermediate_size": 3712,
         "hybrid_override_pattern": "MEMEM*EMEMEM*E", "short_block": 16}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_ssm.make_root(
        str(tmp_path_factory.mktemp("ssm")), state_slots=16)


# -- the files as they stand ------------------------------------------------------

def test_real_cell_resolves_with_every_reader_and_key():
    cell = harness.resolve_cell(REAL)
    runner = harness.load_runner(cell.kind)
    assert runner is serve_visits
    harness.check_keys("traffic", cell.traffic, runner.TRAFFIC_KEYS)
    harness.check_keys("config", cell.config, runner.CONFIG_KEYS)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"seq_cache_reuse_pct", "seq_extend_ms", "seq_match_ms",
            "seq_tokens_per_dispatch", "seq_context_fill_pct",
            "moe_expert_load_max_over_mean", "batcher_slots_mean",
            "device_idle_pct.serve", "serve_p99_ms"} <= names
    # the three-matrix expert cost would read this stack 1.5 x too high
    assert not names & {"moe_experts_roofline", "mla_attention_roofline",
                        "sparse_attention_roofline", "indexer_roofline",
                        "scorer_roofline"}
    for name in names:
        assert callable(harness.load_reader(name))
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_p50_ms", "serve_within_limit_pct", "serve_qps", "setup_s"}
    t = cell.traffic   # the issue's traffic, letter for letter
    assert (t["pool"], t["length_median"], t["length_sigma"], t["length_min"],
            t["length_max"], t["retire_at"]) == (224, 128, 1.0, 4, 2048, 2048)
    assert (t["miss_share"], t["growth_mean"], t["growth_max"],
            t["session_zipf_s"], t["item_zipf_s"], t["num"]) == (
        0.25, 2, 8, 0.8, 1.0, 10)
    assert (t["connections"], t["prefill_connections"], t["max_batch"],
            t["warmup_seconds"], t["timeout_s"]) == (64, 8, 16, 5.0, 10.0)
    assert (t["check_sample"], t["check_min_turns"], t["check_min_extended"],
            t["check_min_misses"]) == (24, 8, 3, 8)
    assert (t["check_states"], t["check_min_states"]) == (8, 4)
    assert 0 < t["limits"]["state_gap"] < 2e-3   # bfloat16 storage reads more
    assert t["rate_qps"] <= 0.8 * t["knee_qps"] + 1e-9
    # the other two sequence cells are as they were
    for name, theirs, reader in (
            ("seq-mistral-small4-ep4.serve-sessions", serve_sessions,
             "mla_attention_roofline"),
            ("seq-keye-vl2-30b-a3b.serve-lifelong", serve_lifelong,
             "sparse_attention_roofline")):
        other = harness.resolve_cell(name)
        assert harness.load_runner(other.kind) is theirs
        metrics = {m["name"] for m in other.per_layer}
        assert reader in metrics and "moe_experts_roofline" in metrics
        assert not set(NEW_METRICS) & metrics


def test_configuration_keeps_every_published_width():
    cfg = harness.resolve_cell(REAL).config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"]
                     == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")["config"]
    differing = {k for k, v in published.items() if cfg.get(k) != v}
    assert differing == {"num_hidden_layers", "hybrid_override_pattern",
                         "vocab_size", "max_position_embeddings"}
    assert differing | {"n_routed_experts"} == set(cfg["reduced"])
    assert cfg["hybrid_override_pattern"] == "MEMEM*EMEMEM*E" \
        == published["hybrid_override_pattern"][:14]
    assert cfg["num_hidden_layers"] == 14
    assert cfg["vocab_size"] == 65536 == published["vocab_size"] // 2
    assert cfg["max_position_embeddings"] == cfg["serve"]["max_len"] == 2048
    assert cfg["n_routed_experts"] == 128 and cfg["experts_held"] == 64
    assert (cfg["serve"]["state_slots"], cfg["serve"]["cache_tokens"]) == (
        256, 262144)
    # the bytes the file states are the arithmetic of its own widths
    d, f, fs = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                cfg["moe_shared_expert_intermediate_size"])
    heads, p, n, g = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                      cfg["ssm_state_size"], cfg["n_groups"])
    inner, conv = heads * p, heads * p + 2 * g * n
    mixer = d * (inner + conv + heads) + inner * d + conv * 5 + 3 * heads \
        + inner + d
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attention = 2 * d * h * dh + 2 * d * kv * dh + d
    experts = d * 128 + 128 + 2 * d * fs + 64 * 2 * d * f + d
    b = cfg["bytes"]
    assert (b["state_space_layer_params"], b["attention_layer_params"],
            b["expert_layer_here_params"]) == (mixer, attention, experts)
    weights = 2 * (6 * mixer + 2 * attention + 6 * experts) \
        + 2 * 2 * cfg["vocab_size"] * d
    assert b["weights_bytes"] == weights and 9.16e9 < weights < 9.18e9
    assert b["weights_stored_bytes"] == weights + 6 * 64 * d * 64 * 2
    assert b["state_bytes_per_session"] == 6 * (heads * p * n * 4
                                                + 3 * conv * 2) == 12_804_096
    assert b["cache_bytes_per_token"] == 2 * 2 * 2 * kv * dh + 4 == 2052
    resident = b["weights_stored_bytes"] + b["state_bytes"] + b["cache_bytes"]
    assert 0.80 * 16e9 < resident < 0.84 * 16e9


def test_cost_functions_count_what_the_equations_need():
    # a lone turn of 2 items: the state read and written once, 2 tokens' rows
    turn = ssm_scan.cost(1, 2, SHAPE)
    state = 64 * 64 * 128
    assert turn["ops"] == 4 * 2 * state
    assert turn["bytes"] == 4 * (2 * state + 2 * (2 * 4096 + 2 * 1024 + 64))
    # a 2,048-item miss from zero: the same state traffic, 2,048 tokens' work
    miss = ssm_scan.cost(1, 2048, SHAPE)
    assert miss["ops"] == 4 * 2048 * state
    assert miss["bytes"] - turn["bytes"] == 4 * 2046 * (2 * 4096 + 2048 + 64)
    peaks = harness.load_peaks("TPU v5 lite")
    # both are bound by bytes: a turn by the state's, a miss by its rows'
    # (22 operations a byte against the chip's 240)
    for c in (turn, miss):
        assert c["bytes"] / peaks["hbm_bytes_per_s"] \
            > c["ops"] / peaks["bf16_flops_per_s"]
    assert turn["bytes"] < 1.02 * 4 * 2 * state < miss["bytes"] / 20
    # 16 sessions in one dispatch read and write 16 states
    assert ssm_scan.cost(16, 32, SHAPE)["bytes"] > 15 * turn["bytes"]
    # two matrices an expert: two thirds of the accepted three-matrix cost
    two = moe_experts_relu2.cost(1000, 12, 2688, 1856)
    three = moe_experts.cost(1000, 12, 2688, 1856)
    assert two["ops"] == 2 * 2 * 1000 * 2688 * 1856 == three["ops"] * 2 / 3
    assert two["bytes"] == 12 * 2 * 2688 * 1856 * 2 + 1000 * 2688 * 6
    assert two["bytes"] < three["bytes"]


def test_readers_on_a_recorded_trace():
    """``benchmarks/testdata/ssm_small.xplane.pb``: one lone turn (1x16) and
    one 2,000-item miss (1x2048) of the stack at the cell's own widths,
    recorded on the v5e (my chip run, PR 34), with the scope map the program
    gave; the four new readers read it, and a run with nothing to read reads
    as nothing."""
    from benchmarks import seq_trace, trace_reduce
    from benchmarks.layer_metrics import (
        moe_experts_relu2_roofline,
        moe_experts_roofline,
        seq_ssm_share_pct,
        seq_turn_sessions_mean,
        ssm_scan_roofline,
    )

    data = os.path.join(bench_tiny.ROOT, "benchmarks", "testdata")
    with open(os.path.join(data, "ssm_small.scopes.json")) as f:
        stored = json.load(f)
    path = os.path.join(data, "ssm_small.xplane.pb")
    scopes = seq_trace.scope_seconds(path, stored["device_scopes"])
    assert set(scopes["scope_s"]) >= {
        "ssm_proj", "ssm_conv", "ssm_scan", "gqa_proj", "gqa_attn",
        "moe_router", "moe_experts", "moe_shared", "head_topk"}
    reduced = trace_reduce.reduce_file(path)
    # the scopes' sum against busy time: control flow is left out of the
    # map, so the scopes add up to no more than the device was busy, and to
    # nearly all of it (the embed programs are under no scope)
    assert sum(scopes["scope_s"].values()) <= reduced["busy_s"]
    assert sum(scopes["scope_s"].values()) > 0.9 * reduced["busy_s"]
    # (`while.8` / `while.9` in the map are the scan's results read out of
    # its loop, not the loop: with the loop itself in, the sum passes busy)
    assert "while" not in stored["device_scopes"]["jit_seq_ssm_b1_t2048"]
    reused = np.asarray(stored["reused"])
    computed = np.asarray(stored["computed"])
    ev = {"trace": reduced, "seq_scope_s": scopes, "trace_window_s": 1.0,
          "device_scopes": stored["device_scopes"],
          "peaks": harness.load_peaks("TPU v5 lite"), "shape": SHAPE,
          "requests": {"due": np.zeros(len(reused)),
                       "ok": np.ones(len(reused), bool), "reused": reused,
                       "computed": computed},
          "metrics_before": {},
          "metrics_after": {
              'pio_seq_state_tokens_total{form="step"}': 2.0,
              'pio_seq_state_tokens_total{form="scan"}': 2000.0,
              "pio_seq_state_step_sessions_total": 1.0,
              "pio_seq_prefill_chunks_total": 1.0,
              'pio_seq_dispatches_total{bucket="1x16@512"}': 1.0,
              'pio_seq_dispatches_total{bucket="1x2048@2048"}': 1.0,
              # 2,002 tokens x 6 picks, about half of them held here
              'pio_moe_expert_tokens_total{layer="1",expert="0"}': 36036.0,
              'pio_moe_experts_touched_total{layer="1"}': 6 * (6 + 64.0)}}
    scan = ssm_scan_roofline.read(ev)
    relu2 = moe_experts_relu2_roofline.read(ev)
    share = seq_ssm_share_pct.read(ev)
    assert 0.0 < scan <= 100.0 and 0.0 < relu2 <= 100.0
    assert 0.0 < share <= 100.0
    # the accepted three-matrix cost reads the same run 1.5 x higher in ops
    assert relu2 < moe_experts_roofline.read(ev)
    under = sum(scopes["scope_s"][s] for s in seq_ssm_share_pct.SCOPES)
    assert share == pytest.approx(100.0 * under / reduced["busy_s"])
    assert seq_turn_sessions_mean.read(ev) == 1.0
    ev["metrics_after"]["pio_seq_state_step_sessions_total"] = 3.0
    assert seq_turn_sessions_mean.read(ev) == 3.0
    for reader in (ssm_scan_roofline, moe_experts_relu2_roofline,
                   seq_ssm_share_pct, seq_turn_sessions_mean):
        assert reader.read({}) is None
        # the latent block's cell: no such scopes, no such counters
        assert reader.read({**ev, "seq_scope_s": {
            "scope_s": {"mla_attn": 1.0, "moe_experts": 1.0},
            "unscoped_s": 0.0, "module_runs": {}},
            "metrics_after": {
                'pio_moe_expert_tokens_total{layer="1",expert="0"}': 9.0,
                'pio_moe_experts_touched_total{layer="1"}': 3.0},
            "metrics_before": {},
            "shape": {"hidden_size": 4096, "moe_intermediate_size": 2048,
                      "short_block": 16}}) is None


def test_benchmark_side_imports_nothing_of_the_program():
    for rel in ("benchmarks/reference/ssm_gqa_moe_ref.py",
                "benchmarks/seeded_ssm.py", "benchmarks/costs/ssm_scan.py",
                "benchmarks/costs/moe_experts_relu2.py"):
        with open(os.path.join(bench_tiny.ROOT, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.startswith("incubator_predictionio_tpu")
                           for n in names), (rel, names)


def test_seeded_weights_follow_the_pattern_and_the_initialisation():
    from benchmarks import seeded_ssm

    cfg = harness.resolve_cell(bench_tiny_ssm.REAL).config
    tiny = {**cfg, "hidden_size": 64, "mamba_num_heads": 8,
            "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
            "n_routed_experts": 8, "experts_held": 8,
            "moe_intermediate_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "moe_shared_expert_intermediate_size": 48}
    kinds = [sorted(seeded_ssm.layer_weights(3, i, tiny))[0]
             for i in range(7)]
    assert kinds == ["a_log", "b_r", "a_log", "b_r", "a_log", "norm1", "b_r"]
    lw = seeded_ssm.layer_weights(3, 0, tiny)
    step = np.log1p(np.exp(np.asarray(lw["dt_bias"])))
    assert (step >= 1e-3 * 0.999).all() and (step <= 0.1 * 1.001).all()
    a = np.exp(np.asarray(lw["a_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    assert (np.asarray(lw["d_skip"]) == 1).all()
    assert not np.asarray(
        seeded_ssm.layer_weights(3, 0, tiny, "no_skip")["d_skip"]).any()
    again = seeded_ssm.layer_weights(3, 0, tiny)
    assert all((np.asarray(again[k]) == np.asarray(v)).all()
               for k, v in lw.items())
    assert lw["w_in"].dtype == jax.numpy.bfloat16
    assert (np.asarray(seeded_ssm.layer_weights(4, 0, tiny)["w_in"])
            != np.asarray(lw["w_in"])).any()


# -- the runner at a small size --------------------------------------------------------

def test_sound_run_is_correct_and_reports_its_layers(root):
    line = bench_tiny.run_cell(root, bench_tiny_ssm.CELL,
                               seed=2_147_483_659, seconds=3.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 75
    got = line["metrics"]
    for name in ("seq_match_ms", "seq_extend_ms", "seq_tokens_per_dispatch",
                 "seq_cache_reuse_pct", "seq_context_fill_pct",
                 "moe_expert_load_max_over_mean", "seq_turn_sessions_mean",
                 "batcher_queue_wait_ms", "deploy_restore_s",
                 "deploy_warmup_s", "serve_p99_ms"):
        assert name in got, name
    # no device plane on the CPU: the device_trace readers say nothing
    for name in ("ssm_scan_roofline", "moe_experts_relu2_roofline",
                 "seq_ssm_share_pct"):
        assert name not in got
    assert 60.0 < got["seq_cache_reuse_pct"]["value"] < 85.0
    assert 1.0 <= got["seq_turn_sessions_mean"]["value"] < 4.0
    assert line["device"]["platform"] == "cpu"  # a test, never a result


def test_a_run_whose_state_is_evicted_underneath_is_not_correct(
        tmp_path_factory):
    """Six slots for a pool of twelve sessions: every answer is still the
    reference's, but the server recomputes what the schedule says it holds,
    and the served reuse share gives it away."""
    small = bench_tiny_ssm.make_root(
        str(tmp_path_factory.mktemp("ssm_small")), state_slots=6)
    line = bench_tiny.run_cell(small, bench_tiny_ssm.CELL,
                               seed=2_147_483_659, seconds=3.0)
    assert line["correct"] is False and line["failed"] == 0


@pytest.mark.parametrize("name, fails", [
    ("sound", False), ("float8", True), ("no_skip", True),
    ("state_bf16", True)])
def test_controls_fall_outside_the_limits_and_the_program_inside(
        root, name, fails):
    """The program with float8 weights, with the skip term left out and with
    the recurrent state kept in bfloat16 between requests, each against the
    reference of the configuration as it stands, fall outside the limits.
    The state's precision is seen by ``state_gap`` alone: its part of a
    logit is two decades under what bfloat16 weights already move. The skip
    term is no part of the state, and ``state_gap`` does not see it."""
    cell = harness.resolve_cell(bench_tiny_ssm.CELL, root)
    saved = dict(os.environ)
    try:
        got = serve_visits.control_numbers(
            cell, 9, jax.devices()[:1],
            lower={"float8": True, "sound": False}.get(name, name))
    finally:
        os.environ.clear()
        os.environ.update(saved)
    failed = control.fails(cell, got)
    assert bool(failed) == fails, got
    if name == "state_bf16":
        assert failed == ["state_gap"], got
        assert got["state_gap"] > 5 * cell.traffic["limits"]["state_gap"]
    if name in ("sound", "no_skip"):
        assert got["state_gap"] < cell.traffic["limits"]["state_gap"] / 5
