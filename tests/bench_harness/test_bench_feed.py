"""The feed cell (``seq-lfm2-8b-a1b-ep2.serve-feed``): its configuration,
traffic, cell file, readers and cost function resolve from the files as they
stand; the configuration keeps every published number of the catalog's row
and its ``bytes`` are the arithmetic of its own widths; the real runner,
generator and comparison run at a small size on the CPU; ``correct`` is true
on a sound path and false with float8 weights and with every turn started
from a zero carry; a program from before the configuration's letters fails
at once. CPU only; nothing here asks for a chip.
"""

import ast
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import control, harness
from benchmarks.costs import short_conv as conv_cost
from benchmarks.runners import serve_feed, serve_visits

import bench_tiny
import bench_tiny_conv

REAL = bench_tiny_conv.REAL
NEW_METRICS = ("shortconv_roofline", "seq_conv_share_pct",
               "seq_carry_slots_live")
SHAPE = {"hidden_size": 2048, "conv_L_cache": 3, "moe_intermediate_size": 1792,
         "layer_types": ["conv", "conv", "full_attention", "conv"] * 6,
         "short_block": 16}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_conv.make_root(str(tmp_path_factory.mktemp("conv")))


# -- the files as they stand ------------------------------------------------------

def test_real_cell_resolves_with_every_reader_and_key():
    cell = harness.resolve_cell(REAL)
    runner = harness.load_runner(cell.kind)
    assert runner is serve_feed
    harness.check_keys("traffic", cell.traffic, runner.TRAFFIC_KEYS)
    harness.check_keys("config", cell.config, runner.CONFIG_KEYS)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"seq_cache_reuse_pct", "seq_extend_ms", "seq_match_ms",
            "seq_tokens_per_dispatch", "seq_context_fill_pct",
            "moe_expert_load_max_over_mean", "moe_experts_roofline",
            "seq_turn_sessions_mean", "seq_turn_launch_ms",
            "batcher_slots_mean", "device_idle_pct.serve",
            "serve_p99_ms"} <= names
    # another stack's kernels, and the reader that reads null since PR 37
    assert not names & {"ssm_scan_roofline", "moe_experts_relu2_roofline",
                        "seq_ssm_share_pct", "mla_attention_roofline",
                        "sparse_attention_roofline", "seq_turn_device_ms"}
    for name in names:
        assert callable(harness.load_reader(name))
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_p50_ms", "serve_within_limit_pct", "serve_qps", "setup_s"}
    t = cell.traffic   # the issue's traffic, letter for letter
    assert (t["pool"], t["length_median"], t["length_sigma"], t["length_min"],
            t["length_max"], t["retire_at"]) == (1024, 160, 1.0, 8, 4096, 4096)
    assert (t["miss_share"], t["growth_mean"], t["growth_max"],
            t["session_zipf_s"], t["item_zipf_s"], t["num"]) == (
        0.10, 2, 8, 0.8, 1.0, 10)
    assert (t["connections"], t["prefill_connections"], t["max_batch"],
            t["warmup_seconds"], t["timeout_s"]) == (64, 8, 16, 5.0, 10.0)
    assert (t["check_sample"], t["check_min_turns"], t["check_min_extended"],
            t["check_min_misses"]) == (24, 8, 3, 8)
    assert (t["check_states"], t["check_min_states"]) == (8, 4)
    assert 0 < t["limits"]["carry_gap"] < 0.1
    assert t["rate_qps"] <= 0.7 * t["knee_qps"] + 1e-9
    # the visitor cell's own keys at other numbers: the same generator
    theirs = harness.resolve_cell("seq-nemotron3-nano-ep2.serve-visits")
    assert set(theirs.traffic) - {"limits"} == set(t) - {"limits"}
    assert harness.load_runner(theirs.kind) is serve_visits
    assert not set(NEW_METRICS) & {m["name"] for m in theirs.per_layer}


def test_configuration_keeps_every_published_number():
    cfg = harness.resolve_cell(REAL).config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
    published = row["config"]
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in published.items() if cfg.get(k) != v}
    assert differing == {"max_position_embeddings"}
    assert set(cfg["reduced"]) == {"num_experts", "max_position_embeddings"} \
        == set(cfg["reduced_why"])
    assert cfg["num_hidden_layers"] == 24 == len(cfg["layer_types"])
    assert cfg["layer_types"].count("conv") == 18
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert cfg["vocab_size"] == 65536 and cfg["num_experts"] == 32
    assert (cfg["experts_held"], cfg["expert_offset"]) == (16, 0)
    assert cfg["max_position_embeddings"] == cfg["serve"]["max_len"] == 4096
    assert (cfg["serve"]["state_slots"], cfg["serve"]["cache_tokens"]) == (
        2048, 327680)
    # the bytes the file states are the arithmetic of its own widths
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    h, kv, taps = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["conv_L_cache"])
    dh = d // h
    conv = d * 3 * d + taps * d + d * d + d
    attention = 2 * d * h * dh + 2 * d * kv * dh + d + 2 * dh
    dense = 3 * d * f + d
    expert = 3 * d * fe
    experts = 16 * expert + d * 32 + 32 + d
    b = cfg["bytes"]
    assert (b["conv_layer_params"], b["attention_layer_params"],
            b["dense_layer_params"], b["one_routed_expert_params"],
            b["expert_layer_here_params"]) == (
        conv, attention, dense, expert, experts) == (
        16_785_408, 10_487_936, 44_042_240, 11_010_048, 176_228_384)
    here = 18 * conv + 6 * attention + 2 * dense + 22 * experts \
        + 65536 * d + d
    assert b["weights_params_here"] == here and 4.464e9 < here < 4.465e9
    assert 8.33e9 < b["model_params_published"] < 8.35e9   # "8.3B"
    small = 18 * (taps * d + d) + 6 * (d + 2 * dh) + 2 * d \
        + 22 * (d * 32 + 32 + d) + d                       # kept in float32
    assert b["weights_stored_bytes"] == 2 * (here - small) + 4 * small
    assert 8.92e9 < b["weights_stored_bytes"] < 8.94e9
    assert b["cache_bytes_per_token"] == 6 * 2 * kv * dh * 2 + 4 == 12_292
    assert b["state_bytes_per_session"] == 18 * (taps - 1) * d * 2 == 147_456
    assert b["resident_bytes"] == b["weights_stored_bytes"] \
        + b["cache_bytes"] + b["state_bytes"]
    assert 0.80 * 16e9 < b["resident_bytes"] < 0.86 * 16e9


def test_cost_function_counts_what_the_equations_need():
    d = 2048
    weights = 2 * 4 * d * d + 4 * (3 * d + d)
    # a lone turn of 2 items: the weights once, a carry read and written
    turn = conv_cost.cost(1, 1, 2, SHAPE)
    assert turn["bytes"] == weights + 2 * 8 * d + 2 * 2 * 2 * d
    assert turn["ops"] == 2 * (2 * 4 * d * d + 2 * 3 * d + 2 * d)
    # 16 sessions in one dispatch read the weights once and 16 carries
    batch = conv_cost.cost(1, 16, 32, SHAPE)
    assert batch["bytes"] - turn["bytes"] == 30 * 8 * d + 15 * 8 * d
    assert batch["bytes"] < 1.05 * turn["bytes"]
    # a 1,024-item miss: the same weights, 1,024 tokens' rows and work
    miss = conv_cost.cost(1, 1, 1024, SHAPE)
    assert miss["ops"] == 512 * turn["ops"]
    peaks = harness.load_peaks("TPU v5 lite")

    def bound(c):
        return (c["bytes"] / peaks["hbm_bytes_per_s"],
                c["ops"] / peaks["bf16_flops_per_s"])

    assert bound(turn)[0] > 50 * bound(turn)[1]      # a turn reads weights
    assert bound(miss)[1] > bound(miss)[0]           # a long block multiplies
    # the same count whatever implements it: nothing but shapes goes in
    assert conv_cost.cost(2, 2, 4, SHAPE)["ops"] == 2 * turn["ops"]


def test_readers_on_a_recorded_trace():
    """``benchmarks/testdata/conv_small.xplane.pb``: one lone turn of 1 item
    (1x16@1024) and one 1,000-item miss (1x1024@1024) of the stack at the
    cell's own widths cut to its first four layers (CD CD AE CE: 3
    convolution layers), recorded on the v5e (my chip run, PR 38), with the
    scope map the program gave; the three new readers read it, and a run with
    nothing to read reads as nothing."""
    from benchmarks import seq_trace, trace_reduce
    from benchmarks.layer_metrics import (
        moe_experts_roofline,
        seq_carry_slots_live,
        seq_conv_share_pct,
        shortconv_roofline,
    )

    data = os.path.join(bench_tiny.ROOT, "benchmarks", "testdata")
    with open(os.path.join(data, "conv_small.scopes.json")) as f:
        stored = json.load(f)
    path = os.path.join(data, "conv_small.xplane.pb")
    scopes = seq_trace.scope_seconds(path, stored["device_scopes"])
    assert set(scopes["scope_s"]) == {
        "conv_proj", "conv_mix", "ffn_dense", "gqa_proj", "gqa_attn",
        "moe_router", "moe_experts", "head_topk"}
    reduced = trace_reduce.reduce_file(path)
    assert sum(scopes["scope_s"].values()) <= reduced["busy_s"]
    assert sum(scopes["scope_s"].values()) > 0.85 * reduced["busy_s"]
    assert reduced["module_runs"]["jit_seq_conv_b1_t1024"] == 3
    assert reduced["module_runs"]["jit_seq_turn_b1_t16_c1024"] == 1
    shape = {**SHAPE, "layer_types": SHAPE["layer_types"][:4]}
    ev = {"trace": reduced, "seq_scope_s": scopes, "trace_window_s": 1.0,
          "device_scopes": stored["device_scopes"],
          "peaks": harness.load_peaks("TPU v5 lite"), "shape": shape,
          "requests": {"due": np.zeros(2), "ok": np.ones(2, bool),
                       "reused": np.asarray(stored["reused"]),
                       "computed": np.asarray(stored["computed"])},
          "metrics_before": {},
          "metrics_after": {
              'pio_seq_state_tokens_total{form="step"}': 1.0,
              'pio_seq_state_tokens_total{form="scan"}': 1000.0,
              "pio_seq_state_step_sessions_total": 1.0,
              "pio_seq_prefill_chunks_total": 1.0,
              'pio_seq_state_slots{state="used"}': 3.0,
              'pio_seq_state_slots{state="capacity"}': 8.0,
              # 1,001 tokens x 4 picks, about half of them held here
              'pio_moe_expert_tokens_total{layer="5",expert="0"}': 4004.0,
              'pio_moe_experts_touched_total{layer="5"}': 2 * (4 + 16.0)}}
    conv = shortconv_roofline.read(ev)
    # the long block's three runs: 1,000 tokens' 33.6 GFLOP a layer at the
    # matrix unit's peak against 0.266 ms a run
    runs_s = reduced["module_s"]["jit_seq_conv_b1_t1024"]
    ops = 1000 * (2 * 4 * 2048 * 2048 + 2 * 3 * 2048 + 2 * 2048)
    assert conv == pytest.approx(
        100.0 * 3 * ops / ev["peaks"]["bf16_flops_per_s"] / runs_s, rel=1e-6)
    assert 50.0 < conv < 80.0
    share = seq_conv_share_pct.read(ev)
    under = scopes["scope_s"]["conv_proj"] + scopes["scope_s"]["conv_mix"]
    assert share == pytest.approx(100.0 * under / reduced["busy_s"])
    assert 5.0 < share < 15.0
    assert seq_carry_slots_live.read(ev) == 37.5
    assert 0.0 < moe_experts_roofline.read(ev) <= 100.0   # the accepted one
    # the turn's own convolution time is NOT what the share is read from:
    # its three sub-blocks' scopes read under the floor of their 100.7 MB
    turn_conv = under - runs_s
    floor = 3 * 2 * 4 * 2048 * 2048 / ev["peaks"]["hbm_bytes_per_s"]
    assert turn_conv < floor
    for reader in (shortconv_roofline, seq_conv_share_pct,
                   seq_carry_slots_live):
        assert reader.read({}) is None
        # the state-space pattern's cell: no such scopes or programs (its
        # slots it does have: the gauge is the program's, not this stack's)
        other = {**ev, "trace": {**reduced, "module_s": {
            "jit_seq_ssm_b1_t128": 1.0}, "module_runs": {
            "jit_seq_ssm_b1_t128": 1}}, "seq_scope_s": {
            "scope_s": {"ssm_scan": 1.0, "moe_experts": 1.0},
            "unscoped_s": 0.0, "module_runs": {}},
            "shape": {"hidden_size": 2688, "moe_intermediate_size": 1856,
                      "short_block": 16}}
        if reader is not seq_carry_slots_live:
            assert reader.read(other) is None


def test_benchmark_side_imports_nothing_of_the_program():
    for rel in ("benchmarks/reference/conv_gqa_moe_ref.py",
                "benchmarks/seeded_conv.py", "benchmarks/costs/short_conv.py"):
        with open(os.path.join(bench_tiny.ROOT, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.startswith("incubator_predictionio_tpu")
                           for n in names), (rel, names)


def test_seeded_weights_follow_the_published_parts():
    from benchmarks import seeded_conv

    cfg = harness.resolve_cell(REAL).config
    parts = seeded_conv.parts(cfg)
    assert len(parts) == 48 and parts[:6] == [
        "conv", "dense", "conv", "dense", "full_attention", "experts"]
    assert parts.count("conv") == 18 and parts.count("experts") == 22
    shapes = seeded_conv.layer_shapes(cfg, "experts")
    assert shapes["we1"][0] == (16, 2048, 1792)
    assert shapes["w_r"][0] == (2048, 32) and "ws1" not in shapes
    assert seeded_conv.layer_shapes(cfg, "conv")["w_in"][0] == (2048, 6144)
    assert seeded_conv.layer_shapes(cfg, "full_attention")["norm_qh"][0] \
        == (64,)
    tiny = {**cfg, "hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 96,
            "layer_types": ["conv", "conv", "full_attention", "conv"],
            "num_experts": 8, "experts_held": 8, "moe_intermediate_size": 32,
            "vocab_size": 512}
    kinds = [sorted(seeded_conv.layer_weights(3, i, tiny))[0]
             for i in range(8)]
    assert kinds == ["conv_w", "norm1", "conv_w", "norm1", "norm1", "b_r",
                     "conv_w", "b_r"]
    lw = seeded_conv.layer_weights(3, 0, tiny)
    again = seeded_conv.layer_weights(3, 0, tiny)
    assert all((np.asarray(again[k]) == np.asarray(v)).all()
               for k, v in lw.items())
    assert lw["w_in"].dtype == jax.numpy.bfloat16
    assert lw["conv_w"].dtype == jax.numpy.float32
    assert (np.asarray(seeded_conv.layer_weights(4, 0, tiny)["w_in"])
            != np.asarray(lw["w_in"])).any()
    top = seeded_conv.top_weights(3, tiny)
    assert set(top) == {"item_emb", "norm_f"}             # the head is tied
    # float8 moves the matrices and leaves the float32 arrays alone
    low = seeded_conv.layer_weights(3, 0, tiny, "float8")
    assert (np.asarray(low["w_in"]) != np.asarray(lw["w_in"])).any()
    assert (np.asarray(low["conv_w"]) == np.asarray(lw["conv_w"])).all()


# -- the runner at a small size --------------------------------------------------------

def test_sound_run_is_correct_and_reports_its_layers(root):
    line = bench_tiny.run_cell(root, bench_tiny_conv.CELL,
                               seed=2_147_483_659, seconds=3.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 75
    got = line["metrics"]
    for name in ("seq_match_ms", "seq_extend_ms", "seq_tokens_per_dispatch",
                 "seq_cache_reuse_pct", "seq_context_fill_pct",
                 "moe_expert_load_max_over_mean", "seq_turn_sessions_mean",
                 "seq_carry_slots_live", "batcher_queue_wait_ms",
                 "deploy_restore_s", "deploy_warmup_s", "serve_p99_ms"):
        assert name in got, name
    # no device plane on the CPU: the device_trace readers say nothing
    for name in ("shortconv_roofline", "seq_conv_share_pct",
                 "moe_experts_roofline"):
        assert name not in got
    assert 80.0 < got["seq_cache_reuse_pct"]["value"] < 95.0
    assert 1.0 <= got["seq_turn_sessions_mean"]["value"] < 4.0
    assert 0.0 < got["seq_carry_slots_live"]["value"] <= 100.0
    assert line["device"]["platform"] == "cpu"  # a test, never a result


@pytest.mark.parametrize("name, fails", [
    ("sound", False), ("float8", True), ("zero_carry", True)])
def test_controls_fall_outside_the_limits_and_the_program_inside(
        tmp_path_factory, monkeypatch, name, fails):
    """The program with float8 weights and the program that starts every
    turn from a zero carry, each against the reference of the configuration
    as it stands, fall outside the limits. (A root of its own a control, and
    another number of slots: a step once traced for a configuration is found
    again by every later deploy of this process, with the slot read it had
    then.)"""
    from incubator_predictionio_tpu.models import short_conv

    monkeypatch.setattr(short_conv, "slot_rows", short_conv.slot_rows)
    slots = {"sound": 16, "float8": 16, "zero_carry": 15}[name]
    root = bench_tiny_conv.make_root(
        str(tmp_path_factory.mktemp("conv_" + name)), state_slots=slots)
    cell = harness.resolve_cell(bench_tiny_conv.CELL, root)
    saved = dict(os.environ)
    try:
        got = serve_feed.control_numbers(
            cell, 9, jax.devices()[:1],
            lower={"float8": True, "sound": False}.get(name, name))
    finally:
        os.environ.clear()
        os.environ.update(saved)
    failed = control.fails(cell, got)
    assert bool(failed) == fails, got
    if name == "zero_carry":
        # the turns grow by 2 items: the carry written is the block's own,
        # the answers are what give a zero carry away
        assert "score_gap_p50" in failed, got
    if name == "sound":
        assert got["carry_gap"] < cell.traffic["limits"]["carry_gap"] / 5


def test_a_program_without_the_letters_fails_at_once(root, monkeypatch):
    """The parent's failure mode: its algorithm params do not bind (unknown
    keys), before any weight is made: a ``HarnessError``, never a hang."""
    from benchmarks.engines import seeded_conv as engine_mod

    params = engine_mod.algorithm_params
    monkeypatch.setattr(
        engine_mod, "algorithm_params",
        lambda *a, **k: {**params(*a, **k), "aKeyOfALaterProgram": 1})
    cell = harness.resolve_cell(bench_tiny_conv.CELL, root)
    saved = dict(os.environ)
    t0 = time.time()
    try:
        with pytest.raises(harness.HarnessError,
                           match="cannot run configuration 'tiny-conv'"):
            serve_feed.build_and_deploy(
                cell, 5, harness.work_dir(cell), jax.devices()[:1])
    finally:
        os.environ.clear()
        os.environ.update(saved)
    assert time.time() - t0 < 30.0
