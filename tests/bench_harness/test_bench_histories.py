"""The histories cell (``seq-mellum2-12b-ep4.serve-histories``): its
configuration, traffic, cell file, readers and cost function resolve from the
files as they stand; the configuration keeps every published number of the
catalog's row and its ``bytes`` are the arithmetic of its own widths; the
traffic's schedule has the shares, lengths and warm pool the cell is defined
by; the real runner, generator and comparison run at a small size on the CPU;
``correct`` is true on a sound path and false with float8 weights, with the
window left out and with plain angles on the full layer; a program from
before the configuration's letter fails at once. CPU only; nothing here asks
for a chip.
"""

import ast
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import control, control_sessions, harness, loadgen_sessions
from benchmarks.costs import window_attention as window_cost
from benchmarks.runners import serve_histories, serve_lifelong

import bench_tiny
import bench_tiny_window

REAL = bench_tiny_window.REAL
NEW_METRICS = ("window_attention_roofline", "seq_window_share_pct",
               "seq_window_rows_held_pct")
SHAPE = {"hidden_size": 2304, "num_attention_heads": 32,
         "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 1024,
         "moe_intermediate_size": 896,
         "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
         "short_block": 16, "piece": 2048}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_window.make_root(str(tmp_path_factory.mktemp("window")))


# -- the files as they stand ------------------------------------------------------

def test_real_cell_resolves_with_every_reader_and_key():
    cell = harness.resolve_cell(REAL)
    runner = harness.load_runner(cell.kind)
    assert runner is serve_histories and cell.chips == 1
    harness.check_keys("traffic", cell.traffic, runner.TRAFFIC_KEYS)
    harness.check_keys("config", cell.config, runner.CONFIG_KEYS)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"seq_cache_reuse_pct", "seq_extend_ms", "seq_match_ms",
            "seq_tokens_per_dispatch", "seq_context_fill_pct",
            "seq_lock_wait_ms", "seq_turn_stage_ms", "seq_turn_launch_ms",
            "seq_turn_wait_ms", "seq_miss_extend_ms",
            "seq_turn_sessions_mean", "seq_carry_slots_live",
            "moe_expert_load_max_over_mean", "moe_experts_roofline",
            "batcher_queue_wait_ms", "batcher_slots_mean", "server_empty_pct",
            "device_idle_pct.serve", "device_idle_occupied_pct.serve",
            "deploy_warmup_s", "loadgen_lag_p99_ms", "serve_p95_ms",
            "serve_p99_ms"} <= names
    # another stack's kernels, and the reader that reads null since PR 37
    assert not names & {"ssm_scan_roofline", "moe_experts_relu2_roofline",
                        "shortconv_roofline", "mla_attention_roofline",
                        "sparse_attention_roofline", "seq_turn_device_ms"}
    for name in names:
        assert callable(harness.load_reader(name))
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_p50_ms", "serve_within_limit_pct", "serve_qps", "setup_s"}
    t = cell.traffic   # the issue's traffic, letter for letter
    assert (t["pool"], t["length_median"], t["length_sigma"], t["length_min"],
            t["length_max"], t["retire_at"]) == (
        24, 6144, 1.0, 256, 14336, 16384)
    assert (t["miss_share"], t["growth_mean"], t["growth_max"],
            t["session_zipf_s"], t["item_zipf_s"], t["num"]) == (
        0.02, 4, 16, 0.8, 1.0, 10)
    assert (t["connections"], t["prefill_connections"], t["max_batch"],
            t["trace_seconds"], t["schedule_seed"]) == (64, 4, 8, 10.0, 0)
    assert (t["check_long_over"], t["check_min_long"],
            t["check_min_wrapped"]) == (8192, 1, 1)
    assert t["rate_qps"] <= 0.6 * t["knee_qps"] + 1e-9
    assert set(t["limits"]) == {"score_gap_max", "score_gap_p50",
                                "regret_max", "recall_at_k_min",
                                "failed_share_max"}
    assert t["limits"]["failed_share_max"] == 0.001
    assert t["reuse_tolerance"] == 0.05
    # the lifelong cell's own keys at other numbers: the same generator
    theirs = harness.resolve_cell("seq-keye-vl2-30b-a3b.serve-lifelong")
    assert set(t) - set(theirs.traffic) == {
        "check_long_over", "check_min_long", "check_min_wrapped"}
    assert harness.load_runner(theirs.kind) is serve_lifelong
    assert not set(NEW_METRICS) & {m["name"] for m in theirs.per_layer}
    # only three metrics are new, none read from program spans
    bench = harness.load_benchmark()
    new = [m for m in bench["per_layer"] if m.get("workloads") == [REAL]]
    assert sorted(m["name"] for m in new) == sorted(NEW_METRICS)
    assert "program_span" not in {m["source"] for m in new}
    assert bench["workloads"][-1]["name"] == REAL
    assert bench["configs"][-1]["name"] == "seq-mellum2-12b-ep4"


def test_configuration_keeps_every_published_number():
    cfg = harness.resolve_cell(REAL).config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    published = row["config"]
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in published.items() if cfg.get(k) != v}
    assert differing == {"max_position_embeddings"}
    assert set(cfg["reduced"]) == {"num_experts", "max_position_embeddings"} \
        == set(cfg["reduced_why"])
    assert cfg["num_hidden_layers"] == 28 == len(cfg["layer_types"])
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 7
    assert cfg["vocab_size"] == 98304 and cfg["num_experts"] == 64
    assert (cfg["experts_held"], cfg["expert_offset"]) == (16, 0)
    assert cfg["max_position_embeddings"] == cfg["serve"]["max_len"] == 16384
    assert cfg["sliding_window"] == 1024
    assert cfg["rope_parameters"]["full_attention"]["attention_factor"] \
        == 1.2772588722239782
    assert (cfg["serve"]["state_slots"], cfg["serve"]["cache_tokens"],
            cfg["serve"]["cache_page"]) == (32, 17 * 16384, 128)
    assert "4 chips" in cfg["deployment"] and len(cfg["assumed"]) >= 6
    # the bytes the file states are the arithmetic of its own widths
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attention = 2 * d * h * dh + 2 * d * kv * dh + d
    expert = 3 * d * fe
    experts = 16 * expert + d * 64 + d
    b = cfg["bytes"]
    assert (b["attention_layer_params"], b["one_routed_expert_params"],
            b["expert_layer_here_params"]) == (attention, expert, experts) \
        == (21_235_968, 6_193_152, 99_240_192)
    here = 28 * (attention + experts) + 2 * 98304 * d + d
    assert b["weights_params_here"] == here and 3.82e9 < here < 3.83e9
    assert 12.1e9 < b["model_params_published"] < 12.2e9      # "12B"
    assert 2.4e9 < b["active_params_published"] < 2.5e9       # "A2.5B"
    small = 28 * (2 * d + d * 64) + d                     # kept in float32
    assert b["weights_stored_bytes"] == 2 * (here - small) + 4 * small
    assert 7.65e9 < b["weights_stored_bytes"] < 7.67e9
    assert b["cache_bytes_per_token"] == 7 * 2 * kv * dh * 2 + 4 == 14_340
    assert b["state_bytes_per_session"] == 21 * 1024 * 2 * kv * dh * 2 \
        == 44_040_192
    assert b["state_bytes"] == 33 * b["state_bytes_per_session"]
    assert b["resident_bytes"] == b["weights_stored_bytes"] \
        + b["cache_bytes"] + b["state_bytes"]
    assert 0.80 * 16e9 < b["resident_bytes"] < 0.86 * 16e9
    # a 14k session: 0.25 GB with the window, 0.82 GB without it
    assert round((14336 * 14336 + b["state_bytes_per_session"]) / 1e9, 2) \
        == 0.25
    assert round(14336 * 28 * 2048 / 1e9, 2) == 0.82


def test_the_traffics_schedule_is_the_cells():
    """Shares, lengths and the warm pool, from the schedule alone: 24
    sessions asked once in set-up, 2% misses, lengths clipped 256-14,336
    with a few per cent under the window and two fifths past 8,192."""
    cell = harness.resolve_cell(REAL)
    t = cell.traffic
    spec = {**{k: t[k] for k in t if k not in ("limits", "per_cell")},
            "seed": 11, "seconds": 51.0, "rate_qps": 20.0,
            "vocab_size": cell.config["vocab_size"]}
    plan = loadgen_sessions.plan(spec)
    pool = plan["phase"] == 0
    assert pool.sum() == 24 and (plan["kind"][pool] == 1).all()
    assert len(set(plan["sid"][pool])) == 24       # every session, once
    win = plan["phase"] == 2
    assert win.sum() == 1020
    misses = plan["kind"][win] == 1
    assert misses.sum() in (20, 21)                # 2% (and a retirement)
    turns = ~misses
    grown = plan["computed"][win][turns]
    assert grown.min() >= 1 and grown.max() <= 16 and 3.0 < grown.mean() < 5.0
    assert (plan["computed"][win][misses]
            == plan["length"][win][misses]).all()  # a miss: its whole list
    assert plan["length"].min() >= 256 and plan["length"].max() <= 16384
    # the length distribution itself (the multiset is schedule_seed's)
    many = loadgen_sessions.lengths(np.random.default_rng(0), 200_000, t)
    assert many.min() == 256 and many.max() == 14336
    assert 7000 < many.mean() < 7600
    assert 0.03 < (many < 1024).mean() < 0.05
    assert 0.37 < (many > 8192).mean() < 0.41
    # the pool holds about 175k tokens
    pools = [loadgen_sessions.lengths(np.random.default_rng(s), 24, t).sum()
             for s in range(200)]
    assert 165_000 < np.mean(pools) < 185_000
    # same schedule_seed, another seed: the same multiset of work
    other = loadgen_sessions.plan({**spec, "seed": 12})
    assert sorted(other["computed"][other["phase"] == 2]) \
        == sorted(plan["computed"][win])
    # item ids over rows 1..98303
    flat = np.concatenate(plan["sessions"])
    assert flat.min() >= 1 and flat.max() <= 98303


def test_the_generator_sends_the_same_bytes_made_before_the_first_send():
    """``loadgen_histories.py``: every request of the run, set-up and
    warm-up too, has its bytes made before the first send, and they are the
    bytes ``loadgen_sessions.py`` would format at the due instant; the
    runner starts that child, and only around its own ``drive``."""
    from benchmarks import loadgen_histories
    from benchmarks.runners import serve_sessions

    cell = harness.resolve_cell(REAL)
    t = cell.traffic
    spec = {**{k: t[k] for k in t if k not in ("limits", "per_cell")},
            "seed": 11, "seconds": 10.0, "rate_qps": 12.0, "host": "127.0.0.1",
            "port": 8000, "vocab_size": cell.config["vocab_size"]}
    made = loadgen_histories.payloads(spec)
    plan = loadgen_sessions.plan(spec)
    assert len(made) == len(plan["sid"]) == 24 + 60 + 120
    for sid, n in zip(plan["sid"], plan["length"]):
        body = made[int(sid), int(n)]
        assert body == loadgen_sessions._payload(
            "127.0.0.1", 8000, int(sid), plan["sessions"][sid][:n], 10)
    assert max(map(len, made.values())) > 100_000      # a 14k-item list
    assert serve_histories.GENERATOR == "loadgen_histories.py"
    assert serve_sessions.GENERATOR == "loadgen_sessions.py"
    assert os.path.exists(os.path.join(
        harness.BENCH_DIR, serve_histories.GENERATOR))


def test_cost_function_counts_what_the_equations_need():
    row = 2 * 4 * 128 * 2                       # a key/value row: 2,048 B
    per = 2 * 2 * 32 * 128                      # q.k and p v, a row seen
    # a lone turn of 2 items on a 5,000-item session: each query sees a
    # whole window; the 1,023 rows before the block and its own are read
    turn = window_cost.cost([5000], [2], SHAPE)
    assert turn["ops"] == per * 2 * 1024
    assert turn["bytes"] == (1023 + 2 + 2) * row
    # a session under the window sees what it has: 1 + 2 + ... + 100
    cold = window_cost.cost([0], [100], SHAPE)
    assert cold["ops"] == per * 100 * 101 // 2
    assert cold["bytes"] == (100 + 100) * row
    # across the window's edge: 24 queries still ramp, then whole windows
    edge = window_cost.cost([1000], [40], SHAPE)
    assert edge["ops"] == per * (sum(range(1001, 1024)) + 17 * 1024)
    assert edge["bytes"] == (1000 + 40 + 40) * row
    # a 3,000-item miss: the ramp, then 1,977 whole windows
    miss = window_cost.cost([0], [3000], SHAPE)
    assert miss["ops"] == per * (1023 * 1024 // 2 + 1977 * 1024)
    assert miss["bytes"] == 2 * 3000 * row
    both = window_cost.cost([5000, 0], [2, 3000], SHAPE)
    assert both["ops"] == turn["ops"] + miss["ops"]
    assert both["bytes"] == turn["bytes"] + miss["bytes"]
    peaks = harness.load_peaks("TPU v5 lite")

    def bound(c):
        return (c["bytes"] / peaks["hbm_bytes_per_s"],
                c["ops"] / peaks[c["ops_peak"]])

    assert bound(turn)[0] > 10 * bound(turn)[1]      # a turn reads a ring
    assert bound(miss)[1] > 5 * bound(miss)[0]       # a miss multiplies
    # unwindowed the same miss would see 3000 x 3001 / 2 rows
    assert miss["ops"] < per * 3000 * 3001 // 2 / 1.7


def test_readers_on_a_recorded_trace():
    """``benchmarks/testdata/window_small.xplane.pb``: one lone turn of 1
    item on a 1,500-item session (1x16@2048) and one 3,000-item miss in two
    pieces (1x2048@2048, then 952 items in 1x2048@4096) of the stack at the
    cell's own widths cut to its first four layers (WE WE WE AE: 3 window
    layers), recorded on the v5e (my chip run, PR 46), with the scope map
    the program gave; the three new readers read it, and a run with nothing
    to read reads as nothing."""
    from benchmarks import seq_trace, seq_window_trace, trace_reduce
    from benchmarks.layer_metrics import (
        moe_experts_roofline,
        seq_window_rows_held_pct,
        seq_window_share_pct,
        window_attention_roofline,
    )

    data = os.path.join(bench_tiny.ROOT, "benchmarks", "testdata")
    with open(os.path.join(data, "window_small.scopes.json")) as f:
        stored = json.load(f)
    path = os.path.join(data, "window_small.xplane.pb")
    scopes = seq_trace.scope_seconds(path, stored["device_scopes"])
    assert set(scopes["scope_s"]) == {
        "gqa_proj", "win_attn", "gqa_attn", "moe_router", "moe_experts",
        "head_topk"}
    reduced = trace_reduce.reduce_file(path)
    assert sum(scopes["scope_s"].values()) <= reduced["busy_s"]
    assert sum(scopes["scope_s"].values()) > 0.85 * reduced["busy_s"]
    assert reduced["module_runs"]["jit_seq_win_b1_t2048"] == 3 * 2
    assert reduced["module_runs"]["jit_seq_moe_b1_t2048"] == 4 * 2
    assert reduced["module_runs"]["jit_seq_gqa_b1_t2048_c2048"] == 1
    assert reduced["module_runs"]["jit_seq_gqa_b1_t2048_c4096"] == 1
    assert reduced["module_runs"]["jit_seq_turn_b1_t16_c2048"] == 1
    assert stored["reused"] == [1500, 0] and stored["computed"] == [1, 3000]
    apart = {kind: seq_trace.scope_seconds(path, {
        name: found for name, found in stored["device_scopes"].items()
        if ("_seq_turn_" in name) == (kind == "turn")})
        for kind in ("turn", "piece")}
    for name, s in scopes["scope_s"].items():   # the two parts are the whole
        assert sum(part["scope_s"].get(name, 0.0)
                   for part in apart.values()) == pytest.approx(s)
    ev = {"trace": reduced, "seq_scope_s": scopes, "trace_window_s": 1.0,
          "seq_window_scope_s": apart,
          "device_scopes": stored["device_scopes"],
          "peaks": harness.load_peaks("TPU v5 lite"), "shape": SHAPE,
          "requests": {"due": np.zeros(2), "ok": np.ones(2, bool),
                       "reused": np.asarray(stored["reused"]),
                       "computed": np.asarray(stored["computed"])},
          "metrics_before": {},
          "metrics_after": {
              # the turn read its ring's 1,024 rows and its 1 of 1,501
              "pio_seq_window_rows_held_total": 1025.0,
              "pio_seq_window_rows_unwindowed_total": 1501.0,
              # 3,001 tokens x 8 picks, a quarter of them held here
              'pio_moe_expert_tokens_total{layer="1",expert="0"}':
                  4 * 3001 * 2.0,
              'pio_moe_experts_touched_total{layer="1"}': 4 * (6 + 2 * 16.0)}}
    # the ladder's cut of the two requests: one turn, a miss in two pieces
    assert seq_window_trace.dispatches(ev) == {
        "turn": ([1500], [1]), "piece": ([0, 2048], [2048, 952])}
    long_tail = {**ev, "requests": {**ev["requests"],
                                    "computed": np.asarray([1, 2048 + 9])}}
    assert seq_window_trace.dispatches(long_tail) == {
        "turn": ([1500, 2048], [1, 9]), "piece": ([0], [2048])}
    share = window_attention_roofline.read(ev)
    # THE TURN: its 1,024 ring rows and its own, read and written once a
    # layer, at the memory's peak (its 33.6 MFLOP need a hundredth of that
    # time), x 3 layers against the time under ``win_attn`` in the one run
    # of the turn program
    peaks = ev["peaks"]
    c = window_cost.cost([1500], [1], SHAPE)
    assert c["bytes"] == (1023 + 1 + 1) * 2048
    least = c["bytes"] / peaks["hbm_bytes_per_s"]
    assert least > 10 * c["ops"] / peaks["bf16_flops_per_s"]
    assert share == pytest.approx(
        100.0 * 3 * least / apart["turn"]["scope_s"]["win_attn"], rel=1e-6)
    assert 3.0 < share < 30.0
    # the pieces (printed, not returned): the miss's 41.8 GFLOP a window
    # layer at the matrix unit's peak x 3 layers against 12 ms
    c = window_cost.cost([0, 2048], [2048, 952], SHAPE)
    assert c["ops"] == 2 * 2 * 32 * 128 * (1023 * 1024 // 2 + 1977 * 1024)
    pieces = 100.0 * 3 * c["ops"] / peaks["bf16_flops_per_s"] \
        / apart["piece"]["scope_s"]["win_attn"]
    assert 3.0 < pieces < 10.0
    both = seq_window_share_pct.read(ev)
    turn = apart["turn"]
    under = sum(turn["scope_s"][n]
                for n in ("win_attn", "gqa_attn", "gqa_proj"))
    assert both == pytest.approx(100.0 * under / (
        sum(turn["scope_s"].values()) + turn["unscoped_s"]))
    # (four layers under a head of 98,304 rows: the head is the turn's most)
    assert 10.0 < both < 30.0
    # in the chains the window layers' band is the larger part
    assert apart["piece"]["scope_s"]["win_attn"] \
        > apart["piece"]["scope_s"]["gqa_attn"]
    assert seq_window_rows_held_pct.read(ev) == pytest.approx(
        100.0 * 1025 / 1501)
    assert 0.0 < moe_experts_roofline.read(ev) <= 100.0   # the accepted one
    for reader in (window_attention_roofline, seq_window_share_pct,
                   seq_window_rows_held_pct):
        assert reader.read({}) is None
        # the short-convolution pattern's cell: no such scope or counter
        theirs = {"scope_s": {"conv_mix": 1.0, "gqa_attn": 1.0,
                              "gqa_proj": 1.0, "moe_experts": 1.0},
                  "unscoped_s": 0.0, "module_runs": {}}
        other = {**ev, "seq_scope_s": theirs,
                 "seq_window_scope_s": {"turn": theirs, "piece": theirs},
            "metrics_after": {"pio_seq_tokens_computed_total": 5.0},
            "shape": {"hidden_size": 2048, "conv_L_cache": 3,
                      "layer_types": ["conv", "full_attention"],
                      "moe_intermediate_size": 1792, "short_block": 16}}
        assert reader.read(other) is None


def test_benchmark_side_imports_nothing_of_the_program():
    for rel in ("benchmarks/reference/window_gqa_moe_ref.py",
                "benchmarks/seeded_window.py",
                "benchmarks/costs/window_attention.py",
                "benchmarks/seq_window_trace.py",
                "benchmarks/loadgen_histories.py"):   # and it imports no jax
        with open(os.path.join(bench_tiny.ROOT, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.startswith("incubator_predictionio_tpu")
                           or (n == "jax" and "loadgen" in rel)
                           for n in names), (rel, names)


def test_seeded_weights_follow_the_published_parts():
    from benchmarks import seeded_window

    cfg = harness.resolve_cell(REAL).config
    parts = seeded_window.parts(cfg)
    assert len(parts) == 56 and parts[:8] == [
        "sliding_attention", "experts"] * 3 + ["full_attention", "experts"]
    assert parts.count("sliding_attention") == 21
    assert parts.count("full_attention") == 7 and parts.count("experts") == 28
    shapes = seeded_window.layer_shapes(cfg, "experts")
    assert shapes["we1"][0] == (16, 2304, 896)
    assert shapes["w_r"][0] == (2304, 64) and "b_r" not in shapes
    for part in ("sliding_attention", "full_attention"):
        attention = seeded_window.layer_shapes(cfg, part)
        assert attention["w_q"][0] == (2304, 4096)
        assert attention["w_k"][0] == (2304, 512) and "norm_qh" not in attention
    tiny = {**cfg, "hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
            "num_experts": 8, "experts_held": 8, "moe_intermediate_size": 32,
            "vocab_size": 512}
    kinds = [sorted(seeded_window.layer_weights(3, i, tiny))[0]
             for i in range(8)]
    assert kinds == ["norm1", "norm2"] * 4
    lw = seeded_window.layer_weights(3, 0, tiny)
    again = seeded_window.layer_weights(3, 0, tiny)
    assert all((np.asarray(again[k]) == np.asarray(v)).all()
               for k, v in lw.items())
    assert lw["w_q"].dtype == jax.numpy.bfloat16
    assert seeded_window.layer_weights(3, 1, tiny)["w_r"].dtype \
        == jax.numpy.float32
    assert (np.asarray(seeded_window.layer_weights(4, 0, tiny)["w_q"])
            != np.asarray(lw["w_q"])).any()
    top = seeded_window.top_weights(3, tiny)
    assert set(top) == {"item_emb", "head", "norm_f"}     # an untied head
    # float8 moves the matrices and leaves the float32 arrays alone
    low = seeded_window.layer_weights(3, 1, tiny, "float8")
    sound = seeded_window.layer_weights(3, 1, tiny)
    assert (np.asarray(low["we1"]) != np.asarray(sound["we1"])).any()
    assert (np.asarray(low["w_r"]) == np.asarray(sound["w_r"])).all()
    # the other controls change the program, not the weights
    same = seeded_window.layer_weights(3, 0, tiny, "no_window")
    assert (np.asarray(same["w_q"]) == np.asarray(lw["w_q"])).all()


def test_the_engine_binds_the_published_keys_and_the_controls():
    from benchmarks.engines import seeded_window as engine_mod
    from incubator_predictionio_tpu.utils.params import params_from_json

    cfg = harness.resolve_cell(REAL).config

    def config(control=False):
        algo = engine_mod.SeededWindowAlgorithm(params_from_json(
            engine_mod.SeededWindowParams,
            engine_mod.algorithm_params(cfg, 1, control)))
        return algo.model_config(cfg["vocab_size"])

    c = config()
    assert c.layer_pattern == "WEWEWEAE" * 7 and c.n_layers == 56
    assert (c.sliding_window, c.rope_theta, c.head_dim, c.n_kv_heads) == (
        1024, 500000, 128, 4)
    assert dict(c.rope_parameters)["factor"] == 16
    assert dict(c.rope_parameters)["rope_type"] == "yarn"
    assert (c.router_scoring, c.experts_per_token, c.n_routed_experts,
            c.experts_held, c.tie_head) == ("softmax", 8, 64, 16, False)
    assert (c.max_len, c.cache_tokens, c.state_slots, c.index_kv_tile) == (
        16384, 278528, 32, 512)
    assert config("float8") == c
    assert config("no_yarn") == config().__class__(**{
        **c.__dict__, "rope_parameters": ()})
    wide = config("no_window")
    assert wide.sliding_window == 16384 and wide.state_slots == 3
    assert wide.layer_pattern == c.layer_pattern


# -- the runner at a small size --------------------------------------------------------

def test_sound_run_is_correct_and_reports_its_layers(root):
    line = bench_tiny.run_cell(root, bench_tiny_window.CELL,
                               seed=2_147_483_659, seconds=3.0, trace=True)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 75
    got = line["metrics"]
    for name in ("seq_match_ms", "seq_extend_ms", "seq_tokens_per_dispatch",
                 "seq_cache_reuse_pct", "seq_context_fill_pct",
                 "moe_expert_load_max_over_mean", "seq_window_rows_held_pct",
                 "seq_miss_extend_ms", "batcher_queue_wait_ms",
                 "deploy_restore_s", "deploy_warmup_s", "serve_p99_ms"):
        assert name in got, name
    assert all(set(v) == {"value", "unit"} for v in got.values())
    # no device plane on the CPU: the device_trace readers say nothing
    for name in ("window_attention_roofline", "seq_window_share_pct",
                 "moe_experts_roofline"):
        assert name not in got
    assert 75.0 < got["seq_cache_reuse_pct"]["value"] < 95.0
    # sessions of ~30 items against a window of 8: a third of the rows
    assert 15.0 < got["seq_window_rows_held_pct"]["value"] < 60.0
    assert line["device"]["platform"] == "cpu"  # a test, never a result
    untraced = bench_tiny.run_cell(root, bench_tiny_window.CELL,
                                   seed=2_147_483_660, seconds=2.0)
    assert untraced["correct"] is True and set(untraced["metrics"]) == {
        "serve_p50_ms", "serve_within_limit_pct", "serve_qps", "setup_s"}


def test_the_sample_holds_a_long_session_and_a_wrapped_ring(root):
    """Whatever the seeded sample drew: a turn past ``check_long_over``
    items, one whose ring has wrapped and, where the window has one, one
    whose session passed a multiple of the window since the server cached it
    take the place of its last turns."""
    cell = harness.resolve_cell(bench_tiny_window.CELL, root)
    n = 40
    result = {
        "ok": np.ones(n, bool), "kind": np.zeros(n, np.int64),
        "extended": np.full(n, 5), "sid": np.arange(n) % 10,
        "length": np.full(n, 7), "reused": np.full(n, 5)}
    result["kind"][[3, 7, 11]] = 1
    # one long turn, one session that grew from 14 to 17 (past 16 = 2 x 8)
    result["length"][20], result["reused"][20] = 47, 41
    result["sid"][20] = 98
    result["length"][8], result["reused"][8] = 15, 14
    result["sid"][8] = result["sid"][38] = 99
    result["length"][38], result["reused"][38] = 17, 15
    wrapped = serve_histories.wrapped(cell, result)
    assert list(np.flatnonzero(wrapped)) == [8, 20, 38]   # 8 rows or more
    # every session was cached at 5 items but 98 (at 41) and 99 (at 14)
    cached_at = {**{s: 5 for s in range(10)}, 98: 41, 99: 14}
    crossed = serve_histories.crossed(cell, result, cached_at)
    assert list(np.flatnonzero(crossed)) == [38]
    pick = serve_histories.pick_sample(cell, 7, result, cached_at)
    assert len(pick) == 8 and len(set(pick)) == 8
    assert 20 in pick and 38 in pick
    assert (result["kind"][pick] == 1).sum() == 2
    # a window in which no session crossed: the sample is still whole
    result["length"][38] = 16 - 1
    assert not serve_histories.crossed(cell, result, cached_at).any()
    pick = serve_histories.pick_sample(cell, 7, result, cached_at)
    assert len(set(pick)) == 8 and serve_histories.wrapped(
        cell, result)[pick].sum() >= 1


@pytest.mark.parametrize("name, fails", [
    ("sound", False), ("float8", True), ("no_window", True),
    ("no_yarn", True)])
def test_controls_fall_outside_the_limits_and_the_program_inside(
        tmp_path_factory, name, fails):
    """The program with float8 weights, with a window as long as the longest
    session and with plain angles on the full layer, each against the
    reference of the configuration as it stands, fall outside the limits."""
    root = bench_tiny_window.make_root(
        str(tmp_path_factory.mktemp("window_" + name)))
    cell = harness.resolve_cell(bench_tiny_window.CELL, root)
    saved = dict(os.environ)
    control_sessions.ss = serve_histories
    try:
        got = control_sessions.numbers(
            cell, 9, jax.devices()[:1],
            lower={"float8": True, "sound": False}.get(name, name))
    finally:
        os.environ.clear()
        os.environ.update(saved)
        from benchmarks.runners import serve_sessions
        control_sessions.ss = serve_sessions
    failed = control.fails(cell, got)
    assert bool(failed) == fails, got
    if name in ("no_window", "no_yarn"):
        assert "score_gap_p50" in failed, got


def test_a_program_without_the_letter_fails_at_once(root, monkeypatch):
    """The parent's failure mode: its algorithm params do not bind (unknown
    keys), before any weight is made: a ``HarnessError``, never a hang."""
    from benchmarks.engines import seeded_window as engine_mod

    params = engine_mod.algorithm_params
    monkeypatch.setattr(
        engine_mod, "algorithm_params",
        lambda *a, **k: {**params(*a, **k), "aKeyOfALaterProgram": 1})
    cell = harness.resolve_cell(bench_tiny_window.CELL, root)
    saved = dict(os.environ)
    t0 = time.time()
    try:
        with pytest.raises(harness.HarnessError,
                           match="cannot run configuration 'tiny-window'"):
            serve_histories.build_and_deploy(
                cell, 5, harness.work_dir(cell), jax.devices()[:1])
    finally:
        os.environ.clear()
        os.environ.update(saved)
    assert time.time() - t0 < 30.0
