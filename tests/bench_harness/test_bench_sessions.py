"""The sequence-serving cell (``seq-mistral-small4-ep4.serve-sessions``): its
configuration, traffic, cell file, readers and cost functions resolve from
the files as they stand; the real runner, generator and comparison run at a
small size on the CPU; ``correct`` is true on a sound path and false with a
stale cache, a dropped expert contribution and the control; the generator's
work is the same across seeds. CPU only; nothing here asks for a chip.
"""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import (
    control,
    control_sessions,
    harness,
    loadgen_sessions,
    sweep_sessions,
)
from benchmarks.costs import mla_attention, moe_experts
from benchmarks.runners import serve_sessions

import bench_tiny
import bench_tiny_seq

REAL = "seq-mistral-small4-ep4.serve-sessions"
NEW_METRICS = ("seq_match_ms", "seq_extend_ms", "seq_tokens_per_dispatch",
               "seq_cache_reuse_pct", "moe_expert_load_max_over_mean",
               "moe_experts_roofline", "mla_attention_roofline")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_seq.make_root(str(tmp_path_factory.mktemp("seq")))


# -- the files as they stand ------------------------------------------------------

def test_real_cell_resolves_with_every_reader_and_key():
    cell = harness.resolve_cell(REAL)
    runner = harness.load_runner(cell.kind)
    assert runner is serve_sessions
    harness.check_keys("traffic", cell.traffic, runner.TRAFFIC_KEYS)
    harness.check_keys("config", cell.config, runner.CONFIG_KEYS)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert not names & {"scorer_roofline", "retrieval_lookup_ms",
                        "retrieval_rows_ms", "retrieval_dispatch_p50_ms"}
    for name in names:
        assert callable(harness.load_reader(name))
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_p50_ms", "serve_within_limit_pct", "serve_qps", "setup_s"}
    t = cell.traffic   # the issue's traffic, letter for letter
    assert (t["pool"], t["length_median"], t["length_sigma"], t["length_min"],
            t["length_max"], t["retire_at"]) == (192, 1024, 0.7, 64, 3072, 4096)
    assert (t["miss_share"], t["growth_mean"], t["growth_max"],
            t["session_zipf_s"], t["item_zipf_s"]) == (0.1, 4, 16, 0.8, 1.0)
    assert (t["num"], t["connections"], t["max_batch"], t["timeout_s"],
            t["warmup_seconds"], t["schedule_seed"]) == (10, 64, 64, 10.0, 5.0, 0)
    assert t["rate_qps"] == pytest.approx(0.8 * t["knee_qps"])
    assert (t["check_sample"], t["check_min_turns"], t["check_min_extended"],
            t["check_min_misses"]) == (24, 8, 3, 4)


def test_configuration_keeps_every_published_width():
    cell = harness.resolve_cell(REAL)
    cfg, traffic = cell.config, cell.traffic
    published = {
        "hidden_size": 4096, "num_attention_heads": 32, "q_lora_rank": 1024,
        "kv_lora_rank": 256, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "n_routed_experts": 128, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "moe_intermediate_size": 2048,
        "first_k_dense_replace": 0, "rms_norm_eps": 1e-6,
        "routed_scaling_factor": 1, "tie_word_embeddings": False,
        "hidden_act": "silu", "model_type": "mistral4"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "max_position_embeddings"]
    assert (cfg["num_hidden_layers"], cfg["experts_held"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (6, 32, 32768, 4096)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    # the byte count written in the file is the arithmetic of its own keys
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h = cfg["num_attention_heads"]
    outside = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * 128
               + d * 320 + cfg["kv_lora_rank"] * h * 192 + h * 128 * d
               + 3 * d * f + d * cfg["n_routed_experts"])
    layer = outside + cfg["experts_held"] * 3 * d * f
    weights = 2 * (cfg["num_hidden_layers"] * layer
                   + 2 * cfg["vocab_size"] * d)
    assert weights == pytest.approx(cfg["bytes"]["weights_bytes"], rel=0.002)
    # the cache row is the 320-value latent padded to whole 128-lane tiles,
    # plus the token id: what the program's status page reports
    per_token = cfg["num_hidden_layers"] * 384 * 2 + 4
    assert per_token == cfg["bytes"]["latent_cache_bytes_per_token"]
    # sized by the operator: live sessions x the length they may reach
    assert cfg["serve"]["cache_tokens"] == \
        traffic["pool"] * traffic["retire_at"]
    assert cfg["bytes"]["latent_cache_bytes"] == \
        per_token * cfg["serve"]["cache_tokens"]


def test_cost_functions_count_what_the_equations_need():
    shape = {"num_attention_heads": 32, "kv_lora_rank": 256,
             "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "v_head_dim": 128}
    up = mla_attention.cost(0, 1024, "up", shape)
    pairs = 1024 * 1025 / 2
    assert up["bytes"] == 1024 * 320 * 2
    assert up["ops"] == 2 * 1024 * 256 * 32 * 192 + 2 * 32 * pairs * 256
    turn = mla_attention.cost(1020, 4, "absorbed", shape)
    pairs = 4 * 1020 + 10
    assert turn["bytes"] == 1024 * 320 * 2
    assert turn["ops"] == 2 * 4 * 32 * 256 * 192 + 2 * 32 * pairs * 576
    # a cold kilotoken is bound by its operations, not by the cache read
    peaks = harness.load_peaks("TPU v5 lite")
    assert up["bytes"] / peaks["hbm_bytes_per_s"] \
        < up["ops"] / peaks["bf16_flops_per_s"]
    c = moe_experts.cost(100, 8, 4096, 2048)
    assert c["ops"] == 6 * 100 * 4096 * 2048
    assert c["bytes"] == 8 * 3 * 4096 * 2048 * 2 + 100 * 4096 * 6


def test_benchmark_side_imports_nothing_of_the_program():
    for rel in ("benchmarks/reference/mla_moe_ref.py",
                "benchmarks/seeded_seq.py", "benchmarks/loadgen_sessions.py",
                "benchmarks/seq_trace.py", "benchmarks/costs/moe_experts.py",
                "benchmarks/costs/mla_attention.py"):
        with open(os.path.join(bench_tiny.ROOT, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.startswith("incubator_predictionio_tpu")
                           for n in names), (rel, names)
            if rel.endswith("loadgen_sessions.py"):
                assert "jax" not in names


def test_the_two_references_give_identical_outputs(root):
    from benchmarks import seeded_seq
    from benchmarks.reference import mla_moe_ref as theirs
    from incubator_predictionio_tpu.models.reference import mla_moe as ours

    cfg = harness.resolve_cell(bench_tiny_seq.CELL, root).config
    shape = seeded_seq.shape_config(cfg)
    params = seeded_seq.top_weights(3, cfg)
    params["layers"] = [seeded_seq.layer_weights(3, i, cfg) for i in range(2)]
    tokens = np.random.default_rng(0).integers(1, 512, 50)
    a = jax.jit(lambda p: ours.forward(p, tokens, shape))(params)
    b = jax.jit(lambda p: theirs.forward(p, tokens, shape))(params)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(a).max()) > 0.5   # logits of unit scale, not zeros


def test_device_seconds_by_scope_on_a_recorded_trace():
    """``benchmarks/testdata/seq_small.xplane.pb``: one dispatch of one turn
    (4x16@4096, absorbed) and one cold 100-token session (1x128@128, up) at
    the cell's own widths, recorded on the v5e (my chip run, PR 26), with the
    scope map the program gave for those executables."""
    from benchmarks import seq_trace

    data = os.path.join(bench_tiny.ROOT, "benchmarks", "testdata")
    with open(os.path.join(data, "seq_small.scopes.json")) as f:
        scopes = json.load(f)
    got = seq_trace.scope_seconds(
        os.path.join(data, "seq_small.xplane.pb"), scopes)
    assert got["module_runs"] == {
        "jit_seq_layer_b4_t16_c4096": 6, "jit_seq_head_b4_t16_c4096": 1,
        "jit_seq_layer_b1_t128_c128": 6, "jit_seq_head_b1_t128_c128": 1}
    assert set(got["scope_s"]) == {"mla_proj", "mla_attn", "moe_router",
                                   "moe_experts", "moe_shared", "head_topk"}
    # the grouped matmul comes back from the compiler without its scope and
    # is placed by the program's map all the same: it is most of the time
    assert got["scope_s"]["moe_experts"] == pytest.approx(0.02344, rel=0.01)
    assert got["scope_s"]["mla_attn"] == pytest.approx(0.001834, rel=0.01)
    total = sum(got["scope_s"].values()) + got["unscoped_s"]
    assert got["unscoped_s"] < 0.06 * total
    # a program that gives no map, or other executables, reads as nothing
    assert seq_trace.scope_seconds(
        os.path.join(data, "seq_small.xplane.pb"), {}) == {
        "scope_s": {}, "unscoped_s": 0.0, "module_runs": {}}
    assert seq_trace.traced_scopes({"trace": {"busy_s": 1.0}}) is None


# -- the generator -------------------------------------------------------------------

def _spec(root, seed, rate=30.0, seconds=4.0):
    cell = harness.resolve_cell(bench_tiny_seq.CELL, root)
    path = serve_sessions.write_spec(cell, 1, seed, seconds, rate,
                                     os.path.join(root, "x.npz"))
    with open(path) as f:
        return json.load(f)


def test_generator_work_is_the_same_across_seeds(root):
    plans = [loadgen_sessions.plan(_spec(root, seed))
             for seed in (5, 2_147_483_659 % (2**31 - 1), 77)]
    first = plans[0]
    win = first["phase"] == 2
    assert win.sum() == 120 and (first["phase"] == 0).sum() == 12
    assert (first["kind"][win] == 1).sum() == 12        # 10% misses
    for p in plans[1:]:
        for phase in (0, 1, 2):
            a, b = first["phase"] == phase, p["phase"] == phase
            assert sorted(first["computed"][a]) == sorted(p["computed"][b])
            assert sorted(first["kind"][a]) == sorted(p["kind"][b])
        # the order, the sessions picked and the item ids are the seed's
        assert list(first["computed"][win]) != list(p["computed"][p["phase"] == 2])
        assert not np.array_equal(first["sessions"][0][:8], p["sessions"][0][:8])
    # the same seed gives the same run
    again = loadgen_sessions.plan(_spec(root, 5))
    for k in ("sid", "length", "kind", "reused", "computed"):
        np.testing.assert_array_equal(first[k], again[k])
    # a turn sends the whole list and has grown by 1..16 since it was asked
    turns = win & (first["kind"] == 0)
    assert (first["computed"][turns] >= 1).all()
    assert (first["computed"][turns] <= 16).all()
    assert (first["reused"][turns] + first["computed"][turns]
            == first["length"][turns]).all()
    assert (first["reused"][first["kind"] == 1] == 0).all()
    lengths = first["length"][first["phase"] == 0]
    assert lengths.min() >= 8 and lengths.max() <= 160


def _window(first_ms, second_ms, n=200, late=0):
    lat = np.concatenate([np.full(n, first_ms), np.full(n, second_ms)])
    lat[:late] = 600.0
    return {"lat": lat, "miss": np.zeros(2 * n, bool),
            "first": np.arange(2 * n) < n, "failed": 0}


@pytest.mark.parametrize("windows, limit_ms, sustained", [
    # a stationary queue: the halves differ either way by chance
    ([_window(20.0, 21.0), _window(22.0, 20.5)], 500.0, True),
    # a growing queue: the second half is slower in every window
    ([_window(20.0, 21.0), _window(22.0, 40.0)], 500.0, False),
    # steady, and over the limit for more than one request in a hundred
    ([_window(20.0, 19.0), _window(600.0, 19.0)], 500.0, False),
    # 1.5% of ONE window over the limit, 0.75% of the two together: a run is
    # one window, so the rate is not one a run sustains
    ([_window(20.0, 19.0), _window(20.0, 19.0, late=6)], 500.0, False),
    ([_window(20.0, 19.0, late=3), _window(20.0, 19.0, late=3)], 500.0, True),
])
def test_sweep_verdict_is_the_knee_method_over_all_windows_of_a_rate(
        windows, limit_ms, sustained):
    got = sweep_sessions.verdict(windows, limit_ms)
    assert got["sustained"] is sustained, got
    assert got["n"] == 800


# -- the runner at a small size --------------------------------------------------------

def test_sound_run_is_correct_and_reports_its_layers(root):
    line = bench_tiny.run_cell(root, bench_tiny_seq.CELL, seed=2_147_483_659,
                               seconds=3.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 75
    got = line["metrics"]
    for name in ("seq_match_ms", "seq_extend_ms", "seq_tokens_per_dispatch",
                 "seq_cache_reuse_pct", "moe_expert_load_max_over_mean",
                 "batcher_queue_wait_ms", "deploy_restore_s",
                 "deploy_warmup_s", "serve_p99_ms"):
        assert name in got, name
    # no device plane on the CPU: the rooflines find nothing and say nothing
    assert "moe_experts_roofline" not in got
    assert "mla_attention_roofline" not in got
    assert 70.0 < got["seq_cache_reuse_pct"]["value"] < 95.0
    assert got["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert line["device"]["platform"] == "cpu"  # a test, never a result


def _answers_fail(root, seed=7) -> bool:
    line = bench_tiny.run_cell(root, bench_tiny_seq.CELL, seed=seed,
                               seconds=3.0)
    assert line["failed"] == 0   # answered, and wrong
    return line["correct"] is False


def test_a_stale_prefix_comes_out_not_correct(root, monkeypatch):
    """Every session is looked up under one key and the table claims the
    whole incoming prefix is what it holds: answers come from another
    session's cached rows."""
    from incubator_predictionio_tpu.serving.latent_cache import LatentServing

    real = LatentServing._match

    def stale(self, row, key, tokens, busy, release):
        held = self._sessions.get("one")
        if held is not None:
            held.tokens = tokens[:len(held.tokens)]
        return real(self, row, "one", tokens, busy, release)

    monkeypatch.setattr(LatentServing, "_match", stale)
    assert _answers_fail(root)


def test_a_dropped_expert_contribution_comes_out_not_correct(
        root, monkeypatch):
    from incubator_predictionio_tpu.models import latent_moe

    real = latent_moe.moe_experts

    def dropped(x, idx, w, token_valid, lw, cfg):
        return real(x, idx, w.at[:, 0].set(0.0), token_valid, lw, cfg)

    monkeypatch.setattr(latent_moe, "moe_experts", dropped)
    assert _answers_fail(root)


def test_control_falls_outside_the_limits_and_the_program_inside(root):
    cell = harness.resolve_cell(bench_tiny_seq.CELL, root)
    devices = jax.devices()[:1]
    saved = dict(os.environ)
    try:
        low = control_sessions.numbers(cell, 9, devices, lower=True)
        sound = control_sessions.numbers(cell, 9, devices, lower=False)
    finally:
        os.environ.clear()
        os.environ.update(saved)
    # by the number the window's router flips cannot reach, as by the others
    assert "score_gap_p50" in control.fails(cell, low), low
    assert not control.fails(cell, sound), sound
    assert set(cell.traffic["limits"]) - {"failed_share_max"} == {
        "score_gap_max", "score_gap_p50", "regret_max", "recall_at_k_min"}


def test_control_asks_turns_through_the_cache(root):
    """Two thirds of the control's sessions are grown through the latent
    cache as a window's sampled turns are: the lowered program's cache rows
    and absorbed attention are under the control, not cold sessions alone."""
    from incubator_predictionio_tpu.obs.metrics import REGISTRY

    def reused():
        return REGISTRY.get("pio_seq_tokens_reused_total").value

    cell = harness.resolve_cell(bench_tiny_seq.CELL, root)
    before = reused()
    saved = dict(os.environ)
    try:
        control_sessions.numbers(cell, 11, jax.devices()[:1], lower=False)
    finally:
        os.environ.clear()
        os.environ.update(saved)
    assert reused() - before > 0
