"""``seq_context_fill_pct`` (ISSUE 31): what the window's short-block
dispatches held of the cache rows they read, from two host counters of
``LatentServing._dispatch``; a program that lacks them (the parent commit)
gives the reader nothing to read. CPU only."""

import jax
import pytest

from benchmarks import harness
from benchmarks.runners import common
from incubator_predictionio_tpu.models import latent_moe
from incubator_predictionio_tpu.models.transformer import TransformerConfig
from incubator_predictionio_tpu.obs.metrics import REGISTRY
from incubator_predictionio_tpu.serving.latent_cache import LatentServing

HELD, READ = ("pio_seq_context_rows_held_total",
              "pio_seq_context_rows_read_total")


@pytest.mark.parametrize("held, rows, want", [
    (1024.0, 4 * 4096.0, 6.25),     # a lone turn in the parent's one bucket
    (1024.0, 1024.0, 100.0),        # the same turn filling 1x16@1024
    (1300.0 + 700.0, 4 * 2048.0, 24.4140625),   # two sessions in 4x16@2048
])
def test_fill_is_delta_held_over_delta_read(held, rows, want):
    read = harness.load_reader("seq_context_fill_pct")
    before = {HELD: 5000.0, READ: 81920.0, "pio_seq_dispatches_total": 7.0}
    after = {HELD: 5000.0 + held, READ: 81920.0 + rows,
             "pio_seq_dispatches_total": 8.0}
    assert read({"metrics_before": before, "metrics_after": after}) == want


def test_fill_reads_nothing_from_a_program_without_the_counters():
    read = harness.load_reader("seq_context_fill_pct")
    assert read({}) is None
    parent = 'pio_seq_dispatches_total{bucket="4x16@4096"}'
    assert read({"metrics_before": {parent: 0.0},
                 "metrics_after": {parent: 9.0}}) is None
    # no short dispatch in the window: no share
    same = {HELD: 10.0, READ: 64.0}
    assert read({"metrics_before": same, "metrics_after": same}) is None


def test_the_program_counts_short_dispatches_only():
    """Through ``/metrics`` text as the runner scrapes it: a long block moves
    neither counter; a short dispatch adds its sessions' tokens and its
    bucket's batch x context."""
    rope = harness.resolve_cell(
        "seq-mistral-small4-ep4.serve-sessions").config["rope_parameters"]
    cfg = TransformerConfig(
        vocab_size=64, max_len=32, d_model=32, n_heads=2, n_layers=1,
        attention_kind="mla", q_lora_rank=16, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        rope_parameters=tuple(sorted(rope.items())), n_routed_experts=4,
        experts_per_token=2, moe_intermediate_size=16, n_shared_experts=1,
        tie_head=False, cache_page=8, cache_tokens=8 * 32)
    serving = LatentServing(
        latent_moe.init_params(jax.random.key(0), cfg), cfg)
    read = harness.load_reader("seq_context_fill_pct")

    def scrape():
        return common.parse_metrics(REGISTRY.expose())

    def moved(requests):
        before = scrape()
        serving.extend(requests)
        after = scrape()
        return (after.get(HELD, 0.0) - before.get(HELD, 0.0),
                after.get(READ, 0.0) - before.get(READ, 0.0),
                read({"metrics_before": before, "metrics_after": after}))

    try:
        serving.warmup(4)
        assert serving.info()["buckets"] == [
            "1x16@16:absorbed", "1x16@32:absorbed", "4x16@16:absorbed",
            "4x16@32:absorbed", "1x32@32:up"]
        tokens = list(range(1, 33))
        assert moved([("a", tokens[:20])]) == (0.0, 0.0, None)   # 1x32@32
        assert moved([("b", tokens[:10])]) == (10.0, 16.0, 62.5)  # 1x16@16
        assert moved([("a", tokens[:24])]) == (24.0, 32.0, 75.0)  # 1x16@32
        # the longer member sets the context: 4x16@32
        assert moved([("a", tokens[:30]), ("b", tokens[:12])]) == (
            42.0, 128.0, 100.0 * 42 / 128)
    finally:
        serving.close()


def test_benchmark_json_reports_the_fill_in_the_two_sequence_cells():
    bench = harness.load_benchmark()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "seq_context_fill_pct"]
    assert bench["per_layer"][-1] is entry       # appended, nothing moved
    assert entry == {
        "name": "seq_context_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "sequence serving",
        "moves": "serve_p50_ms", "workloads": [
            "seq-mistral-small4-ep4.serve-sessions",
            "seq-keye-vl2-30b-a3b.serve-lifelong"]}
    for name in entry["workloads"]:
        cell = harness.resolve_cell(name)
        assert "seq_context_fill_pct" in [m["name"] for m in cell.per_layer]
        assert "serve_p50_ms" in [m["name"] for m in cell.end_to_end]
