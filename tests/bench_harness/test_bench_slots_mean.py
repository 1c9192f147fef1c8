"""``batcher_slots_mean`` (ISSUE 29): the mean number of dispatch slots the
window's batches ran under, from two counters of the program; a program that
lacks the counter (the parent commit) gives the reader nothing to read."""

import pytest

from benchmarks import harness

BEFORE = {"pio_serving_batches": 100.0, "pio_serving_requests": 250.0,
          "pio_serving_batch_slots_total": 200.0}


@pytest.mark.parametrize("slots_after, want", [
    (1000.0, 2.0),   # the limiter never shrank the bound
    (600.0, 1.0),    # one slot throughout
    (900.0, 1.75),   # a quarter of the batches assembled under one slot
])
def test_slots_mean_is_delta_slots_over_delta_batches(slots_after, want):
    read = harness.load_reader("batcher_slots_mean")
    after = {"pio_serving_batches": 500.0, "pio_serving_requests": 1500.0,
             "pio_serving_batch_slots_total": slots_after}
    assert read({"metrics_before": BEFORE, "metrics_after": after}) == want


def test_slots_mean_reads_nothing_from_a_program_without_the_counter():
    read = harness.load_reader("batcher_slots_mean")
    assert read({}) is None
    bare = {"metrics_before": {"pio_serving_batches": 0.0},
            "metrics_after": {"pio_serving_batches": 9.0}}
    assert read(bare) is None
    # no batch in the window: no mean
    assert read({"metrics_before": BEFORE, "metrics_after": BEFORE}) is None


def test_benchmark_json_reports_slots_mean_in_the_three_serve_cells():
    bench = harness.load_benchmark()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "batcher_slots_mean"]
    serve = [w["name"] for w in bench["workloads"]
             if "serve_p50_ms" in [m["name"] for m in harness.resolve_cell(
                 w["name"]).end_to_end]]
    assert sorted(entry["workloads"]) == sorted(serve) and len(serve) == 3
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "serve_p50_ms"
