"""BENCHMARK.json and the result line against the benchmark's contract:
names, units, lengths, which cell reports what, and name resolution of every
cell, reader and runner. CPU only; nothing here asks for a chip."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import harness

from bench_tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(len(w) <= 200 for w in BENCH["command"])
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert entry["file"].startswith("benchmarks/configs/")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|rank|hidden|width)$", key)
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    assert "\n" not in entry["why"] and "\t" not in entry["why"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric in BENCH["end_to_end"]
    keys = ({"name", "unit", "better", "bound", "source"} if e2e else
            {"name", "unit", "better", "source", "layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # reported only in cells that report the metric it moves
        for cell in metric.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS)
        assert callable(harness.load_reader(metric["name"]))
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_names_are_unique_and_setup_is_everywhere():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files_runner_and_known_keys(name):
    cell = harness.resolve_cell(name)
    runner = harness.load_runner(cell.kind)
    harness.check_keys("traffic", cell.traffic, runner.TRAFFIC_KEYS)
    harness.check_keys("config", cell.config, runner.CONFIG_KEYS)
    assert "limits" in cell.traffic
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and len(cell.per_layer) >= 1


def test_the_exact_configuration_is_the_two_stage_one_but_for_its_knob():
    """The pair of serve cells shows one serving path against the other only
    while everything else in the two hand-kept files is equal."""
    def load(name):
        with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
            return json.load(f)

    base, exact = load("rec-amzn-elec-r128.json"), load("rec-amzn-elec-r128-exact.json")
    differ = {"name", "deployment", "env", "precision", "expect"}
    assert set(base) == set(exact)
    for key in set(base) - differ:
        assert base[key] == exact[key], key
    assert base["env"] == {"PIO_RETRIEVAL_MODE": "auto"}
    assert exact["env"] == {"PIO_RETRIEVAL_MODE": "exact"}
    assert base["expect"]["serve_path_prefix"] == exact["expect"]["serve_path_prefix"]


def test_the_command_takes_the_contract_arguments_and_no_override():
    from benchmarks import run

    with pytest.raises(SystemExit):
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0", "--set", "rate_qps=1"])


def test_unknown_names_and_keys_are_errors():
    with pytest.raises(harness.HarnessError, match="unknown workload"):
        harness.resolve_cell("no-such.cell")
    with pytest.raises(harness.HarnessError, match="unknown key"):
        harness.check_keys("traffic", {"kind": "x", "burst": 3}, {"kind"})
    with pytest.raises(harness.HarnessError, match="no runner"):
        harness.load_runner("no_such_kind")
    with pytest.raises(harness.HarnessError, match="no reader"):
        harness.load_reader("no_such_metric")
    with pytest.raises(harness.HarnessError, match="peaks.json"):
        harness.load_peaks("TPU v9 imaginary")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_chip_is_an_error_never_a_cpu_result(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # restored after the test
    with pytest.raises(harness.HarnessError, match="no accelerator"):
        harness.claim_chip(1)


class _FakeChip:
    """A device whose allocator reads what the test sets."""

    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self):
        self.stats = {"bytes_in_use": 0, "bytes_reserved": 0,
                      "peak_bytes_in_use": 0, "peak_bytes_reserved": 0}

    def memory_stats(self):
        return dict(self.stats)


def test_memory_peak_is_of_one_instant_not_a_sum_of_two_peaks():
    chip = _FakeChip()
    watch = harness.MemoryWatch([chip], period_s=3600.0)
    # set-up: a restore's transient of live buffers, nothing reserved yet
    chip.stats.update(bytes_in_use=58, peak_bytes_in_use=58)
    watch.sample()
    # the window: fewer live buffers, a pool reserved for temporaries
    chip.stats.update(bytes_in_use=37, bytes_reserved=20,
                      peak_bytes_reserved=20)
    watch.window(True)
    chip.stats.update(bytes_in_use=36)
    watch.window(False)
    chip.stats.update(bytes_in_use=1, bytes_reserved=64,
                      peak_bytes_reserved=64)  # after the window: not its
    watch.stop()
    report = harness.device_report([chip], watch)
    assert report["memory_window_bytes"] == 57
    assert report["memory_window_live_bytes"] == 37
    assert report["memory_peak_bytes"] == 65  # 1 + 64 at one instant
    assert report["memory_peak_bytes"] < 58 + 64  # never the two peaks added
    assert {"platform", "kind", "count"} <= set(report)


def test_result_line_carries_exactly_the_contract_keys():
    cell = harness.resolve_cell(CELLS[0])
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 5}
    e2e = {m["name"]: 1.5 for m in cell.end_to_end}
    plain = json.loads(harness.result_line(
        cell, False, True, 10, 0, e2e, {}, device))
    assert set(plain) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(plain["metrics"]) == set(e2e)
    assert set(plain["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    layer = {"x": {"value": 1.0, "unit": "ms"}}
    traced = json.loads(harness.result_line(
        cell, True, True, 10, 0, e2e, layer,
        dict(device, busy_s=1.0, window_s=2.0),
        {"device_ops": [["a", 1.0]], "idle_gaps": []}))
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert traced["metrics"] == layer
    with pytest.raises(harness.HarnessError, match="reported no"):
        harness.result_line(cell, False, True, 1, 0, {}, {}, device)


def test_command_fails_without_a_result_where_only_the_benchmark_is(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("_work", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and done.stdout.strip() == ""
