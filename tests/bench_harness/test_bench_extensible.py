"""A configuration, a traffic mix, a cell and a per-layer metric are each
added as new files plus BENCHMARK.json entries, with no edit to a file that
is there: a dummy of each is added to a copy of the tree and resolved."""

import json
import os
import shutil
import subprocess
import sys

from bench_tiny import ROOT


def test_adding_files_and_entries_is_enough(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bdir = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bdir,
                    ignore=shutil.ignore_patterns("_work", "__pycache__"))
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}

    (bdir / "configs" / "dummy-cfg.json").write_text(json.dumps(
        {"name": "dummy-cfg", "source": "test", "n_users": 7, "n_items": 9,
         "rank": 128, "reduced": []}))
    (bdir / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "dummy_kind", "why": "test", "per_cell": ["pace"],
         "pace": 1}))
    (bdir / "cells" / "dummy-cfg.dummy-mix.json").write_text(json.dumps(
        {"pace": 5}))
    (bdir / "runners" / "dummy_kind.py").write_text(
        "TRAFFIC_KEYS = {'kind', 'why', 'per_cell', 'pace'}\n"
        "CONFIG_KEYS = {'name', 'source', 'n_users', 'n_items', 'rank', "
        "'reduced'}\n")
    (bdir / "layer_metrics" / "dummy_metric_x.py").write_text(
        "def read(ev):\n    return ev.get('x')\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "dummy-cfg", "source": "test", "reduced": [], "why": "test",
         "file": "benchmarks/configs/dummy-cfg.json"})
    bench["workloads"].append(
        {"name": "dummy-cfg.dummy-mix", "config": "dummy-cfg",
         "traffic": "dummy-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "dummy_metric.x", "unit": "ms", "better": "lower",
         "source": "program_counter", "layer": "dummy", "moves": "setup_s",
         "workloads": ["dummy-cfg.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    script = (
        "from benchmarks import harness\n"
        "c = harness.resolve_cell('dummy-cfg.dummy-mix')\n"
        "r = harness.load_runner(c.kind)\n"
        "harness.check_keys('t', c.traffic, r.TRAFFIC_KEYS)\n"
        "harness.check_keys('c', c.config, r.CONFIG_KEYS)\n"
        "m = harness.read_layer_metrics(c, {'x': 2.5})\n"
        "assert c.traffic['pace'] == 5 and c.config['n_items'] == 9\n"
        "assert m == {'dummy_metric.x': {'value': 2.5, 'unit': 'ms'}}, m\n"
        "assert harness.read_layer_metrics(c, {}) == {}\n"
        "old = harness.resolve_cell(harness.load_benchmark()['workloads'][0]"
        "['name'])\n"
        "assert 'dummy_metric.x' not in [m['name'] for m in old.per_layer]\n"
        "print('resolved')\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=60)
    assert done.returncode == 0 and "resolved" in done.stdout, done.stderr
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there was edited
