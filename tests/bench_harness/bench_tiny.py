"""A small copy of the benchmark tree for CPU tests: the real traffic files
and readers, configurations cut to sizes a test run can hold."""

from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SERVE_LIMITS = {"score_gap_max": 0.012, "regret_max": 0.008,
                "recall_at_k_min": 0.985, "failed_share_max": 0.001}
TRAIN_LIMITS = {"loss_gap": 1e-3, "dnorm_gap": 5e-5, "row_rms_gap": 2e-3,
                "untouched_max": 0.0}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp: str) -> str:
    """``tmp`` becomes a checkout in miniature: BENCHMARK.json with three
    tiny cells, their configs / cells, the real traffic files and peaks."""
    bdir = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bdir, sub))
    real = os.path.join(ROOT, "benchmarks")
    for name in os.listdir(os.path.join(real, "traffic")):
        shutil.copy(os.path.join(real, "traffic", name),
                    os.path.join(bdir, "traffic", name))
    peaks = _load(os.path.join(real, "peaks.json"))
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    _dump(peaks, os.path.join(bdir, "peaks.json"))

    def cfg(src, dst, **over):
        c = _load(os.path.join(real, "configs", src + ".json"))
        c.update(over, name=dst)
        _dump(c, os.path.join(bdir, "configs", dst + ".json"))

    cfg("rec-amzn-elec-r128", "tiny-two", n_users=5000, n_items=20000,
        env={"PIO_RETRIEVAL_MODE": "auto", "PIO_RETRIEVAL_MIN_ITEMS": "10000"},
        expect={"serve_path_prefix": "device-", "retrieval_mode": "two_stage"})
    cfg("rec-amzn-elec-r128-exact", "tiny-exact", n_users=5000, n_items=20000,
        expect={"serve_path_prefix": "device-", "retrieval_mode": "exact"})
    cfg("rec-1Mx100k-r128", "tiny-train", n_users=3000, n_items=500,
        train={"rank": 128, "numIterations": 2, "batchSize": 4096,
               "lambda_": 0.5})
    for real_cell, tiny in (("rec-amzn-elec-r128", "tiny-two"),
                            ("rec-amzn-elec-r128-exact", "tiny-exact")):
        # the numbers the real cell compares, at limits this size can hold
        compared = _load(os.path.join(
            real, "cells", real_cell + ".serve-steady.json"))["limits"]
        _dump({"knee_qps": 200, "limit_ms": 100, "rate_qps": 150,
               "limits": {k: SERVE_LIMITS[k] for k in compared}},
              os.path.join(bdir, "cells", tiny + ".serve-steady.json"))
    _dump({"limits": TRAIN_LIMITS},
          os.path.join(bdir, "cells", "tiny-train.train-verb.json"))

    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    serve = ["tiny-two.serve-steady", "tiny-exact.serve-steady"]
    train = ["tiny-train.train-verb"]
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"benchmarks/configs/{n}.json",
         "reduced": [], "why": "test"}
        for n in ("tiny-two", "tiny-exact", "tiny-train")]
    bench["workloads"] = [
        {"name": w, "config": w.split(".")[0], "traffic": w.split(".")[1],
         "chips": 1, "why": "test"} for w in serve + train]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            real_cells = m["workloads"]
            m["workloads"] = (
                train if any("train-verb" in c for c in real_cells) else
                serve[:1] if m["name"] == "retrieval_host_rerank_ms" else serve)
    _dump(bench, os.path.join(tmp, "BENCHMARK.json"))
    return tmp


def run_cell(root: str, name: str, seed: int, seconds: float,
             trace: bool = False) -> dict:
    """Drives a run as ``benchmarks.run`` does, minus its look for a chip."""
    import time

    import jax

    from benchmarks import harness

    cell = harness.resolve_cell(name, root)
    runner = harness.load_runner(cell.kind)
    saved = dict(os.environ)  # a run sets its own PIO_* environment
    try:
        line = runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                          devices=jax.devices()[:1],
                          process_start=time.time())
    finally:
        os.environ.clear()
        os.environ.update(saved)
    return json.loads(line)
