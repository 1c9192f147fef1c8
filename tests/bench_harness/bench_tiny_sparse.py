"""The lifelong-session cell in miniature for CPU tests: the real runner,
generator, readers and cost functions; the configuration cut to a size a
test run can hold (4 query / 2 key-value heads of 16, indexer 2 x 16 with
top-8, 8 experts top-2) and traffic of a few seconds over a dozen sessions
of 24-80 items."""

from __future__ import annotations

import os

from bench_tiny import ROOT, _dump, _load

CELL = "tiny-sparse.serve-lifelong"
REAL = "seq-keye-vl2-30b-a3b.serve-lifelong"
LIMITS = {"score_gap_max": 0.08, "score_gap_p50": 0.04, "regret_max": 0.08,
          "recall_at_k_min": 0.8, "failed_share_max": 0.001}


def make_root(tmp: str) -> str:
    """``tmp`` becomes a checkout in miniature holding the one cell."""
    real = os.path.join(ROOT, "benchmarks")
    bdir = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bdir, sub))
    peaks = _load(os.path.join(real, "peaks.json"))
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    _dump(peaks, os.path.join(bdir, "peaks.json"))

    cfg = _load(os.path.join(real, "configs", "seq-keye-vl2-30b-a3b.json"))
    cfg.update(
        name="tiny-sparse", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts=8,
        num_local_experts=8, experts_held=8, num_experts_per_tok=2,
        moe_intermediate_size=32, num_hidden_layers=2, vocab_size=512,
        sa_config={"indexer_head_dim": 16, "indexer_num_heads": 2,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                   "q_chunk_size": 8, "topk": 8},
        serve={"max_len": 96, "cache_page": 8, "cache_tokens": 16 * 96,
               "weight_dtype": "float32"})
    _dump(cfg, os.path.join(bdir, "configs", "tiny-sparse.json"))

    traffic = _load(os.path.join(real, "traffic", "serve-lifelong.json"))
    traffic.update(
        pool=12, length_median=40, length_min=24, length_max=80,
        retire_at=96, miss_share=0.1, connections=8, max_batch=4,
        warmup_seconds=1.0, check_sample=8, check_min_turns=2,
        check_min_extended=1, check_min_misses=1, trace_seconds=2.0)
    _dump(traffic, os.path.join(bdir, "traffic", "serve-lifelong.json"))
    _dump({"knee_qps": 40, "limit_ms": 2000, "rate_qps": 25,
           "limits": LIMITS},
          os.path.join(bdir, "cells", CELL + ".json"))

    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"] = [
        {"name": "tiny-sparse", "source": cfg["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-sparse.json", "why": "test"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny-sparse", "traffic": "serve-lifelong",
         "chips": 1, "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" not in m:
                kept.append(m)
            elif REAL in m["workloads"]:
                kept.append({**m, "workloads": [CELL]})
        bench[group] = kept
    _dump(bench, os.path.join(tmp, "BENCHMARK.json"))
    return tmp
