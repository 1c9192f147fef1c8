"""The histories cell in miniature for CPU tests: the real runner,
generator, readers and cost functions; the configuration cut to a size a test
run can hold (four published layers sliding, sliding, sliding, full: the
letters WE WE WE AE; a window of 8 keys; a yarn rule over 32 original
positions; 4 query / 2 key-value heads of 16; 8 softmax-routed experts top-2
of width 32, all held; an untied head over 512 rows) and traffic of a few
seconds over a dozen sessions of 4-80 items (ten windows long, past the
rule's 32 positions), misses in pieces of 32, with 16 slots for their
rings."""

from __future__ import annotations

import os

from bench_tiny import ROOT, _dump, _load

CELL = "tiny-window.serve-histories"
REAL = "seq-mellum2-12b-ep4.serve-histories"
LIMITS = {"score_gap_max": 0.02, "score_gap_p50": 0.005, "regret_max": 0.02,
          "recall_at_k_min": 0.9, "failed_share_max": 0.001}


def make_root(tmp: str, **serve) -> str:
    """``tmp`` becomes a checkout in miniature holding the one cell."""
    real = os.path.join(ROOT, "benchmarks")
    bdir = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bdir, sub))
    peaks = _load(os.path.join(real, "peaks.json"))
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    _dump(peaks, os.path.join(bdir, "peaks.json"))

    cfg = _load(os.path.join(real, "configs", "seq-mellum2-12b-ep4.json"))
    rope = cfg["rope_parameters"]
    cfg.update(
        name="tiny-window", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        num_hidden_layers=4, sliding_window=8,
        rope_parameters={
            "full_attention": {
                **rope["full_attention"], "rope_theta": 1e4, "factor": 4,
                "original_max_position_embeddings": 32,
                "attention_factor": 1.1386294361119891},
            "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}},
        num_experts=8, experts_held=8, num_experts_per_tok=2,
        moe_intermediate_size=32, vocab_size=512,
        serve={"max_len": 96, "cache_page": 8, "cache_tokens": 16 * 96,
               "state_slots": 16, "index_kv_tile": 8,
               "weight_dtype": "float32", **serve})
    _dump(cfg, os.path.join(bdir, "configs", "tiny-window.json"))

    traffic = _load(os.path.join(real, "traffic", "serve-histories.json"))
    traffic.update(
        pool=12, length_median=24, length_min=4, length_max=80,
        retire_at=96, miss_share=0.1, connections=8, prefill_connections=2,
        max_batch=4, warmup_seconds=1.0, check_sample=8, check_min_turns=2,
        check_min_extended=1, check_min_misses=2, check_long_over=32,
        trace_seconds=2.0)
    _dump(traffic, os.path.join(bdir, "traffic", "serve-histories.json"))
    _dump({"knee_qps": 40, "limit_ms": 2000, "rate_qps": 25,
           "limits": LIMITS},
          os.path.join(bdir, "cells", CELL + ".json"))

    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"] = [
        {"name": "tiny-window", "source": cfg["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-window.json", "why": "test"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny-window", "traffic": "serve-histories",
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
