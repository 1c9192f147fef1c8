"""The feed cell in miniature for CPU tests: the real runner, generator,
readers and cost functions; the configuration cut to a size a test run can
hold (four published layers conv, conv, full_attention, conv, the first two
with dense feed-forward parts of 96: the letters CD CD AE CE; 3 taps; 4 query
/ 2 key-value heads of 16; 8 gated experts top-2 of width 32, all held; a
tied head over 512 rows) and traffic of a few seconds over a dozen sessions
of 4-80 items with 16 slots for their carries."""

from __future__ import annotations

import os

from bench_tiny import ROOT, _dump, _load

CELL = "tiny-conv.serve-feed"
REAL = "seq-lfm2-8b-a1b-ep2.serve-feed"
LIMITS = {"score_gap_max": 0.02, "score_gap_p50": 0.005, "regret_max": 0.02,
          "recall_at_k_min": 0.9, "failed_share_max": 0.001,
          "carry_gap": 1e-4}


def make_root(tmp: str, **serve) -> str:
    """``tmp`` becomes a checkout in miniature holding the one cell."""
    real = os.path.join(ROOT, "benchmarks")
    bdir = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bdir, sub))
    peaks = _load(os.path.join(real, "peaks.json"))
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    _dump(peaks, os.path.join(bdir, "peaks.json"))

    cfg = _load(os.path.join(real, "configs", "seq-lfm2-8b-a1b-ep2.json"))
    cfg.update(
        name="tiny-conv", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=96,
        layer_types=["conv", "conv", "full_attention", "conv"],
        num_hidden_layers=4, num_experts=8, experts_held=8,
        num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=512,
        serve={"max_len": 96, "cache_page": 8, "cache_tokens": 16 * 96,
               "state_slots": 16, "weight_dtype": "float32", **serve})
    cfg["seeded"] = dict(cfg["seeded"], embedding_sd=64 ** -0.5)
    _dump(cfg, os.path.join(bdir, "configs", "tiny-conv.json"))

    traffic = _load(os.path.join(real, "traffic", "serve-feed.json"))
    traffic.update(
        pool=12, length_median=24, length_min=4, length_max=80,
        retire_at=96, connections=8, prefill_connections=2, max_batch=4,
        warmup_seconds=1.0, check_sample=8, check_min_turns=2,
        check_min_extended=1, check_min_misses=2, check_states=4,
        check_min_states=1, trace_seconds=2.0)
    _dump(traffic, os.path.join(bdir, "traffic", "serve-feed.json"))
    _dump({"knee_qps": 40, "limit_ms": 2000, "rate_qps": 25,
           "limits": LIMITS},
          os.path.join(bdir, "cells", CELL + ".json"))

    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"] = [
        {"name": "tiny-conv", "source": cfg["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-conv.json", "why": "test"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny-conv", "traffic": "serve-feed",
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
