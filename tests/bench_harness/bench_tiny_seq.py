"""The sequence-serving cell in miniature for CPU tests: the real runner,
generator, readers and cost functions; the configuration cut to a size a
test run can hold (the published ratios, widths of 64) and traffic of a few
seconds over a dozen short sessions."""

from __future__ import annotations

import os

from bench_tiny import ROOT, _dump, _load

CELL = "tiny-seq.serve-sessions"
LIMITS = {"score_gap_max": 0.08, "score_gap_p50": 0.04, "regret_max": 0.08,
          "recall_at_k_min": 0.8,
          "failed_share_max": 0.001}


def make_root(tmp: str) -> str:
    """``tmp`` becomes a checkout in miniature holding the one cell."""
    real = os.path.join(ROOT, "benchmarks")
    bdir = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bdir, sub))
    peaks = _load(os.path.join(real, "peaks.json"))
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    _dump(peaks, os.path.join(bdir, "peaks.json"))

    cfg = _load(os.path.join(real, "configs", "seq-mistral-small4-ep4.json"))
    cfg.update(
        name="tiny-seq", hidden_size=64, num_attention_heads=4,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
        experts_held=4, expert_offset=4, moe_intermediate_size=32,
        num_hidden_layers=2, vocab_size=512,
        serve={"max_len": 256, "cache_page": 16, "cache_tokens": 24 * 256,
               "weight_dtype": "float32"})
    _dump(cfg, os.path.join(bdir, "configs", "tiny-seq.json"))

    traffic = _load(os.path.join(real, "traffic", "serve-sessions.json"))
    traffic.update(
        pool=12, length_median=40, length_min=8, length_max=160,
        retire_at=256, connections=8, max_batch=8, warmup_seconds=1.0,
        check_sample=8, check_min_turns=2, check_min_extended=1,
        check_min_misses=1, trace_seconds=2.0)
    _dump(traffic, os.path.join(bdir, "traffic", "serve-sessions.json"))
    _dump({"knee_qps": 40, "limit_ms": 2000, "rate_qps": 25,
           "limits": LIMITS},
          os.path.join(bdir, "cells", CELL + ".json"))

    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    real_cell = "seq-mistral-small4-ep4.serve-sessions"
    bench["configs"] = [
        {"name": "tiny-seq", "source": cfg["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-seq.json", "why": "test"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny-seq", "traffic": "serve-sessions",
         "chips": 1, "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" not in m:
                kept.append(m)
            elif real_cell in m["workloads"]:
                kept.append({**m, "workloads": [CELL]})
        bench[group] = kept
    _dump(bench, os.path.join(tmp, "BENCHMARK.json"))
    return tmp
