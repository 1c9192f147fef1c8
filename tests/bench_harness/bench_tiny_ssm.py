"""The visitor-session cell in miniature for CPU tests: the real runner,
generator, readers and cost functions; the configuration cut to a size a
test run can hold (the pattern MEM*EME; 8 state-space heads of 8 with a
state of 16 in 2 groups; 4 query / 2 key-value heads of 16; 8 relu2 experts
top-2 of width 160, stored 256 wide, a shared one of 48) and traffic of a
few seconds over a dozen sessions of 4-80 items with 6 state slots for
them."""

from __future__ import annotations

import os

from bench_tiny import ROOT, _dump, _load

CELL = "tiny-ssm.serve-visits"
REAL = "seq-nemotron3-nano-ep2.serve-visits"
LIMITS = {"score_gap_max": 0.08, "score_gap_p50": 0.02, "regret_max": 0.08,
          "recall_at_k_min": 0.8, "failed_share_max": 0.001,
          "state_gap": 2e-4}


def make_root(tmp: str, **serve) -> str:
    """``tmp`` becomes a checkout in miniature holding the one cell."""
    real = os.path.join(ROOT, "benchmarks")
    bdir = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bdir, sub))
    peaks = _load(os.path.join(real, "peaks.json"))
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    _dump(peaks, os.path.join(bdir, "peaks.json"))

    cfg = _load(os.path.join(real, "configs", "seq-nemotron3-nano-ep2.json"))
    cfg.update(
        name="tiny-ssm", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, hybrid_override_pattern="MEM*EME",
        num_hidden_layers=7, mamba_num_heads=8, mamba_head_dim=8,
        ssm_state_size=16, n_groups=2, chunk_size=8, n_routed_experts=8,
        experts_held=8, num_experts_per_tok=2, moe_intermediate_size=160,
        moe_shared_expert_intermediate_size=48, vocab_size=512,
        serve={"max_len": 96, "cache_page": 8, "cache_tokens": 16 * 96,
               "state_slots": 6, "weight_dtype": "float32",
               **serve})
    _dump(cfg, os.path.join(bdir, "configs", "tiny-ssm.json"))

    traffic = _load(os.path.join(real, "traffic", "serve-visits.json"))
    traffic.update(
        pool=12, length_median=24, length_min=4, length_max=80,
        retire_at=96, connections=8, prefill_connections=2, max_batch=4,
        warmup_seconds=1.0, check_sample=8, check_min_turns=2,
        check_min_extended=1, check_min_misses=2, check_states=4,
        check_min_states=1, trace_seconds=2.0)
    _dump(traffic, os.path.join(bdir, "traffic", "serve-visits.json"))
    _dump({"knee_qps": 40, "limit_ms": 2000, "rate_qps": 25,
           "limits": LIMITS},
          os.path.join(bdir, "cells", CELL + ".json"))

    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"] = [
        {"name": "tiny-ssm", "source": cfg["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-ssm.json", "why": "test"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny-ssm", "traffic": "serve-visits",
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
