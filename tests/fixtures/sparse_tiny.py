"""The sparse-index block (``attention_kind="gqa_sparse"``) at a size the
CPU tests hold, and its plain reference's answers."""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.reference import gqa_sparse_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models import sparse_gqa
from incubator_predictionio_tpu.models.transformer import TransformerConfig
from incubator_predictionio_tpu.serving.latent_cache import TOP_K


def config(**over) -> TransformerConfig:
    """d 64, 4 query / 2 key-value heads of 16, indexer 2 x 16 with top-8,
    8 experts top-2 of width 32; pages and key tiles of 8, so pieces of 32
    and context buckets 16 / 32 / 64 / 96."""
    base = dict(
        vocab_size=512, max_len=96, d_model=64, n_heads=4, n_layers=2,
        attention_kind="gqa_sparse", n_kv_heads=2, head_dim=16,
        rope_theta=1e7, index_n_heads=2, index_head_dim=16, index_topk=8,
        index_kv_tile=8,
        router_scoring="softmax", n_routed_experts=8, experts_per_token=2,
        moe_intermediate_size=32, tie_head=False, cache_page=8,
        cache_tokens=6 * 96)
    base.update(over)
    return TransformerConfig(**base)


def seeded_params(cfg: TransformerConfig, seed: int = 0) -> dict:
    """Random weights; embeddings and head large enough that logits and the
    index scores are of unit scale."""
    params = lm.init_params(jax.random.key(seed), cfg)
    params["item_emb"] = params["item_emb"] * 50.0
    params["head"] = params["head"] * 6.0
    return params


_REFERENCE: dict = {}


def reference_logits(params, cfg, tokens) -> np.ndarray:
    """The reference's logits after the last of ``tokens`` (one jitted full
    forward over the session padded to ``max_len``: the block is causal)."""
    key = (id(params), cfg)
    if key not in _REFERENCE:
        pub = sparse_gqa.published(cfg)
        _REFERENCE[key] = jax.jit(lambda p, t: ref.forward(p, t, pub))
    padded = np.ones(cfg.max_len, np.int32)
    padded[:len(tokens)] = tokens
    return np.array(_REFERENCE[key](params, padded)[len(tokens) - 1])


def masked_reference(params, cfg, tokens, k=TOP_K):
    logits = reference_logits(params, cfg, tokens)
    logits[0] = -np.inf
    logits[np.asarray(tokens)] = -np.inf
    top = np.argsort(-logits, kind="stable")[:k]
    return logits[top], top
