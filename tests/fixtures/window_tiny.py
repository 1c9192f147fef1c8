"""The window / full grouped-query attention pattern with softmax-routed
experts (``attention_kind="gqa"``, a ``layer_pattern`` over ``W``, ``A``,
``E``) at a size the CPU tests hold, and its plain reference's answers."""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.reference import window_gqa_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models.transformer import TransformerConfig
from incubator_predictionio_tpu.serving.latent_cache import TOP_K

YARN = {"rope_type": "yarn", "rope_theta": 1e4, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.1386294361119891}


def config(**over) -> TransformerConfig:
    """d 64; four published layers as the eight letters WE WE WE AE (three
    window layers of 8 keys beside one full layer, as the published stack's
    three to one); 4 query / 2 key-value heads of 16, rotary pairs at 1e4,
    the full layer's by a yarn rule whose original context is 32 positions
    (factor 4: sessions of 96 pass it three times over); 8 softmax-routed
    gated experts top-2 of width 32, no shared one; an untied head; pages of
    8, max_len 96, pieces of 4 x 8 = 32 tokens: context buckets 32 / 64 / 96;
    six slots, each a ring of 8 rows a window layer."""
    base = dict(
        vocab_size=512, max_len=96, d_model=64, n_heads=4, n_layers=8,
        attention_kind="gqa", layer_pattern="WEWEWEAE", n_kv_heads=2,
        head_dim=16, attention_rope=True, rope_theta=1e4, sliding_window=8,
        rope_parameters=tuple(sorted(YARN.items())), index_kv_tile=8,
        rms_norm_eps=1e-6, n_routed_experts=8, experts_per_token=2,
        moe_intermediate_size=32, n_shared_experts=0,
        router_scoring="softmax", expert_activation="gated_silu",
        routed_scaling_factor=1.0, tie_head=False,
        cache_page=8, cache_tokens=6 * 96, state_slots=6)
    base.update(over)
    return TransformerConfig(**base)


def published(cfg) -> dict:
    """``TransformerConfig`` of a ``W`` / ``A`` / ``E`` pattern → the
    reference's dict, under the published config's key names
    (benchmarks/reference/window_gqa_moe_ref.py): a published layer is two
    letters, its attention (``"W"`` sliding, ``"A"`` full) and its experts."""
    plain = {"rope_type": "default", "rope_theta": cfg.rope_theta}
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "layer_types": [{"W": "sliding_attention", "A": "full_attention"}[k]
                        for k in cfg.layer_pattern[0::2]],
        "sliding_window": cfg.sliding_window,
        "rope_parameters": {
            "full_attention": dict(cfg.rope_parameters) or plain,
            "sliding_attention": plain},
        "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts": cfg.n_routed_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "norm_topk_prob": True,
        "experts_held": cfg.experts_held or cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
    }


def seeded_params(cfg: TransformerConfig, seed: int = 0) -> dict:
    """Random weights; embeddings and head large enough that a token's
    identity stays visible and logits are of unit scale."""
    params = lm.init_params(jax.random.key(seed), cfg)
    params["item_emb"] = params["item_emb"] * 12.0
    params["head"] = params["head"] * 12.0
    return params


_REFERENCE: dict = {}


def reference_logits(params, cfg, tokens) -> np.ndarray:
    """The reference's logits after the last of ``tokens`` (one jitted full
    forward over the session padded to ``max_len``: every layer is causal)."""
    key = (id(params), cfg)
    if key not in _REFERENCE:
        pub = published(cfg)
        fwd = jax.jit(lambda p, t: ref.forward(p, t, pub))
        _REFERENCE[key] = lambda t: fwd(params, t)
    padded = np.ones(cfg.max_len, np.int32)
    padded[:len(tokens)] = tokens
    return np.array(_REFERENCE[key](padded)[len(tokens) - 1])


def masked_reference(params, cfg, tokens, k=TOP_K):
    logits = reference_logits(params, cfg, tokens)
    logits[0] = -np.inf
    logits[np.asarray(tokens)] = -np.inf
    top = np.argsort(-logits, kind="stable")[:k]
    return logits[top], top
