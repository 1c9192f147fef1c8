"""The gated-short-convolution / rotary grouped-query / routed-expert pattern
(``attention_kind="gqa"``, a ``layer_pattern`` over ``C``, ``A``, ``D``,
``E``) at a size the CPU tests hold, and its plain reference's answers."""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.reference import conv_gqa_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models import short_conv
from incubator_predictionio_tpu.models.transformer import TransformerConfig
from incubator_predictionio_tpu.serving.latent_cache import TOP_K


def config(**over) -> TransformerConfig:
    """d 64; four published layers as the eight letters CD CD AE CE (two
    dense feed-forward parts of 96, then experts); a convolution over 3 taps;
    4 query / 2 key-value heads of 16 with per-head norms and rotary pairs at
    1e6; 8 sigmoid-routed gated experts top-2 of width 32, no shared one,
    scaling 1; a tied head; pages of 8, max_len 96: context buckets 24 / 48 /
    96; six slots of 2 x 64 carried values a convolution layer."""
    base = dict(
        vocab_size=512, max_len=96, d_model=64, n_heads=4, n_layers=8,
        attention_kind="gqa", layer_pattern="CDCDAECE", n_kv_heads=2,
        head_dim=16, qk_norm=True, attention_rope=True, rope_theta=1e6,
        conv_kernel=3, intermediate_size=96, rms_norm_eps=1e-5,
        n_routed_experts=8, experts_per_token=2, moe_intermediate_size=32,
        n_shared_experts=0, expert_activation="gated_silu",
        routed_scaling_factor=1.0, tie_head=True,
        cache_page=8, cache_tokens=6 * 96, state_slots=6)
    base.update(over)
    return TransformerConfig(**base)


def seeded_params(cfg: TransformerConfig, seed: int = 0) -> dict:
    """Random weights; embeddings large enough that a token's identity
    stays visible and logits are of unit scale through the tied head, per-head
    gains and a router bias that move results."""
    params = lm.init_params(jax.random.key(seed), cfg)
    params["item_emb"] = params["item_emb"] * 12.0
    keys = iter(jax.random.split(jax.random.key(seed + 1), 3 * cfg.n_layers))
    for lw in params["layers"]:
        if "b_r" in lw:
            lw["b_r"] = 0.1 * jax.random.normal(next(keys), lw["b_r"].shape)
        for name in ("norm_qh", "norm_kh"):
            if name in lw:
                lw[name] = 1.0 + 0.2 * jax.random.normal(
                    next(keys), lw[name].shape)
    return params


_REFERENCE: dict = {}


def reference_logits(params, cfg, tokens) -> np.ndarray:
    """The reference's logits after the last of ``tokens`` (one jitted full
    forward over the session padded to ``max_len``: every layer is causal)."""
    key = (id(params), cfg)
    if key not in _REFERENCE:
        pub = short_conv.published(cfg)
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
