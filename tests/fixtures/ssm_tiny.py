"""The state-space / grouped-query / routed-expert pattern
(``attention_kind="gqa"``, ``layer_pattern``) at a size the CPU tests hold,
and its plain reference's answers."""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.reference import ssm_gqa_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models import state_space
from incubator_predictionio_tpu.models.transformer import TransformerConfig
from incubator_predictionio_tpu.serving.latent_cache import TOP_K


def config(**over) -> TransformerConfig:
    """d 64; the pattern SESEAE; 8 state-space heads of 8 with a state of 16
    in 2 groups, convolution over 4, tiles of 8; 4 query / 2 key-value heads
    of 16; 8 sigmoid-routed relu2 experts top-2 of width 160 (stored 256
    wide: at least one lane tile, so the Pallas grouped matmul on a backend
    that runs kernels, ``latent_moe.expert_form``, and ``ragged_dot`` here)
    and a shared one of 48; pages of 8, max_len 96: context buckets 24 / 48
    / 96."""
    base = dict(
        vocab_size=512, max_len=96, d_model=64, n_heads=4, n_layers=6,
        attention_kind="gqa", layer_pattern="SESEAE", n_kv_heads=2,
        head_dim=16, ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
        conv_kernel=4, ssm_chunk=8, rms_norm_eps=1e-5,
        n_routed_experts=8, experts_per_token=2, moe_intermediate_size=160,
        n_shared_experts=1, shared_intermediate_size=48,
        expert_activation="relu2",
        routed_scaling_factor=2.5, tie_head=False,
        cache_page=8, cache_tokens=6 * 96, state_slots=6)
    base.update(over)
    return TransformerConfig(**base)


def seeded_params(cfg: TransformerConfig, seed: int = 0) -> dict:
    """Random weights; embeddings and head large enough that logits are of
    unit scale, a router bias that moves picks, a convolution bias."""
    params = lm.init_params(jax.random.key(seed), cfg)
    params["item_emb"] = params["item_emb"] * 50.0
    params["head"] = params["head"] * 6.0
    keys = iter(jax.random.split(jax.random.key(seed + 1), 2 * cfg.n_layers))
    for lw in params["layers"]:
        for name in ("b_r", "conv_b"):
            if name in lw:
                lw[name] = 0.1 * jax.random.normal(next(keys), lw[name].shape)
    return params


def published_params(params: dict, cfg) -> dict:
    """The program's tree as the reference takes it: the routed experts'
    matrices without the zeros they are stored with."""
    f = cfg.moe_intermediate_size
    cut = {"we1": lambda v: v[..., :f], "we2": lambda v: v[:, :f]}
    return {**params, "layers": [
        {k: cut.get(k, lambda v: v)(v) for k, v in lw.items()}
        for lw in params["layers"]]}


_REFERENCE: dict = {}


def reference_logits(params, cfg, tokens) -> np.ndarray:
    """The reference's logits after the last of ``tokens`` (one jitted full
    forward over the session padded to ``max_len``: every layer is causal)."""
    key = (id(params), cfg)
    if key not in _REFERENCE:
        pub, plain = state_space.published(cfg), published_params(params, cfg)
        fwd = jax.jit(lambda p, t: ref.forward(p, t, pub))
        _REFERENCE[key] = lambda t: fwd(plain, t)
    padded = np.ones(cfg.max_len, np.int32)
    padded[:len(tokens)] = tokens
    return np.array(_REFERENCE[key](padded)[len(tokens) - 1])


def masked_reference(params, cfg, tokens, k=TOP_K):
    logits = reference_logits(params, cfg, tokens)
    logits[0] = -np.inf
    logits[np.asarray(tokens)] = -np.inf
    top = np.argsort(-logits, kind="stable")[:k]
    return logits[top], top
