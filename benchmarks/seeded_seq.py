"""What a sequence-serving run makes from ``--seed``: the block's weights.

Imports nothing of the program: the engine fills the program's parameter
tree from these functions and the runner's comparison hands the SAME arrays,
made again from the same seed, to the plain reference
(``benchmarks/reference/mla_moe_ref.py``) a layer at a time.

Every array is a function of (seed, layer, name) alone: normal with fan-in
scaling for the matrices (activations keep unit scale through the depth),
gains ``1 + sd n`` for the norms, a small nonzero selection bias for the
router, unit-variance embeddings (a token's identity stays visible in the
residual stream beside what the layers add) and a head at ``d ** -0.5``
(logits of unit scale). ``lower`` rounds the bfloat16 matrices through
float8_e4m3fn: the control's one precision step down.
"""

from __future__ import annotations

import functools

from benchmarks import seeded_data

def shape_config(cfg: dict) -> dict:
    """The reference's ``cfg`` dict from a configuration file: the published
    keys as they stand plus the chip's share."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "moe_intermediate_size", "routed_scaling_factor", "experts_held",
            "expert_offset", "rope_parameters")
    return {k: cfg[k] for k in keys}


def layer_shapes(cfg: dict) -> dict:
    """``{name: (shape, float32?)}`` of one layer, under the program's names."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, e = cfg["moe_intermediate_size"], cfg["experts_held"]
    fs = f * cfg["n_shared_experts"]
    return {
        "norm1": ((d,), True), "norm2": ((d,), True),
        "norm_q": ((qr,), True), "norm_kv": ((kvr,), True),
        "w_dq": ((d, qr), False), "w_uq": ((qr, h * (dn + dr)), False),
        "w_dkv": ((d, kvr + dr), False),
        "w_ukv": ((kvr, h * (dn + dv)), False), "w_o": ((h * dv, d), False),
        "w_r": ((d, cfg["n_routed_experts"]), True),
        "b_r": ((cfg["n_routed_experts"],), True),
        "we1": ((e, d, f), False), "we3": ((e, d, f), False),
        "we2": ((e, f, d), False),
        "ws1": ((d, fs), False), "ws3": ((d, fs), False),
        "ws2": ((fs, d), False),
    }


@functools.lru_cache(maxsize=64)
def _maker(shape: tuple, kind: str, scale: float, lower: bool):
    import jax
    import jax.numpy as jnp

    def make(key):
        n = jax.random.normal(key, shape, jnp.float32)
        if kind == "gain":
            return 1.0 + scale * n
        if kind == "float32":
            return scale * n
        w = (scale * n).astype(jnp.bfloat16)
        # (returned IN float8: inside one program the TPU compiler keeps the
        # excess precision and drops a narrowing it can undo; PERF.md PR 26)
        return w.astype(jnp.float8_e4m3fn) if lower else w

    made = jax.jit(make)
    if kind == "bfloat16" and lower:
        return lambda key: made(key).astype(jnp.bfloat16)
    return made


def _key(seed: int, layer: int, index: int):
    import jax

    key = jax.random.key(seeded_data.fold_seed(seed, 17))
    return jax.random.fold_in(jax.random.fold_in(key, layer + 1), index)


def layer_weights(seed: int, layer: int, cfg: dict, lower: bool = False):
    """One layer's arrays on the default device."""
    sd = cfg["seeded"]
    out = {}
    for index, (name, (shape, f32)) in enumerate(layer_shapes(cfg).items()):
        if name.startswith("norm"):
            kind, scale = "gain", sd["norm_gain_sd"]
        elif name == "b_r":
            kind, scale = "float32", sd["router_bias_sd"]
        elif f32:
            kind, scale = "float32", shape[-2] ** -0.5
        else:
            kind, scale = "bfloat16", shape[-2] ** -0.5
        out[name] = _maker(tuple(shape), kind, float(scale), lower)(
            _key(seed, layer, index))
    return out


def top_weights(seed: int, cfg: dict, lower: bool = False) -> dict:
    """Embedding rows, head rows and the final norm's gain."""
    v, d, sd = cfg["vocab_size"], cfg["hidden_size"], cfg["seeded"]
    return {
        "item_emb": _maker((v, d), "bfloat16", float(sd["embedding_sd"]),
                           lower)(_key(seed, -1, 0)),
        "head": _maker((v, d), "bfloat16", d ** -0.5, lower)(
            _key(seed, -1, 1)),
        "norm_f": _maker((d,), "gain", float(sd["norm_gain_sd"]), False)(
            _key(seed, -1, 2)),
    }
