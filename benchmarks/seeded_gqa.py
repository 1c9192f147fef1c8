"""What a run of the sparse-index sequence cell makes from ``--seed``: the
weights of the grouped-query / sparse-index / routed-expert block, for the
published key set of ``seq-keye-vl2-30b-a3b`` (``num_key_value_heads``,
``sa_config``, ``num_experts`` ...), as ``benchmarks/seeded_seq.py`` makes the
latent block's: every array a function of (seed, layer, name) alone, normal
with fan-in scaling for the matrices, gains ``1 + sd n`` for the norms (the
per-head q and k norms too), unit-variance embeddings and a head at ``d **
-0.5``. Imports nothing of the program. ``lower`` rounds the bfloat16
matrices through float8_e4m3fn: the control's one precision step down.
"""

from __future__ import annotations

from benchmarks.seeded_seq import _key, _maker, top_weights  # noqa: F401


def shape_config(cfg: dict) -> dict:
    """The reference's ``cfg`` dict from a configuration file: the published
    keys as they stand plus the chip's share."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "sa_config",
            "num_experts", "num_experts_per_tok", "moe_intermediate_size",
            "norm_topk_prob", "experts_held", "expert_offset")
    return {k: cfg[k] for k in keys}


def layer_shapes(cfg: dict) -> dict:
    """``{name: (shape, float32?)}`` of one layer, under the program's names."""
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    f, e = cfg["moe_intermediate_size"], cfg["experts_held"]
    return {
        "norm1": ((d,), True), "norm2": ((d,), True),
        "norm_qh": ((dh,), True), "norm_kh": ((dh,), True),
        "w_q": ((d, h * dh), False), "w_k": ((d, kv * dh), False),
        "w_v": ((d, kv * dh), False), "w_o": ((h * dh, d), False),
        "wi_q": ((d, j * di), False), "wi_k": ((d, di), False),
        "wi_w": ((d, j), False),
        "w_r": ((d, cfg["num_experts"]), True),
        "we1": ((e, d, f), False), "we3": ((e, d, f), False),
        "we2": ((e, f, d), False),
    }


def layer_weights(seed: int, layer: int, cfg: dict, lower: bool = False):
    """One layer's arrays on the default device."""
    sd = cfg["seeded"]
    out = {}
    for index, (name, (shape, f32)) in enumerate(layer_shapes(cfg).items()):
        if name.startswith("norm"):
            kind, scale = "gain", sd["norm_gain_sd"]
        else:
            kind, scale = "float32" if f32 else "bfloat16", shape[-2] ** -0.5
        out[name] = _maker(tuple(shape), kind, float(scale), lower)(
            _key(seed, layer, index))
    return out
