"""What a run of the feed cell makes from ``--seed``: the weights of the
gated-short-convolution / rotary grouped-query / routed-expert stack, for the
published key set of ``seq-lfm2-8b-a1b-ep2`` (``layer_types``,
``conv_L_cache``, ``num_dense_layers``, ``num_experts`` ...), as
``benchmarks/seeded_seq.py`` makes the latent block's: every array a function
of (seed, sub-block, name) alone. A published layer is TWO sub-blocks, its
operator and its feed-forward part, each with the norm in front of it
(``parts``): sub-block ``2 i`` is layer ``i``'s operator, ``2 i + 1`` its
feed-forward part. Matrices normal with fan-in scaling (the convolution's
taps too: fan-in ``conv_L_cache``), norm gains ``1 + sd n`` (the per-head
ones of q and k too), a small nonzero selection bias for the router. The
head is the embedding (tied): rows of sd ``embedding_sd``. Imports nothing
of the program. ``control``: ``True`` / ``"float8"`` rounds the bfloat16
matrices through float8_e4m3fn.
"""

from __future__ import annotations

from benchmarks.seeded_seq import _key, _maker

PARTS = ("conv", "full_attention", "dense", "experts")


def shape_config(cfg: dict) -> dict:
    """The reference's ``cfg`` dict from a configuration file: the published
    keys as they stand plus the chip's share."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "layer_types", "conv_L_cache", "intermediate_size",
            "moe_intermediate_size", "num_dense_layers", "num_experts",
            "num_experts_per_tok", "norm_eps", "rope_theta",
            "routed_scaling_factor", "experts_held", "expert_offset")
    return {k: cfg[k] for k in keys}


def parts(cfg: dict) -> list:
    """The sub-blocks in order, two a published layer."""
    return [part for i, op in enumerate(cfg["layer_types"])
            for part in (op, "dense" if i < cfg["num_dense_layers"]
                         else "experts")]


def layer_shapes(cfg: dict, part: str) -> dict:
    """``{name: (shape, how it is made)}`` of one sub-block, under the
    program's names."""
    d = cfg["hidden_size"]
    if part == "conv":
        return {"norm1": ((d,), "gain"), "w_in": ((d, 3 * d), "bfloat16"),
                "conv_w": ((cfg["conv_L_cache"], d), "float32"),
                "w_out": ((d, d), "bfloat16")}
    if part == "full_attention":
        h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dh = d // h
        return {"norm1": ((d,), "gain"), "norm_qh": ((dh,), "gain"),
                "norm_kh": ((dh,), "gain"), "w_q": ((d, h * dh), "bfloat16"),
                "w_k": ((d, kv * dh), "bfloat16"),
                "w_v": ((d, kv * dh), "bfloat16"),
                "w_o": ((h * dh, d), "bfloat16")}
    if part == "dense":
        f = cfg["intermediate_size"]
        return {"norm1": ((d,), "gain"), "w1": ((d, f), "bfloat16"),
                "w3": ((d, f), "bfloat16"), "w2": ((f, d), "bfloat16")}
    f, e = cfg["moe_intermediate_size"], cfg["experts_held"]
    return {"norm2": ((d,), "gain"),
            "w_r": ((d, cfg["num_experts"]), "float32"),
            "b_r": ((cfg["num_experts"],), "router_bias"),
            "we1": ((e, d, f), "bfloat16"), "we3": ((e, d, f), "bfloat16"),
            "we2": ((e, f, d), "bfloat16")}


def _lowered(control) -> bool:
    return control in (True, "float8")


def layer_weights(seed: int, index: int, cfg: dict, control=False):
    """Sub-block ``index``'s arrays on the default device."""
    sd = cfg["seeded"]
    lower = _lowered(control)
    out = {}
    for i, (name, (shape, how)) in enumerate(
            layer_shapes(cfg, parts(cfg)[index]).items()):
        scale = {"gain": sd["norm_gain_sd"],
                 "router_bias": sd["router_bias_sd"]}.get(
                     how, shape[-2] ** -0.5 if len(shape) > 1 else 1.0)
        made = {"gain": "gain", "bfloat16": "bfloat16"}.get(how, "float32")
        out[name] = _maker(tuple(shape), made, float(scale), lower)(
            _key(seed, index, i))
    return out


def top_weights(seed: int, cfg: dict, control=False) -> dict:
    """Embedding rows (the head too: tied) and the final norm's gain."""
    v, d, sd = cfg["vocab_size"], cfg["hidden_size"], cfg["seeded"]
    lower = _lowered(control)
    return {
        "item_emb": _maker((v, d), "bfloat16", float(sd["embedding_sd"]),
                           lower)(_key(seed, -1, 0)),
        "norm_f": _maker((d,), "gain", float(sd["norm_gain_sd"]), False)(
            _key(seed, -1, 2)),
    }
