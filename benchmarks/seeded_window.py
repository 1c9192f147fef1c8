"""What a run of the histories cell makes from ``--seed``: the weights of the
window / full grouped-query attention stack with softmax-routed experts, for
the published key set of ``seq-mellum2-12b-ep4`` (``layer_types``,
``sliding_window``, ``rope_parameters``, ``num_experts`` ...), as
``benchmarks/seeded_seq.py`` makes the latent block's: every array a function
of (seed, sub-block, name) alone. A published layer is TWO sub-blocks, its
attention and its experts, each with the norm in front of it (``parts``):
sub-block ``2 i`` is layer ``i``'s attention, ``2 i + 1`` its experts.
Matrices normal with fan-in scaling, norm gains ``1 + sd n``, no router bias
(the router is a softmax). Embedding, untied head and final norm are
``seeded_seq.top_weights``. Imports nothing of the program. ``control``:
``True`` / ``"float8"`` rounds the bfloat16 matrices through float8_e4m3fn
(the other controls change the program, not the weights).
"""

from __future__ import annotations

from benchmarks.seeded_seq import _key, _maker, top_weights as _top

PARTS = ("sliding_attention", "full_attention", "experts")


def shape_config(cfg: dict) -> dict:
    """The reference's ``cfg`` dict from a configuration file: the published
    keys as they stand plus the chip's share."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "layer_types", "sliding_window", "rope_parameters",
            "rms_norm_eps", "num_experts", "num_experts_per_tok",
            "moe_intermediate_size", "norm_topk_prob", "experts_held",
            "expert_offset")
    return {k: cfg[k] for k in keys}


def parts(cfg: dict) -> list:
    """The sub-blocks in order, two a published layer."""
    return [part for kind in cfg["layer_types"] for part in (kind, "experts")]


def layer_shapes(cfg: dict, part: str) -> dict:
    """``{name: (shape, how it is made)}`` of one sub-block, under the
    program's names."""
    d = cfg["hidden_size"]
    if part != "experts":
        h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        return {"norm1": ((d,), "gain"), "w_q": ((d, h * dh), "bfloat16"),
                "w_k": ((d, kv * dh), "bfloat16"),
                "w_v": ((d, kv * dh), "bfloat16"),
                "w_o": ((h * dh, d), "bfloat16")}
    f, e = cfg["moe_intermediate_size"], cfg["experts_held"]
    return {"norm2": ((d,), "gain"),
            "w_r": ((d, cfg["num_experts"]), "float32"),
            "we1": ((e, d, f), "bfloat16"), "we3": ((e, d, f), "bfloat16"),
            "we2": ((e, f, d), "bfloat16")}


def _lowered(control) -> bool:
    return control in (True, "float8")


def layer_weights(seed: int, index: int, cfg: dict, control=False):
    """Sub-block ``index``'s arrays on the default device."""
    lower = _lowered(control)
    out = {}
    for i, (name, (shape, how)) in enumerate(
            layer_shapes(cfg, parts(cfg)[index]).items()):
        scale = cfg["seeded"]["norm_gain_sd"] if how == "gain" \
            else shape[-2] ** -0.5
        out[name] = _maker(tuple(shape), how, float(scale), lower)(
            _key(seed, index, i))
    return out


def top_weights(seed: int, cfg: dict, control=False) -> dict:
    """Embedding rows, the untied head's rows and the final norm's gain."""
    return _top(seed, cfg, _lowered(control))
