"""What a run of the visitor-session cell makes from ``--seed``: the weights
of the state-space / grouped-query / routed-expert stack, for the published
key set of ``seq-nemotron3-nano-ep2`` (``hybrid_override_pattern``,
``mamba_num_heads``, ``ssm_state_size``, ``n_groups`` ...), as
``benchmarks/seeded_seq.py`` makes the latent block's: every array a
function of (seed, layer, name) alone. Matrices normal with fan-in scaling,
norm gains ``1 + sd n`` (the mixer's gated norm too), a small nonzero
selection bias for the router and bias for the convolution; the Mamba-2
initialisation for what sets the recurrence's time scale: the step
``softplus(dt_bias)`` log-uniform in ``[dt_min, dt_max]`` (floor
``dt_floor``), ``A = -exp(a_log)`` uniform in ``-[a_min, a_max]``, ``d_skip``
one. Imports nothing of the program; arrays have the published shapes (the
engine pads where the program stores wider). ``control``: ``True`` /
``"float8"`` rounds the bfloat16 matrices through float8_e4m3fn, ``"no_skip"``
leaves the skip term out (``d_skip`` zeros).
"""

from __future__ import annotations

import functools
import math

from benchmarks.seeded_seq import _key, _maker, top_weights  # noqa: F401

KINDS = {"M": "state-space mixer", "*": "attention", "E": "experts"}


def shape_config(cfg: dict) -> dict:
    """The reference's ``cfg`` dict from a configuration file: the published
    keys as they stand plus the chip's share."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "hybrid_override_pattern", "mamba_num_heads",
            "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
            "layer_norm_epsilon", "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "routed_scaling_factor", "experts_held", "expert_offset")
    return {k: cfg[k] for k in keys}


def layer_shapes(cfg: dict, kind: str) -> dict:
    """``{name: (shape, how it is made)}`` of one layer of ``kind`` (a letter
    of the pattern), under the program's names."""
    d = cfg["hidden_size"]
    if kind == "M":
        heads = cfg["mamba_num_heads"]
        inner = heads * cfg["mamba_head_dim"]
        c = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
        return {
            "norm1": ((d,), "gain"),
            "w_in": ((d, inner + c + heads), "bfloat16"),
            "conv_w": ((cfg["conv_kernel"], c), "float32"),
            "conv_b": ((c,), "conv_bias"), "dt_bias": ((heads,), "dt_bias"),
            "a_log": ((heads,), "a_log"), "d_skip": ((heads,), "one"),
            "norm_g": ((inner,), "gain"), "w_out": ((inner, d), "bfloat16"),
        }
    if kind == "*":
        h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        return {"norm1": ((d,), "gain"), "w_q": ((d, h * dh), "bfloat16"),
                "w_k": ((d, kv * dh), "bfloat16"),
                "w_v": ((d, kv * dh), "bfloat16"),
                "w_o": ((h * dh, d), "bfloat16")}
    f, e = cfg["moe_intermediate_size"], cfg["experts_held"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    return {"norm2": ((d,), "gain"),
            "w_r": ((d, cfg["n_routed_experts"]), "float32"),
            "b_r": ((cfg["n_routed_experts"],), "router_bias"),
            "we1": ((e, d, f), "bfloat16"), "we2": ((e, f, d), "bfloat16"),
            "ws1": ((d, fs), "bfloat16"), "ws2": ((fs, d), "bfloat16")}


@functools.lru_cache(maxsize=8)
def _recurrence(shape: tuple, how: str, lo: float, hi: float, floor: float):
    import jax
    import jax.numpy as jnp

    def make(key):
        if how == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi))), floor)
        return step + jnp.log(-jnp.expm1(-step))     # softplus^-1

    return jax.jit(make)


def layer_weights(seed: int, layer: int, cfg: dict, control=False):
    """One layer's arrays on the default device."""
    import jax.numpy as jnp

    sd = cfg["seeded"]
    lower = control in (True, "float8")
    out = {}
    kind = cfg["hybrid_override_pattern"][layer]
    for index, (name, (shape, how)) in enumerate(
            layer_shapes(cfg, kind).items()):
        key = _key(seed, layer, index)
        if how == "one":
            out[name] = jnp.full(shape, 0.0 if control == "no_skip" else 1.0,
                                 jnp.float32)
        elif how == "a_log":
            out[name] = _recurrence(
                tuple(shape), how, sd["a_min"], sd["a_max"], 0.0)(key)
        elif how == "dt_bias":
            out[name] = _recurrence(tuple(shape), how, sd["dt_min"],
                                    sd["dt_max"], sd["dt_floor"])(key)
        else:
            scale = {"gain": sd["norm_gain_sd"],
                     "router_bias": sd["router_bias_sd"],
                     "conv_bias": sd["conv_bias_sd"]}.get(
                         how, shape[-2] ** -0.5 if len(shape) > 1 else 1.0)
            made = {"gain": "gain", "bfloat16": "bfloat16"}.get(how, "float32")
            out[name] = _maker(tuple(shape), made, float(scale), lower)(key)
    return out
