"""Two more letters of a layer pattern (models/state_space.py holds the
pattern; ``TransformerConfig(attention_kind="gqa", layer_pattern=...)``): the
**gated short convolution** (``"C"``) and the **dense gated feed-forward**
part (``"D"``). Each is ``h <- h + f(norm(h))`` behind one RMSNorm, like
every letter.

The convolution (``d = d_model``, ``K = conv_kernel`` taps)::

    [B, C, x] = W_in n                 three vectors of d
    u_t = B_t * x_t
    v_t = sum_{j=0..K-1} w_j * u_(t-K+1+j)     causal, depthwise, zeros before token 0; no bias, no activation
    f = W_out (C_t * v_t)

The feed-forward part: ``f = W2 (silu(W1 n) * W3 n)`` at ``intermediate_size``.

What a session carries between dispatches is the convolution's last ``K - 1``
REAL ``u`` rows (``state_layout``: ``(K - 1) * d`` values in the weights'
dtype, a row of a ``[slots, values]`` array a layer): a padding position has
``u = 0`` and is passed over when the rows to keep are picked, a block that
starts at offset 0 starts from zeros whatever its slot held, and a session of
fewer than ``K - 1`` tokens carries zero rows in front of its own.

Precision: the two projections multiply in the weights' dtype and accumulate
in float32; the gates, the taps and their sum are float32; ``u`` is rounded to
the weights' dtype BEFORE the convolution, the block's own rows as the carried
ones, so a turn and a recomputation see the same inputs.

Named scopes: ``conv_proj`` (norm, ``W_in``, ``W_out``), ``conv_mix`` (carry
read, gates, taps, carry write), ``ffn_dense``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.models.latent_moe import (
    F32,
    _expert,
    _mm,
    put_slot_rows,
    rms_norm,
    slot_rows,
)

#: the named scopes of each letter
SCOPES = {"C": ("conv_proj", "conv_mix"), "D": ("ffn_dense",)}


def published(cfg) -> dict:
    """``TransformerConfig`` → the reference's dict, under the published
    config's key names (benchmarks/reference/conv_gqa_moe_ref.py): a
    published layer is two letters, its operator (``"C"`` or ``"A"``) and its
    feed-forward part (``"D"`` in the first ``num_dense_layers``, then
    ``"E"``)."""
    operators, forward = cfg.layer_pattern[0::2], cfg.layer_pattern[1::2]
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "layer_types": [{"C": "conv", "A": "full_attention"}[k]
                        for k in operators],
        "conv_L_cache": cfg.conv_kernel,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_dense_layers": forward.count("D"),
        "num_experts": cfg.n_routed_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "experts_held": cfg.experts_held or cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
    }


def shapes(cfg, kind: str) -> dict:
    """The arrays of a ``"C"`` or a ``"D"`` layer beside its norm."""
    d = cfg.d_model
    if kind == "D":
        f = cfg.intermediate_size
        return {"w1": ((d, f), False), "w3": ((d, f), False),
                "w2": ((f, d), False)}
    return {"w_in": ((d, 3 * d), False),
            "conv_w": ((cfg.conv_kernel, d), True),
            "w_out": ((d, d), False)}


def state_layout(cfg) -> dict:
    """What a ``"C"`` layer keeps for a session, ``{name: (values, dtype)}``:
    the convolution's last ``conv_kernel - 1`` inputs."""
    return {"conv": ((cfg.conv_kernel - 1) * cfg.d_model,
                     jnp.dtype(cfg.weight_dtype))}


def gated_conv(lw, h, cfg, token_valid, counts=None, carried=None):
    """``h [B, T, d] + f(norm(h))``. ``carried``: the ``K - 1`` inputs ``[B,
    K - 1, d]`` from before the block (``None``: zeros, the block starts its
    sessions). Returns ``(h, the last K - 1 inputs up to counts real
    tokens)``; nothing is carried on without ``counts``."""
    b, t, d = h.shape
    k = cfg.conv_kernel
    wdt = lw["w_in"].dtype
    if carried is None:
        carried = jnp.zeros((b, k - 1, d), wdt)
    with jax.named_scope("conv_proj"):
        proj = _mm(rms_norm(h, lw["norm1"], cfg.rms_norm_eps), lw["w_in"])
        gate_in, gate_out, x = jnp.split(proj, 3, -1)
    with jax.named_scope("conv_mix"):
        # (padding rows are zeros: left padding stands for "before token 0")
        u = jnp.where(token_valid[..., None], gate_in * x, 0.0).astype(wdt)
        ext = jnp.concatenate([carried, u], 1)
        v = sum(lw["conv_w"][j] * ext[:, j:j + t].astype(F32)
                for j in range(k))
        kept = None if counts is None else jnp.take_along_axis(
            ext, (counts[:, None] + jnp.arange(k - 1))[..., None], 1)
        y = gate_out * v
    with jax.named_scope("conv_proj"):
        return h + _mm(y, lw["w_out"]), kept


def dense_ffn(lw, h, cfg):
    """``h + W2 (silu(W1 n) * W3 n)``, ``n = norm(h)``."""
    with jax.named_scope("ffn_dense"):
        x = rms_norm(h, lw["norm1"], cfg.rms_norm_eps)
        return h + _expert(x.astype(lw["w1"].dtype), lw, ("w1", "w3", "w2"),
                           _mm)


def layer(kind: str, lw, h, cfg, token_valid):
    """A ``"C"`` or ``"D"`` layer when the block is its own context (``fit``,
    ``forward``)."""
    if kind == "D":
        return dense_ffn(lw, h, cfg)
    return gated_conv(lw, h, cfg, token_valid)[0]


# -- the serving side ----------------------------------------------------------------

def conv_step(lw, cache, counters, h, slots, offsets, counts, *, cfg, form):
    """A ``"C"`` layer of "extend a batch of sessions by a block each": each
    session's last inputs are read from its slot (zeros for a block that
    starts at offset 0, whatever the slot held), the block's real tokens are
    convolved after them, and the last ``K - 1`` real inputs written back."""
    b = h.shape[0]
    token_valid = jnp.arange(h.shape[1])[None, :] < counts[:, None]
    with jax.named_scope("conv_mix"):
        kept = jnp.where((offsets == 0)[:, None], 0.0,
                         slot_rows(cache["conv"], slots)).reshape(
            b, cfg.conv_kernel - 1, -1).astype(cache["conv"].dtype)
    h, kept = gated_conv(lw, h, cfg, token_valid, counts, kept)
    with jax.named_scope("conv_mix"):
        new = put_slot_rows(cache["conv"], slots, kept.reshape(b, -1))
    return h, {"conv": new}, counters


def ffn_step(lw, cache, counters, h, slots, offsets, counts, *, cfg, form):
    """A ``"D"`` layer of a pattern: no context, nothing cached."""
    return dense_ffn(lw, h, cfg), cache, counters


STEPS = {"C": conv_step, "D": ffn_step}
