"""Layer patterns with a selective state-space mixer: the block module of
``TransformerConfig(attention_kind="gqa", layer_pattern=...)``. Each layer is
``x <- x + f(norm(x))`` with ``f`` one thing, in the pattern's order: this
module's mixer (``"S"``), models/sparse_gqa.py's dense grouped-query
attention (``"A"``) or the same over a window of the last keys (``"W"``),
models/latent_moe.py's router and experts (``"E"``) or models/short_conv.py's
gated short convolution (``"C"``) and dense feed-forward part (``"D"``); the
norms, the head and the serving steps' geometry are latent_moe's.

The mixer (``inner = ssm_heads * ssm_head_dim``, ``G = ssm_groups``, ``N =
ssm_state``)::

    [z, xBC, dt] = W_in h            z in R^inner, xBC in R^(inner + 2 G N), dt in R^heads
    xBC_t <- silu(sum_j w_j * xBC_(t-K+1+j) + b)      causal, depthwise, zeros before token 0
    x_t (heads x head_dim), B_t, C_t (G x N) = split(xBC_t);  head i reads group i // (heads / G)
    D_t = softplus(dt_t + dt_bias),  A = -exp(a_log)
    S_t = exp(D_t A) S_(t-1) + D_t x_t (x) B_t        per head, S in R^(head_dim x N), S_(-1) = 0
    y_t = S_t C_t + d_skip x_t
    f = W_out (RMSNorm over each group's inner / G values of (y * silu(z)), times a gain)

The recurrence runs as a **chunked scan** (``scan``): inside a tile of
``ssm_chunk`` tokens the outputs are a masked matrix product (decays as
``exp`` of differences of a cumulative sum, never above 1), and the state is
read once before the tile and written once after it; tiles follow each other
in a ``lax.scan``. A short block is one tile. What a session carries between
dispatches is the state after its last REAL token and the convolution's last
``conv_kernel - 1`` real inputs: a padding position has ``D_t = 0`` (the
state decays by ``exp(0)`` and gains nothing) and is passed over when the
inputs to keep are picked.

Precision: the state, the decays, their cumulative sums, softplus and the
matrix products of the scan are float32 at ``highest`` (a bfloat16 pass would
round the state every time it is read); the two projections multiply in the
weights' dtype and accumulate in float32; the convolution's kept inputs are
in the weights' dtype, and the block's own are rounded to it before the
convolution, so a turn and a recomputation see the same inputs.

Named scopes: ``ssm_proj`` (norm, in / out projections, gated norm),
``ssm_conv``, ``ssm_scan`` (state read, scan, state write); ``gqa_proj``,
``gqa_attn`` are the attention layers'.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.models import (
    latent_moe,
    short_conv,
    sparse_gqa,
)
from incubator_predictionio_tpu.models.latent_moe import (
    F32,
    _mm,
    put_slot_rows,
    rms_norm,
    slot_rows,
)

#: the named scopes of each letter beside the experts'
KIND_SCOPES = {"S": ("ssm_proj", "ssm_conv", "ssm_scan"),
               "A": ("gqa_proj", "gqa_attn"), "W": ("gqa_proj", "win_attn"),
               **short_conv.SCOPES}
#: the letters whose layers keep a per-session state in a slot
STATEFUL = ("S", "C", "W")
HI = jax.lax.Precision.HIGHEST


def pattern_scopes(cfg) -> tuple:
    """The named scopes of the pattern's letters, in the order the letters
    first appear."""
    return tuple(dict.fromkeys(
        s for kind in dict.fromkeys(cfg.layer_pattern)
        for s in KIND_SCOPES.get(kind, ())))


def published(cfg) -> dict:
    """``TransformerConfig`` → the reference's dict, under the published
    config's key names (benchmarks/reference/ssm_gqa_moe_ref.py)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "hybrid_override_pattern": cfg.layer_pattern.translate(
            str.maketrans("SA", "M*")),
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.conv_kernel,
        "layer_norm_epsilon": cfg.rms_norm_eps,
        "n_routed_experts": cfg.n_routed_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "moe_shared_expert_intermediate_size": cfg.shared_intermediate_size,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "experts_held": cfg.experts_held or cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
    }


def _inner(cfg) -> int:
    return cfg.ssm_heads * cfg.ssm_head_dim


def _conv_width(cfg) -> int:
    return _inner(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state


def mixer_shapes(cfg, kind: str) -> dict:
    """The arrays of an ``"S"``, ``"A"``, ``"W"``, ``"C"`` or ``"D"`` layer
    beside its norm (a ``"W"`` layer's are the ``"A"`` letter's)."""
    if kind in "AW":
        return sparse_gqa.dense_shapes(cfg)
    if kind in short_conv.STEPS:
        return short_conv.shapes(cfg, kind)
    d, inner, c, heads = cfg.d_model, _inner(cfg), _conv_width(cfg), \
        cfg.ssm_heads
    return {
        "w_in": ((d, inner + c + heads), False),
        "conv_w": ((cfg.conv_kernel, c), True), "conv_b": ((c,), True),
        "dt_bias": ((heads,), True), "a_log": ((heads,), True),
        "d_skip": ((heads,), True), "norm_g": ((inner,), True),
        "w_out": ((inner, d), False),
    }


def _dt_bias(key, shape):
    # softplus^-1 of a step log-uniform in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(
        key, shape, F32, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


#: initial values ``fit`` trains from, where fan-in scaling is not it
INIT = {
    "dt_bias": _dt_bias,
    "a_log": lambda key, shape: jnp.log(
        jax.random.uniform(key, shape, F32, 1.0, 16.0)),
    "d_skip": lambda key, shape: jnp.ones(shape, F32),
    "conv_b": lambda key, shape: jnp.zeros(shape, F32),
}


def row_layout(cfg) -> dict:
    """Per-token rows an ``"A"`` layer keeps in the paged cache."""
    return sparse_gqa.dense_row_layout(cfg)


def state_layout(cfg, kind: str = "S") -> dict:
    """What a layer of a ``STATEFUL`` kind keeps for a session, ``{name:
    (values, dtype)}``: an ``"S"`` layer the recurrent state and its
    convolution's last inputs, a ``"C"`` layer its convolution's alone, a
    ``"W"`` layer a ring of key/value rows (``values`` the ring's ``(rows,
    width)``)."""
    if kind == "C":
        return short_conv.state_layout(cfg)
    if kind == "W":
        return sparse_gqa.ring_layout(cfg)
    return {
        "state": (_inner(cfg) * cfg.ssm_state, jnp.dtype(cfg.state_dtype)),
        "conv": ((cfg.conv_kernel - 1) * _conv_width(cfg),
                 jnp.dtype(cfg.weight_dtype)),
    }


# -- the mixer ------------------------------------------------------------------------

def conv(xbc, prev, lw, counts=None):
    """Causal depthwise convolution and SiLU over ``xbc [B, T, C]`` after the
    ``K - 1`` inputs ``prev`` kept from before; returns ``(out [B, T, C]
    float32, kept)``: ``kept`` the last ``K - 1`` inputs up to ``counts``
    real tokens (``None``: nothing is kept)."""
    t, k = xbc.shape[1], lw["conv_w"].shape[0]
    ext = jnp.concatenate([prev, xbc], 1)
    out = lw["conv_b"] + sum(
        lw["conv_w"][j] * ext[:, j:j + t].astype(F32) for j in range(k))
    kept = None if counts is None else jnp.take_along_axis(
        ext, (counts[:, None] + jnp.arange(k - 1))[..., None], 1)
    return jax.nn.silu(out), kept


def scan(x, dt, a, bm, cm, state, chunk: int):
    """The recurrence over ``x [B, T, H, P]`` with steps ``dt [B, T, H]``,
    log-decays ``a = dt * A [B, T, H]`` (<= 0), ``bm``, ``cm [B, T, G, N]``
    from ``state [B, H, P, N]`` → ``(y [B, T, H, P], state after T)``, in
    tiles of ``chunk`` tokens."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    r = h // g
    q = t if t <= chunk else math.gcd(t, chunk)

    def tile(s, args):
        x, dt, a, bm, cm = args                     # [B, q, ...]
        cum = jnp.cumsum(a, 1).reshape(b, q, g, r)
        xd = (x * dt[..., None]).reshape(b, q, g, r, p)
        later = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None, None]
        decay = jnp.exp(jnp.where(
            later, cum[:, :, None] - cum[:, None, :], -jnp.inf))
        pair = jnp.einsum("bqgn,bsgn->bqsg", cm, bm, precision=HI)
        y = jnp.einsum("bqsgr,bsgrp->bqgrp", pair[..., None] * decay, xd,
                       precision=HI)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bqgn,bgrpn->bqgrp", cm, s, precision=HI)
        last = cum[:, -1]
        s = s * jnp.exp(last)[..., None, None] + jnp.einsum(
            "bsgrp,bsgn->bgrpn", xd * jnp.exp(last[:, None] - cum)[..., None],
            bm, precision=HI)
        return s, y.reshape(b, q, h, p)

    s = state.astype(F32).reshape(b, g, r, p, n)
    if q == t:
        s, y = tile(s, (x, dt, a, bm, cm))
    else:
        def tiles(v):
            return jnp.moveaxis(v.reshape((b, t // q, q) + v.shape[2:]), 1, 0)

        s, y = jax.lax.scan(tile, s, tuple(map(tiles, (x, dt, a, bm, cm))))
        y = jnp.moveaxis(y, 0, 1).reshape(b, t, h, p)
    return y, s.reshape(b, h, p, n)


def mixer(lw, h, cfg, token_valid, counts=None, carried=None):
    """``h [B, T, d] + f(norm(h))``. ``carried``: ``(state [B, H, P, N],
    conv inputs [B, K - 1, C])`` from before the block (``None``: zeros, the
    block starts its sessions). Returns ``(h, carried after the last real
    token)``; nothing is carried on without ``counts``."""
    b, t, _ = h.shape
    heads, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, \
        cfg.ssm_groups
    inner, c = _inner(cfg), _conv_width(cfg)
    wdt = lw["w_in"].dtype
    if carried is None:
        carried = (jnp.zeros((b, heads, p, n), F32),
                   jnp.zeros((b, cfg.conv_kernel - 1, c), wdt))
    with jax.named_scope("ssm_proj"):
        proj = _mm(rms_norm(h, lw["norm1"], cfg.rms_norm_eps), lw["w_in"])
        z, xbc, dt = jnp.split(proj, [inner, inner + c], -1)
    with jax.named_scope("ssm_conv"):
        # (padding rows are zeros: left padding stands for "before token 0")
        xbc = jnp.where(token_valid[..., None], xbc, 0.0).astype(wdt)
        xbc, kept = conv(xbc, carried[1], lw, counts)
    with jax.named_scope("ssm_scan"):
        x = xbc[..., :inner].reshape(b, t, heads, p)
        bm, cm = (v.reshape(b, t, g, n)
                  for v in jnp.split(xbc[..., inner:], 2, -1))
        dt = jnp.where(token_valid[..., None],
                       jax.nn.softplus(dt + lw["dt_bias"]), 0.0)
        y, state = scan(x, dt, -jnp.exp(lw["a_log"]) * dt, bm, cm, carried[0],
                        cfg.ssm_chunk)
        y = (y + lw["d_skip"][:, None] * x).reshape(b, t, inner)
    with jax.named_scope("ssm_proj"):
        y = (y * jax.nn.silu(z)).reshape(b, t, g, inner // g)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, -1, keepdims=True) + cfg.rms_norm_eps)
        return h + _mm(y.reshape(b, t, inner) * lw["norm_g"], lw["w_out"]), \
            (state, kept)


def mixer_layer(kind: str, lw, h, cfg, q_index, token_valid, context,
                pos=None):
    """A pattern's layer other than ``"E"`` when the block is its own
    context (``fit``, ``forward``; ``pos``: the tokens' positions in their
    sessions where they are not ``q_index``). Returns ``(h, None)``."""
    if kind == "A":
        return sparse_gqa.dense_layer(lw, h, cfg, q_index, context, pos)
    if kind == "W":   # the keys' positions are their indices in the row
        def near(rows):
            ctx, valid, _ = context(rows)
            return ctx, jnp.where(valid, q_index, -1), None

        return sparse_gqa.dense_layer(lw, h, cfg, q_index, near, pos, "W")
    if kind in short_conv.STEPS:
        return short_conv.layer(kind, lw, h, cfg, token_valid), None
    return mixer(lw, h, cfg, token_valid)[0], None


block_context = latent_moe.block_context   # the block is its own context


# -- the serving side ----------------------------------------------------------------

def mixer_step(lw, cache, counters, h, slots, offsets, counts, *, cfg, form):
    """An ``"S"`` layer of "extend a batch of sessions by a block each": each
    session's state and convolution inputs are read from its slot (zeros for
    a block that starts at offset 0, whatever the slot held), carried over
    the block's real tokens and written back."""
    b = h.shape[0]
    token_valid = jnp.arange(h.shape[1])[None, :] < counts[:, None]
    fresh = (offsets == 0)[:, None]
    with jax.named_scope("ssm_scan"):
        state = jnp.where(fresh, 0.0, slot_rows(cache["state"], slots)).reshape(
            b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    with jax.named_scope("ssm_conv"):
        kept = jnp.where(fresh, 0.0, slot_rows(cache["conv"], slots)).reshape(
            b, cfg.conv_kernel - 1, -1).astype(cache["conv"].dtype)
    h, (state, kept) = mixer(lw, h, cfg, token_valid, counts, (state, kept))
    with jax.named_scope("ssm_scan"):
        new_state = put_slot_rows(cache["state"], slots, state.reshape(b, -1))
    with jax.named_scope("ssm_conv"):
        new_conv = put_slot_rows(cache["conv"], slots, kept.reshape(b, -1))
    return h, {"state": new_state, "conv": new_conv}, counters


STEPS = {"S": mixer_step, "A": sparse_gqa.dense_step,
         "W": sparse_gqa.window_step, **short_conv.STEPS}


def serve_shapes(cfg) -> latent_moe.ServeShapes:
    """The latent block's ladder under this block's names: short blocks
    batch (``step``: one tile from the sessions' cached states) over the
    smallest of a quarter, a half and the whole of ``max_len`` that holds
    the longest session's key/value rows; a long block runs whole, one
    session a dispatch (``scan``). A pattern with ``"W"`` layers has a
    ladder of its own (``sparse_gqa.window_serve_shapes``)."""
    if "W" in cfg.layer_pattern:
        return sparse_gqa.window_serve_shapes(cfg)
    return dataclasses.replace(
        latent_moe.serve_shapes(cfg), path="device-state-kv-cache",
        short_form="step", long_form="scan")


def count_dispatch(cfg, extents) -> None:
    """Host counters of one serving dispatch beyond the shared ``pio_seq_*``:
    the state's own are the session table's (serving/latent_cache.py)."""
