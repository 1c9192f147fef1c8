"""Plain reference of the latent-attention / routed-expert block
(``TransformerConfig(attention_kind="mla")``): straightforward float32
``jax.numpy`` at ``highest`` matmul precision, one session at a time, the
whole session every time. No cache, no batching, no sorting of tokens by
expert, nothing imported from the rest of the package.

``cfg`` is a plain dict under the published config's own key names
(``hidden_size``, ``q_lora_rank``, ``rope_parameters`` ...) plus the chip's
share: ``experts_held`` experts from ``expert_offset``. ``params`` is
``{"item_emb", "head", "norm_f", "layers": [one dict a layer]}`` under the
program's names; arrays of any float dtype are up-cast here.

    h  = E[tokens]
    per layer:
      x   = RMSNorm(h)
      cq  = RMSNorm(x W_dq);  q = cq W_uq -> [T, H, nope + rope]
      ckv, kr = split(x W_dkv);  ckv = RMSNorm(ckv)
      k_nope, v = split(ckv W_ukv -> [T, H, nope + v])
      q_rope, k_rope = RoPE(q_rope, pos), RoPE(kr, pos)   # pairs (2i, 2i+1)
      a   = softmax(scale (q_nope.k_nope + q_rope.k_rope), causal) v
      h   = h + a W_o
      x   = RMSNorm(h)
      g   = sigmoid(x W_r);  idx = top_k(g + b)
      w   = g[idx] / sum(g[idx]) * routed_scaling_factor
      h   = h + sum_{e in idx, held here} w_e expert_e(x) + shared(x)
    logits = RMSNorm(h) H^T
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def mm(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, g, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


# -- rotary positions (yarn) -------------------------------------------------------

def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(rope: dict, dim: int) -> np.ndarray:
    """``dim // 2`` inverse frequencies: interpolated (``/ factor``) where a
    pair turns fewer than ``beta_slow`` times over the original context,
    extrapolated (unchanged) where it turns more than ``beta_fast`` times, a
    linear blend between."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns: float) -> float:
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    return ((1.0 / (factor * pos_freqs)) * (1.0 - extrapolated)
            + (1.0 / pos_freqs) * extrapolated).astype(np.float32)


def rope_amplitude(rope: dict) -> float:
    """The factor on cos / sin: mscale over mscale_all_dim (1 when equal)."""
    factor = float(rope["factor"])
    if rope.get("mscale") and rope.get("mscale_all_dim"):
        return _yarn_mscale(factor, float(rope["mscale"])) \
            / _yarn_mscale(factor, float(rope["mscale_all_dim"]))
    return _yarn_mscale(factor, 1.0)


def softmax_scale(cfg: dict) -> float:
    """``qk_head_dim ** -0.5 * m * m``, m = 0.1 mscale_all_dim ln(factor) + 1."""
    rope = cfg["rope_parameters"]
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rope.get("mscale_all_dim"):
        m = _yarn_mscale(float(rope["factor"]), float(rope["mscale_all_dim"]))
        scale *= m * m
    return scale


def rotate(x, pos, inv_freq, amplitude: float = 1.0):
    """RoPE on the last axis of ``x`` ``[T, ..., dim]``: the pair
    ``(2i, 2i+1)`` turns by ``pos * inv_freq[i]``; the layout stays."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * amplitude).reshape(shape)
    sin = (jnp.sin(ang) * amplitude).reshape(shape)
    x = x.astype(F32)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def query_scaling(cfg: dict, pos):
    """``1 + beta ln(1 + floor(pos / original_max))``: 1 below the original
    context."""
    rope = cfg["rope_parameters"]
    beta = float(rope.get("llama_4_scaling_beta", 0.0))
    orig = float(rope["original_max_position_embeddings"])
    return 1.0 + beta * jnp.log1p(jnp.floor(pos.astype(F32) / orig))


# -- the layer -------------------------------------------------------------------------

def attention(x, lw: dict, cfg: dict, pos):
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    kvr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    rope = cfg["rope_parameters"]
    inv_freq, amp = yarn_inv_freq(rope, dr), rope_amplitude(rope)

    cq = rms_norm(mm(x, lw["w_dq"]), lw["norm_q"], eps)
    q = mm(cq, lw["w_uq"]).reshape(t, h, dn + dr)
    q = q * query_scaling(cfg, pos)[:, None, None]
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], pos, inv_freq, amp)
    kv = mm(x, lw["w_dkv"])
    ckv = rms_norm(kv[:, :kvr], lw["norm_kv"], eps)
    k_rope = rotate(kv[:, kvr:], pos, inv_freq, amp)            # [T, dr]
    kvu = mm(ckv, lw["w_ukv"]).reshape(t, h, dn + dv)
    k_nope, v = kvu[..., :dn], kvu[..., dn:]

    hi = jax.lax.Precision.HIGHEST
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope, precision=hi)
         + jnp.einsum("thd,sd->hts", q_rope, k_rope, precision=hi))
    s = s * softmax_scale(cfg)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shd->thd", p, v, precision=hi).reshape(t, h * dv)
    return mm(a, lw["w_o"])


def route(x, lw: dict, cfg: dict):
    """``(idx [T, k], w [T, k])``: sigmoid scores, the bias only selects, the
    weights are normalised over all k picks."""
    g = jax.nn.sigmoid(mm(x, lw["w_r"]))
    _, idx = jax.lax.top_k(g + lw["b_r"].astype(F32),
                           cfg["num_experts_per_tok"])
    gi = jnp.take_along_axis(g, idx, -1)
    return idx, gi / gi.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def gated(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def experts(x, lw: dict, cfg: dict):
    """The routed experts held here plus the shared expert; a pick that fell
    on an expert held elsewhere adds nothing."""
    idx, w = route(x, lw, cfg)
    y = gated(x, lw["ws1"], lw["ws3"], lw["ws2"])
    for j in range(cfg["experts_held"]):
        mine = jnp.where(idx == cfg["expert_offset"] + j, w, 0.0).sum(-1)
        y = y + mine[:, None] * gated(x, lw["we1"][j], lw["we3"][j],
                                      lw["we2"][j])
    return y


def layer(h, lw: dict, cfg: dict, pos):
    eps = cfg["rms_norm_eps"]
    h = h + attention(rms_norm(h, lw["norm1"], eps), lw, cfg, pos)
    return h + experts(rms_norm(h, lw["norm2"], eps), lw, cfg)


def embed(params: dict, tokens):
    return params["item_emb"][jnp.asarray(tokens)].astype(F32)


def logits(params: dict, h, cfg: dict):
    return mm(rms_norm(h, params["norm_f"], cfg["rms_norm_eps"]),
              params["head"].T)


def forward(params: dict, tokens, cfg: dict, last_only: bool = False):
    """One session ``[T]`` of token ids (no padding) → logits ``[T, V]``
    (``[V]`` of the last position with ``last_only``)."""
    pos = jnp.arange(len(tokens))
    h = embed(params, tokens)
    for lw in params["layers"]:
        h = layer(h, lw, cfg, pos)
    return logits(params, h[-1] if last_only else h, cfg)


def loss(params: dict, rows, cfg: dict):
    """Next-item cross-entropy over left-padded training rows
    ``[N, L + 1]`` (token 0 = padding): each row's real tokens are one
    session, position t predicts t + 1; the mean over all predictions."""
    total, count = 0.0, 0
    for row in np.asarray(rows):
        real = row[row != 0]
        if len(real) < 2:
            continue
        lp = jax.nn.log_softmax(forward(params, real[:-1], cfg), -1)
        total = total - jnp.take_along_axis(
            lp, jnp.asarray(real[1:])[:, None], -1).sum()
        count += len(real) - 1
    return total / max(count, 1)
