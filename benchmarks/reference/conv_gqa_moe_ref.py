"""The plain reference of the gated-short-convolution / rotary grouped-query /
routed-expert stack (``model_type: lfm2_moe``; in the program
``attention_kind="gqa"`` with a ``layer_pattern`` over ``C``, ``A``, ``D``,
``E``), for the comparison that decides ``correct`` and for the program's own
CPU tests: imported from nowhere in the program. Straightforward float32
``jax.numpy`` at ``highest`` matmul precision, one session at a time, the
whole session every time: no cache, no carry between calls, no batching, no
kernels, no sorting of tokens by expert. The convolution is a ``lax.scan``
over tokens that carries the last ``L - 1`` inputs, attention one masked
score matrix with rotary positions, every pick of a held expert a plain
matmul.

``cfg`` is a plain dict under the published config's own key names
(``hidden_size``, ``layer_types``, ``conv_L_cache``, ``num_dense_layers``,
``num_experts`` ...) plus the chip's share: ``experts_held`` experts from
``expert_offset``. ``params`` is ``{"item_emb", "norm_f", "layers": [...]}``
under the program's names, TWO dicts a published layer (its operator, then
its feed-forward part, each with the norm in front of it); arrays of any float
dtype are up-cast here. The head is the embedding (tied).

    x = E[tokens]                                   (x_t in R^hidden)
    layer i (all norms RMSNorm, eps norm_eps; no biases):
      operator, x <- x + f(operator_norm(x)):
       conv            [B, C, z] = W_in n (three vectors of hidden); u_t = B_t * z_t
                       v_t = sum_{j=0..L-1} w_j * u_{t-L+1+j}   (zeros before token 0; no bias, no activation)
                       f = W_out (C_t * v_t)
       full_attention  q = W_q n (H x dh), k = W_k n, v = W_v n (KV x dh); RMSNorm over
                       each head's dh values of q and of k (a gain each); rotary
                       embedding over the whole head, pair (i, i + dh / 2) turned by
                       t * rope_theta^(-2 i / dh); query head i reads key/value head
                       floor(i / (H / KV)); causal softmax(q . k / sqrt(dh)) v; f = W_o a
      feed-forward, x <- x + g(ffn_norm(x)):
       i < num_dense_layers   g = W2 (silu(W1 n) * W3 n)
       else                   s = sigmoid(W_r n); picks = top-k of s + b_r (use_expert_bias:
                              selection only); weights s_e / sum_picks s (norm_topk_prob)
                              x routed_scaling_factor; g = sum_{e in picks, held here}
                              weight_e W2_e (silu(W1_e n) * W3_e n); no shared expert
    logits = embedding_norm(x) E^T                  (the tied head)

Departures from the published modelling code (``modeling_lfm2_moe.py``):
(a) the weights' denominator has no ``+ 1e-6`` (sigmoid scores of four picks
sum to well over 1e-3: under 1e-3 relative, the same in program and here);
(b) weights are seeded, not the checkpoint; (c) the experts are visited in a
``fori_loop``, every held expert computing every token, instead of a loop
over the experts that were hit: the same sum; (d) the query rows of an
attention layer go in blocks of ``ROWS`` so that the ``[heads, rows, T]``
scores fit: each row's scores and softmax are its own, whole; (e) the
convolution is written token by token where the code calls ``conv1d`` with
left padding ``L - 1``: the same taps on the same inputs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ROWS = 512


def mm(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def rms_norm(x, g, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def round_to(v, stored):
    """``v`` rounded to the values ``stored`` (a dtype) holds, kept float32
    (not ``astype`` there and back: the TPU compiler keeps the excess
    precision of such a pair where it can; PERF.md PR 34)."""
    if stored is None:
        return v
    info = jnp.finfo(stored)
    return jax.lax.reduce_precision(v, info.nexp, info.nmant)


def parts(cfg: dict) -> list:
    """``[(operator, feed-forward)]`` a layer: ``"conv"`` or
    ``"full_attention"``, then ``"dense"`` or ``"experts"``."""
    return [(op, "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, op in enumerate(cfg["layer_types"])]


def conv_inputs(x, lw: dict, stored=None):
    """``(u [T, hidden], C [T, hidden])`` of the normed ``x``: the
    convolution's inputs ``B * z`` and the gate after it. ``stored`` (a
    dtype, for the comparison of the carry alone): what the configuration's
    ``precision`` holds in that dtype, the projection's input and ``u``
    itself, is rounded to it; the arithmetic stays float32."""
    gate_in, gate_out, z = jnp.split(mm(round_to(x, stored), lw["w_in"]), 3, -1)
    return round_to(gate_in * z, stored), gate_out


def conv(x, lw: dict, cfg: dict):
    """The gated short convolution, token by token: the scan carries the last
    ``L - 1`` inputs, zeros before token 0."""
    k = cfg["conv_L_cache"]
    u, gate_out = conv_inputs(x, lw)
    taps = lw["conv_w"].astype(F32)

    def token(before, u_t):
        window = jnp.concatenate([before, u_t[None]])       # [L, hidden]
        return window[1:], (taps * window).sum(0)

    _, v = jax.lax.scan(token, jnp.zeros((k - 1, u.shape[1]), F32), u)
    return mm(gate_out * v, lw["w_out"])


def rope(x, theta: float):
    """Rotary embedding on ``x [T, heads, dh]`` at positions ``0..T-1``:
    half-split pairs over the whole head."""
    t, _, dh = x.shape
    half = dh // 2
    inv_freq = float(theta) ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, lw: dict, cfg: dict):
    t = x.shape[0]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // h
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = rope(rms_norm(mm(x, lw["w_q"]).reshape(t, h, dh), lw["norm_qh"], eps),
             theta).reshape(t, kv, h // kv, dh)
    k = rope(rms_norm(mm(x, lw["w_k"]).reshape(t, kv, dh), lw["norm_kh"],
                      eps), theta)
    v = mm(x, lw["w_v"]).reshape(t, kv, dh)

    def rows(args):
        qb, at = args
        seen = jnp.arange(t)[None, :] <= at[:, None]
        s = jnp.einsum("rngd,snd->ngrs", qb, k, precision=HI) / math.sqrt(dh)
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("ngrs,snd->rngd", prob, v, precision=HI).reshape(
            -1, h * dh)

    r = math.gcd(t, ROWS)
    a = jax.lax.map(rows, (q.reshape(t // r, r, kv, h // kv, dh),
                           jnp.arange(t).reshape(t // r, r)))
    return mm(a.reshape(t, h * dh), lw["w_o"])


def gated(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def dense(x, lw: dict, cfg: dict):
    return gated(x, lw["w1"], lw["w3"], lw["w2"])


def route(x, lw: dict, cfg: dict):
    """``(idx [T, k], w [T, k])``: sigmoid scores, top-k of score + bias,
    the picks' scores normalised over the picks, times the scaling factor."""
    s = jax.nn.sigmoid(mm(x, lw["w_r"]))
    _, idx = jax.lax.top_k(s + lw["b_r"].astype(F32),
                           cfg["num_experts_per_tok"])
    si = jnp.take_along_axis(s, idx, -1)
    return idx, si / si.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def experts(x, lw: dict, cfg: dict):
    """The routed experts held here; a pick that fell on an expert held
    elsewhere adds nothing."""
    idx, w = route(x, lw, cfg)

    def one(e, y):
        mine = jnp.where(idx == cfg["expert_offset"] + e, w, 0.0).sum(-1)
        return y + mine[:, None] * gated(
            x, lw["we1"][e], lw["we3"][e], lw["we2"][e])

    return jax.lax.fori_loop(0, cfg["experts_held"], one, jnp.zeros_like(x))


PARTS = {"conv": conv, "full_attention": attention, "dense": dense,
         "experts": experts}


def sub_block(h, lw: dict, cfg: dict, part: str):
    """One residual branch: ``h + part(norm(h))``."""
    norm = lw["norm2"] if part == "experts" else lw["norm1"]
    return h + PARTS[part](rms_norm(h, norm, cfg["norm_eps"]), lw, cfg)


def first_carry(params: dict, lw: dict, tokens, count, cfg: dict, stored):
    """The ``L - 1`` inputs ``[L - 1, hidden]`` the FIRST layer's convolution
    (its input is the embedding) carries after ``count`` of ``tokens``, zero
    rows in front of a session shorter than that: what a served session's
    carry is compared with. Deeper layers' inputs differ between the program
    and this file by what the layers before them rounded."""
    assert cfg["layer_types"][0] == "conv"
    k = cfg["conv_L_cache"]
    x = rms_norm(embed(params, tokens), lw["norm1"], cfg["norm_eps"])
    u, _ = conv_inputs(x, lw, stored)
    ext = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u])
    return jax.lax.dynamic_slice_in_dim(ext, count, k - 1)


def embed(params: dict, tokens):
    return params["item_emb"][jnp.asarray(tokens)].astype(F32)


def logits(params: dict, h, cfg: dict):
    return mm(rms_norm(h, params["norm_f"], cfg["norm_eps"]),
              params["item_emb"].T)


def forward(params: dict, tokens, cfg: dict, last_only: bool = False):
    """One session ``[T]`` of token ids (no padding) → logits ``[T, V]``
    (``[V]`` of the last position with ``last_only``)."""
    h = embed(params, tokens)
    layers = iter(params["layers"])
    for pair in parts(cfg):
        for part in pair:
            h = sub_block(h, next(layers), cfg, part)
    return logits(params, h[-1] if last_only else h, cfg)
