"""The plain reference of the grouped-query / sparse-index / routed-expert
block (``attention_kind="gqa_sparse"``), for the comparison that decides
``correct`` and for the program's own CPU tests: imported from nowhere in the
program. Straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision, one session at a time, the whole session every time: no cache, no
chunks, no kernels, no gather of selected rows, no sorting of tokens by
expert. The selection is a mask over the full causal score matrix.

``cfg`` is a plain dict under the published config's own key names
(``hidden_size``, ``num_key_value_heads``, ``sa_config`` ...) plus the chip's
share: ``experts_held`` experts from ``expert_offset`` (here all of them).
``params`` is ``{"item_emb", "head", "norm_f", "layers": [one dict a layer]}``
under the program's names; arrays of any float dtype are up-cast here.

    x = E[tokens]                                   (x_t in R^hidden)
    per layer (all norms RMSNorm, eps rms_norm_eps; no biases):
     1 h = norm(x);  q = W_q h (H x dh), k = W_k h (KV x dh), v = W_v h (KV x dh)
       q, k <- RMSNorm over the dh of each head (one gain for q, one for k)
       q, k <- rope, theta rope_theta, half-split pairs (i, i + dh / 2)
       query head i reads key/value head floor(i / (H / KV))
     2 indexer: qI_{t,j} = WI_q h_t (j = 1..J, di each), kI_s = WI_k h_s (one
       head of di), w_t = WI_w h_t in R^J; rope as above on the di;
       I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s) for s <= t
       S_t = the topk largest I_{t,.} (all s <= t while t < topk)
     3 a_{t,i} = sum_{s in S_t} softmax_s(q_{t,i} . k_{s,g(i)} / sqrt(dh)) v_{s,g(i)}
       x <- x + W_o a_t
     4 h = norm(x);  p = softmax(W_r h) over the experts;  E = top-k of p
       x <- x + sum_{e in E, held here} (p_e / sum_E p) W2_e (silu(W1_e h) * W3_e h)
    logits = norm(x) H^T                            (the untied head)

Departures from the equations as ISSUE 30 writes them, none of which changes
a number: (a) the query rows of a session go through steps 2-3 in blocks of
``ROWS`` so that the ``[heads, rows, T]`` score arrays fit a device at 28k
tokens: each row's scores, selection and softmax are its own, whole; (b) a
row that sees fewer than ``topk`` keys lets ``lax.top_k`` return masked
positions too, which the causal mask then removes; among EQUAL scores
``lax.top_k`` keeps the lower index; (c) the experts are visited in a
``fori_loop``, every expert computing every token, instead of 128 unrolled
copies of the same lines.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ROWS = 256


def mm(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def rms_norm(x, g, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def rope(x, pos, theta: float):
    """Half-split rope on the last axis of ``x [T, ..., dim]``: the pair
    ``(i, i + dim / 2)`` turns by ``pos * theta ** (-2 i / dim)``."""
    half = x.shape[-1] // 2
    inv_freq = jnp.asarray(
        [float(theta) ** (-i / half) for i in range(half)], F32)
    ang = pos.astype(F32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def selection(index_scores, seen, topk: int):
    """``[R, T]`` bool: each row's ``topk`` best-scored visible keys (every
    visible key while the row sees no more than that)."""
    if index_scores.shape[-1] <= topk:
        return seen
    _, at = jax.lax.top_k(jnp.where(seen, index_scores, -jnp.inf), topk)
    rows = jnp.arange(index_scores.shape[0])[:, None]
    return jnp.zeros(seen.shape, bool).at[rows, at].set(True) & seen


def attention(x, lw: dict, cfg: dict, pos, with_selection: bool = False):
    t = x.shape[0]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa, eps, theta = cfg["sa_config"], cfg["rms_norm_eps"], cfg["rope_theta"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]

    q = rms_norm(mm(x, lw["w_q"]).reshape(t, h, dh), lw["norm_qh"], eps)
    k = rms_norm(mm(x, lw["w_k"]).reshape(t, kv, dh), lw["norm_kh"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    v = mm(x, lw["w_v"]).reshape(t, kv, dh)
    q_idx = rope(mm(x, lw["wi_q"]).reshape(t, j, di), pos, theta)
    k_idx = rope(mm(x, lw["wi_k"]), pos, theta)
    w_idx = mm(x, lw["wi_w"])

    def rows(args):
        qb, qib, wib, at = args
        seen = jnp.arange(t)[None, :] <= at[:, None]
        scores = (jax.nn.relu(jnp.einsum(
            "rjd,sd->rjs", qib, k_idx, precision=HI)) * wib[..., None]).sum(1)
        chosen = selection(scores, seen, sa["topk"])
        s = jnp.einsum("rngd,snd->ngrs", qb.reshape(-1, kv, h // kv, dh), k,
                       precision=HI) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(chosen[None, None], s, -jnp.inf), -1)
        a = jnp.einsum("ngrs,snd->rngd", p, v, precision=HI)
        return a.reshape(-1, h * dh), chosen

    r = math.gcd(t, ROWS)

    def blocks(a):
        return a.reshape((t // r, r) + a.shape[1:])

    a, chosen = jax.lax.map(rows, (blocks(q), blocks(q_idx), blocks(w_idx),
                                   blocks(jnp.arange(t))))
    out = mm(a.reshape(t, h * dh), lw["w_o"])
    return (out, chosen.reshape(t, t)) if with_selection else out


def route(x, lw: dict, cfg: dict):
    """``(idx [T, k], w [T, k])``: softmax over all the experts, top-k, the
    picks' probabilities normalised over the picks."""
    p = jax.nn.softmax(mm(x, lw["w_r"]), -1)
    pi, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return idx, pi / pi.sum(-1, keepdims=True)


def gated(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def experts(x, lw: dict, cfg: dict):
    """The routed experts held here; a pick that fell on an expert held
    elsewhere adds nothing. No shared expert."""
    idx, w = route(x, lw, cfg)

    def one(e, y):
        mine = jnp.where(idx == cfg["expert_offset"] + e, w, 0.0).sum(-1)
        return y + mine[:, None] * gated(
            x, lw["we1"][e], lw["we3"][e], lw["we2"][e])

    return jax.lax.fori_loop(
        0, cfg["experts_held"], one, jnp.zeros(x.shape, F32))


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
