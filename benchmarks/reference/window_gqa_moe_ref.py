"""The plain reference of the window / full grouped-query attention stack with
softmax-routed experts (``model_type: mellum``; in the program
``attention_kind="gqa"`` with a ``layer_pattern`` over ``W``, ``A`` and
``E``), for the comparison that decides ``correct`` and for the program's own
CPU tests: imported from nowhere in the program. Straightforward float32
``jax.numpy`` at ``highest`` matmul precision, one session at a time, the
whole session every time: no cache, no ring, no batching, no kernels, no
sorting of tokens by expert. Attention is one masked ``[T, T]`` score matrix
a layer (computed a block of query rows at a time, each row whole), every
pick of a held expert a plain matmul.

``cfg`` is a plain dict under the published config's own key names
(``hidden_size``, ``layer_types``, ``sliding_window``, ``rope_parameters``,
``num_experts`` ...) plus the chip's share: ``experts_held`` experts from
``expert_offset``. ``params`` is ``{"item_emb", "head", "norm_f", "layers":
[...]}`` under the program's names, TWO dicts a published layer (its
attention, then its experts, each with the norm in front of it); arrays of
any float dtype are up-cast here.

    x = E[tokens]                                   (x_t in R^hidden)
    layer i (all norms RMSNorm, eps rms_norm_eps; no biases):
      attention, x <- x + W_o a(input_norm(x)):
        q = W_q n (H x dh), k = W_k n, v = W_v n (KV x dh); rotary embedding over
        the whole head, pair (j, j + dh / 2) turned by t * f_j, cos and sin times m;
        query head h reads key/value head floor(h / (H / KV));
        a = softmax(q . k / sqrt(dh) over the keys s the layer's type lets t see) v
        sliding_attention  f_j = rope_theta^(-2 j / dh), m = 1;
                           t sees s with t - sliding_window < s <= t
        full_attention     f_j, m by the yarn rule of rope_parameters.full_attention
                           (``yarn``, below); t sees every s <= t
      experts, x <- x + g(post_attention_norm(x)):
        p = softmax(W_r n) over all num_experts; picks = top-k of p;
        weights p_e / sum_picks p (norm_topk_prob);
        g = sum_{e in picks, held here} weight_e W2_e (silu(W1_e n) * W3_e n)
    logits = final_norm(x) H^T                      (an untied head)

Departures from the published description, and what the config does not
print (``assumed`` in the configuration file): (a) the router takes the
softmax over all experts and then the top-k (the order the published
``norm_topk_prob`` normalisation implies); (b) no per-head norm of q and k
(the config prints no key for one); (c) the window's edge: a query sees
itself and the ``sliding_window - 1`` tokens before it; (d) the yarn rule
multiplies cos and sin of q and k alike by ``attention_factor`` (so a score
by its square), as the ``yarn`` rope type of the published modelling code
does; (e) weights are seeded, not the checkpoint; (f) the experts are visited
in a ``fori_loop``, every held expert computing every token, instead of a
loop over the experts that were hit: the same sum; (g) the multi-token
prediction head the model card mentions has no key in the config and is left
out; (h) the query rows of a layer go in blocks of ``ROWS`` so that the
``[heads, rows, T]`` scores fit at 14k tokens: each row's mask, scores and
softmax are its own, whole.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ROWS = 256
PARTS = ("sliding_attention", "full_attention", "experts")


def mm(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def rms_norm(x, g, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def parts(cfg: dict) -> list:
    """The sub-blocks in order, two a published layer: its attention by its
    ``layer_types`` entry, then its experts."""
    return [part for kind in cfg["layer_types"] for part in (kind, "experts")]


def yarn(rope: dict, dim: int) -> tuple:
    """``(f [dim / 2], m)`` of ``rope_type: yarn``: a pair that turns more
    than ``beta_fast`` times over the original context keeps its frequency,
    one that turns fewer than ``beta_slow`` times has it divided by
    ``factor``, a linear blend between (by the pair's index); cos and sin are
    multiplied by ``attention_factor`` (printed, else 0.1 ln(factor) + 1)."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def pair_that_turns(times: float) -> float:
        return dim * math.log(orig / (times * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(pair_that_turns(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    j = np.arange(dim // 2, dtype=np.float64)
    plain = base ** (-2.0 * j / dim)
    keep = 1.0 - np.clip((j - low) / (high - low), 0.0, 1.0)
    f = plain / factor * (1.0 - keep) + plain * keep
    m = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return f.astype(np.float32), float(m)


def rotary(cfg: dict, part: str) -> tuple:
    """``(f, m)`` of a layer type from ``rope_parameters``."""
    rope = cfg["rope_parameters"][part]
    dim = cfg["head_dim"]
    if rope.get("rope_type", "default") == "yarn":
        return yarn(rope, dim)
    f = float(rope["rope_theta"]) ** (
        -2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    return f.astype(np.float32), 1.0


def rope(x, f, m: float):
    """Rotary embedding on ``x [T, heads, dh]`` at positions ``0..T-1``:
    half-split pairs over the whole head."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(f)
    cos, sin = (jnp.cos(ang) * m)[:, None], (jnp.sin(ang) * m)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, lw: dict, cfg: dict, part: str):
    t = x.shape[0]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, m = rotary(cfg, part)
    q = rope(mm(x, lw["w_q"]).reshape(t, h, dh), f, m).reshape(
        t, kv, h // kv, dh)
    k = rope(mm(x, lw["w_k"]).reshape(t, kv, dh), f, m)
    v = mm(x, lw["w_v"]).reshape(t, kv, dh)
    window = cfg["sliding_window"] if part == "sliding_attention" else t

    def rows(args):
        qb, at = args
        s_at = jnp.arange(t)[None, :]
        seen = (s_at <= at[:, None]) & (s_at > at[:, None] - window)
        s = jnp.einsum("rngd,snd->ngrs", qb, k, precision=HI) / math.sqrt(dh)
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("ngrs,snd->rngd", prob, v, precision=HI).reshape(
            -1, h * dh)

    r = math.gcd(t, ROWS)
    a = jax.lax.map(rows, (q.reshape(t // r, r, kv, h // kv, dh),
                           jnp.arange(t).reshape(t // r, r)))
    return mm(a.reshape(t, h * dh), lw["w_o"])


def route(x, lw: dict, cfg: dict):
    """``(idx [T, k], w [T, k])``: softmax over every expert, top-k, the
    picks' probabilities normalised over the picks."""
    p = jax.nn.softmax(mm(x, lw["w_r"]), -1)
    pi, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return idx, pi / pi.sum(-1, keepdims=True)


def gated(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def experts(x, lw: dict, cfg: dict, part: str = "experts"):
    """The routed experts held here; a pick that fell on an expert held
    elsewhere adds nothing."""
    idx, w = route(x, lw, cfg)

    def one(e, y):
        mine = jnp.where(idx == cfg["expert_offset"] + e, w, 0.0).sum(-1)
        return y + mine[:, None] * gated(
            x, lw["we1"][e], lw["we3"][e], lw["we2"][e])

    return jax.lax.fori_loop(0, cfg["experts_held"], one, jnp.zeros_like(x))


def sub_block(h, lw: dict, cfg: dict, part: str):
    """One residual branch: ``h + part(norm(h))``."""
    if part == "experts":
        return h + experts(rms_norm(h, lw["norm2"], cfg["rms_norm_eps"]), lw,
                           cfg)
    return h + attention(rms_norm(h, lw["norm1"], cfg["rms_norm_eps"]), lw,
                         cfg, part)


def embed(params: dict, tokens):
    return params["item_emb"][jnp.asarray(tokens)].astype(F32)


def logits(params: dict, h, cfg: dict):
    return mm(rms_norm(h, params["norm_f"], cfg["rms_norm_eps"]),
              params["head"].T)


def forward(params: dict, tokens, cfg: dict, last_only: bool = False):
    """One session ``[T]`` of token ids (no padding) → logits ``[T, V]``
    (``[V]`` of the last position with ``last_only``)."""
    h = embed(params, tokens)
    for part, lw in zip(parts(cfg), params["layers"]):
        h = sub_block(h, lw, cfg, part)
    return logits(params, h[-1] if last_only else h, cfg)
