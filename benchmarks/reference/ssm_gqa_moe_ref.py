"""The plain reference of the state-space / grouped-query / routed-expert
stack (``attention_kind="gqa"`` with a ``layer_pattern``), for the comparison
that decides ``correct`` and for the program's own CPU tests: imported from
nowhere in the program. Straightforward float32 ``jax.numpy`` at ``highest``
matmul precision, one session at a time, the whole session every time: no
cache, no state carried between calls, no chunks, no kernels, no sorting of
tokens by expert. The recurrence is a ``lax.scan`` over tokens, the
convolution four shifted adds, attention one masked score matrix.

``cfg`` is a plain dict under the published config's own key names
(``hidden_size``, ``mamba_num_heads``, ``ssm_state_size``, ``n_groups``,
``hybrid_override_pattern`` ...) plus the chip's share: ``experts_held``
experts from ``expert_offset``. ``params`` is ``{"item_emb", "head",
"norm_f", "layers": [one dict a layer]}`` under the program's names; arrays
of any float dtype are up-cast here.

    x = E[tokens]                                   (x_t in R^hidden)
    per layer, by the pattern's letter (all norms RMSNorm, eps
    layer_norm_epsilon; no biases but the convolution's): x <- x + f(norm(x))
     M  [z, xBC, dt] = W_in h     z in R^inner, xBC in R^(inner + 2 G N), dt in R^heads
        xBC_t <- silu(sum_{j=0..K-1} w_j * xBC_{t-K+1+j} + b)   (zeros before token 0)
        x_t (heads x P), B_t, C_t (G x N) = split;  head i reads group floor(i / (heads / G))
        D_t = softplus(dt_t + dt_bias);  A = -exp(a_log)
        S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t;  y_t = S_t C_t + d_skip x_t
        f = W_out (RMSNorm over each group's inner / G of (y * silu(z)), times a gain)
     *  q = W_q h (H x dh), k = W_k h, v = W_v h (KV x dh); query head i reads
        key/value head floor(i / (H / KV)); causal softmax(q . k / sqrt(dh)) v;
        f = W_o a; no positional encoding
     E  s = sigmoid(W_r h); picks = top-k of s + b_r; weights s_e / sum_picks s
        x routed_scaling_factor; f = sum_{e in picks, held here} weight_e
        W2_e relu(W1_e h)^2 + W2_s relu(W1_s h)^2 (the shared expert)
    logits = norm(x) H^T                            (the untied head)

Departures from the equations as ISSUE 34 writes them, none of which changes
a number: (a) the experts are visited in a ``fori_loop``, every expert
computing every token, instead of 64 unrolled copies of the same lines;
(b) the query rows of an attention layer go in blocks of ``ROWS`` so that the
``[heads, rows, T]`` scores of 24 sessions fit beside each other: each row's
scores and softmax are its own, whole.
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


def pattern(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"]


def mixer(x, lw: dict, cfg: dict, count=None, stored=None):
    """``x [T, hidden]`` (normed) → ``f [T, hidden]``; with ``count``, ``(f,
    S [heads, P, N])``: the state after the first ``count`` tokens (a later
    position steps by 0, which leaves the state as it is). ``stored`` (a
    dtype, for the comparison of the state alone): the inputs that the
    configuration's ``precision`` holds in that dtype, the projection's and
    the convolution's, are rounded to it; the arithmetic stays float32."""
    t = x.shape[0]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner = heads * p

    def held(v):
        # (not ``astype`` there and back: the TPU compiler keeps the excess
        # precision of such a pair where it can, and did for the
        # convolution's inputs: every sound state read 2.6e-3 off)
        if stored is None:
            return v
        info = jnp.finfo(stored)
        return jax.lax.reduce_precision(v, info.nexp, info.nmant)

    proj = mm(held(x), lw["w_in"])
    z, xbc, dt = (proj[:, :inner], held(proj[:, inner:-heads]),
                  proj[:, -heads:])
    ext = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(lw["conv_b"].astype(F32) + sum(
        lw["conv_w"][j].astype(F32) * ext[j:j + t] for j in range(k)))
    xs = xbc[:, :inner].reshape(t, heads, p)
    bm = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n),
                    heads // g, 1)
    cm = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n), heads // g, 1)
    step = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))
    if count is not None:
        step = jnp.where(jnp.arange(t)[:, None] < count, step, 0.0)
    a = -jnp.exp(lw["a_log"].astype(F32))

    def token(s, args):
        x_t, b_t, c_t, d_t = args
        s = jnp.exp(d_t * a)[:, None, None] * s \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HI)

    state, y = jax.lax.scan(
        token, jnp.zeros((heads, p, n), F32), (xs, bm, cm, step))
    y = (y + lw["d_skip"].astype(F32)[:, None] * xs).reshape(t, inner)
    y = (y * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(
        jnp.mean(y * y, -1, keepdims=True) + cfg["layer_norm_epsilon"])
    out = mm(y.reshape(t, inner) * lw["norm_g"].astype(F32), lw["w_out"])
    return out if count is None else (out, state)


def attention(x, lw: dict, cfg: dict):
    t = x.shape[0]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = mm(x, lw["w_q"]).reshape(t, kv, h // kv, dh)
    k = mm(x, lw["w_k"]).reshape(t, kv, dh)
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


def route(x, lw: dict, cfg: dict):
    """``(idx [T, k], w [T, k])``: sigmoid scores, top-k of score + bias,
    the picks' scores normalised over the picks, times the scaling factor."""
    s = jax.nn.sigmoid(mm(x, lw["w_r"]))
    _, idx = jax.lax.top_k(s + lw["b_r"].astype(F32),
                           cfg["num_experts_per_tok"])
    si = jnp.take_along_axis(s, idx, -1)
    return idx, si / si.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def relu2(x, w1, w2):
    return mm(jnp.square(jax.nn.relu(mm(x, w1))), w2)


def experts(x, lw: dict, cfg: dict):
    """The routed experts held here plus the shared one; a pick that fell on
    an expert held elsewhere adds nothing."""
    idx, w = route(x, lw, cfg)

    def one(e, y):
        mine = jnp.where(idx == cfg["expert_offset"] + e, w, 0.0).sum(-1)
        return y + mine[:, None] * relu2(x, lw["we1"][e], lw["we2"][e])

    return jax.lax.fori_loop(
        0, cfg["experts_held"], one, relu2(x, lw["ws1"], lw["ws2"]))


PARTS = {"M": mixer, "*": attention, "E": experts}


def layer(h, lw: dict, cfg: dict, kind: str):
    norm = lw["norm2"] if kind == "E" else lw["norm1"]
    return h + PARTS[kind](
        rms_norm(h, norm, cfg["layer_norm_epsilon"]), lw, cfg)


def first_state(params: dict, lw: dict, tokens, count, cfg: dict, stored):
    """The recurrent state the FIRST layer (a mixer: its input is the
    embedding) holds after ``count`` of ``tokens``: what a served session's
    state is compared with. Deeper layers' inputs differ between the program
    and this file by what the layers before them rounded."""
    assert pattern(cfg)[0] == "M"
    x = rms_norm(embed(params, tokens), lw["norm1"],
                 cfg["layer_norm_epsilon"])
    return mixer(x, lw, cfg, count, stored)[1]


def embed(params: dict, tokens):
    return params["item_emb"][jnp.asarray(tokens)].astype(F32)


def logits(params: dict, h, cfg: dict):
    return mm(rms_norm(h, params["norm_f"], cfg["layer_norm_epsilon"]),
              params["head"].T)


def forward(params: dict, tokens, cfg: dict, last_only: bool = False):
    """One session ``[T]`` of token ids (no padding) → logits ``[T, V]``
    (``[V]`` of the last position with ``last_only``)."""
    h = embed(params, tokens)
    for kind, lw in zip(pattern(cfg), params["layers"]):
        h = layer(h, lw, cfg, kind)
    return logits(params, h[-1] if last_only else h, cfg)
