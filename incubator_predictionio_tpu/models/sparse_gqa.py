"""Grouped-query attention behind a learned sparse index: the attention half
of ``TransformerConfig(attention_kind="gqa_sparse")``. The expert half, the
norms, the head and the serving steps are models/latent_moe.py's, shared with
the latent block (``latent_moe.layer_apply`` composes the two halves from the
config).

- ``n_heads`` query heads of ``head_dim`` read ``n_kv_heads`` key/value heads
  (query head i reads head ``i // (n_heads // n_kv_heads)``); RMSNorm over
  each head's ``head_dim`` of q and k (one gain each), then rope in half-split
  pairs ``(i, i + head_dim / 2)`` at ``rope_theta``;
- the **indexer**: ``index_n_heads`` small query heads, ONE key head of
  ``index_head_dim`` and a per-head weight, all from the normed hidden state,
  rope on the whole key; the index score of query t for key s is
  ``sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])``, and a query attends to
  its ``index_topk`` best-scored visible keys only (all of them while it
  sees no more than that);
- what a token leaves behind is TWO rows: its keys and values (``2 *
  n_kv_heads * head_dim`` values) and its index key (``index_head_dim``
  values, padded to a whole 128-lane tile).

Three forms of the same attention, by where the context lives:

- ``full``: the block is its own context (``fit``, the tests): index scores
  and attention scores as whole matrices under the selection mask;
- ``select`` (serving, a turn of a few tokens a session): index scores over
  the session's index rows, ``lax.top_k``, the selected key/value rows
  gathered through the page table, attention over the gathered rows. The
  dense key/value rows of the context are never read;
- ``chunk`` (serving, a long block cut into pieces): key tiles of
  ``index_kv_tile`` rows are read through the page table up to the last
  visible one; the index scores of the chunk fill one ``[T, context]``
  array, the ``index_topk``-th largest of each row is found by bisection on
  the scores' bit patterns (32 counting passes, exact), and attention runs
  tile by tile with a running softmax under the mask that threshold gives.

Ties: among equal scores the lower index is kept, in every form
(``lax.top_k``'s rule; the mask forms count the keys that equal the
threshold in order and keep as many as the top-k has room for).

Named scopes, for the device trace: ``gqa_proj`` (projections, head norms,
rope, output projection), ``idx_score`` (index rows written and read, index
scores), ``idx_select`` (top-k or threshold), ``sparse_attn`` (key/value rows
written and read, attention).

The plain part, for the ``"A"`` layers of a layer pattern
(``attention_kind="gqa"``, composed in models/state_space.py): the same
grouped heads and key/value rows with no index, no head norms and no
positional encoding, every query attending to all it sees (``dense_*``;
scopes ``gqa_proj``, ``gqa_attn``). With the config's ``rope_parameters`` of
``rope_type`` "yarn" the ``"A"`` layers turn their pairs by the scaled angles
and multiply cos and sin by the rule's amplitude (``rotary_rule``).

The window part, a pattern's ``"W"`` layers: the ``"A"`` letter's weights and
projections, plain rotary angles at ``rope_theta``, and query i sees keys j
with ``i - sliding_window < j <= i``. What a session keeps for such a layer
is a **ring** of its last ``sliding_window`` key/value rows (the row of
position p at ring row ``p % sliding_window``; ``window_step``), whatever its
length; a long block attends in a band (``attend_dense``). Scopes
``gqa_proj`` and ``win_attn`` (ring read and write, scores, sum).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from incubator_predictionio_tpu.models.latent_moe import (
    BATCH_LADDER,
    CONTEXT_BATCH,
    F32,
    NEG,
    Q_CHUNK,
    ServeShapes,
    _block_geometry,
    _einsum,
    _mm,
    rms_norm,
    rope_amplitude,
    yarn_inv_freq,
)
from incubator_predictionio_tpu.obs.metrics import REGISTRY

ATTENTION_SCOPES = ("gqa_proj", "idx_score", "idx_select", "sparse_attn")
DEFAULT_FORM = "full"
#: a long block is cut into pieces of this many key tiles: every piece reads
#: all the experts' weights, so a longer piece reads them fewer times a miss;
#: four tiles (2048 tokens at the published tile of 512) keep a piece's
#: [T, context] index-score array at 256 MB and its per-tile scores at 128 MB
PIECE_TILES = 4
SHORT_BLOCK = 16

_INDEX_SCORED = REGISTRY.counter(
    "pio_seq_index_rows_scored_total",
    "Query x context rows the sparse index scored (a layer; every layer "
    "scores the same)")
_SPARSE_SELECTED = REGISTRY.counter(
    "pio_seq_sparse_rows_selected_total",
    "Query x rows attended after the sparse index's selection (a layer)")


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def row_layout(cfg) -> dict:
    """Row kinds of the serving cache and their widths, each padded to whole
    128-lane tiles (an array whose minor dimension is no multiple of 128 is
    laid out column-major by the TPU compiler and copied whole by every
    scatter into it; PERF.md PR 26)."""
    return {"kv": _lanes(2 * cfg.n_kv_heads * cfg.head_dim),
            "idx": _lanes(cfg.index_head_dim)}


def attention_shapes(cfg) -> dict:
    """The attention half's arrays of one layer: ``{name: (shape, float32?)}``."""
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ih, di = cfg.index_n_heads, cfg.index_head_dim
    return {
        "norm_qh": ((dh,), True), "norm_kh": ((dh,), True),
        "w_q": ((d, h * dh), False), "w_k": ((d, kv * dh), False),
        "w_v": ((d, kv * dh), False), "w_o": ((h * dh, d), False),
        "wi_q": ((d, ih * di), False), "wi_k": ((d, di), False),
        "wi_w": ((d, ih), False),
    }


def published(cfg) -> dict:
    """``TransformerConfig`` → the reference's dict, under the published
    config's key names (benchmarks/reference/gqa_sparse_moe_ref.py)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "sa_config": {"indexer_num_heads": cfg.index_n_heads,
                      "indexer_head_dim": cfg.index_head_dim,
                      "indexer_num_kv_heads": 1, "topk": cfg.index_topk},
        "num_experts": cfg.n_routed_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "norm_topk_prob": True,
        "experts_held": cfg.experts_held or cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
    }


# -- pieces ------------------------------------------------------------------------

def rope(x, pos, theta: float, inv_freq=None, amplitude: float = 1.0):
    """Half-split rope on the last axis of ``x [B, T, ..., dim]`` at ``pos
    [B, T]``: the pair ``(i, i + dim / 2)`` turns by ``pos * theta ** (-2 i /
    dim)``, or by ``pos * inv_freq[i]`` where a rule gives its own
    frequencies; cos and sin times ``amplitude``."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        inv_freq = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    inv_freq = jnp.asarray(inv_freq, F32)
    ang = pos.astype(F32)[..., None] * inv_freq
    shape = pos.shape + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def project(x, lw, cfg, pos):
    """``x [B, T, d]`` (normed) → ``(q [B, T, H, dh]`` with the softmax scale,
    ``q_idx [B, T, J, di], w_idx [B, T, J], rows)``; ``rows`` is what the
    cache keeps: ``kv [B, T, 2 KV dh]`` (normed, rotated keys ‖ values) and
    ``idx [B, T, di]`` (the rotated index key), in the weights' dtype."""
    b, t, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ih, di, eps = cfg.index_n_heads, cfg.index_head_dim, cfg.rms_norm_eps
    wdt = lw["w_q"].dtype
    q = rms_norm(_mm(x, lw["w_q"]).reshape(b, t, h, dh), lw["norm_qh"], eps)
    k = rms_norm(_mm(x, lw["w_k"]).reshape(b, t, kv, dh), lw["norm_kh"], eps)
    q = rope(q, pos, cfg.rope_theta) * dh ** -0.5
    k = rope(k, pos, cfg.rope_theta).reshape(b, t, kv * dh)
    q_idx = rope(_mm(x, lw["wi_q"]).reshape(b, t, ih, di), pos, cfg.rope_theta)
    k_idx = rope(_mm(x, lw["wi_k"]), pos, cfg.rope_theta)
    rows = {"kv": jnp.concatenate([k, _mm(x, lw["w_v"])], -1).astype(wdt),
            "idx": k_idx.astype(wdt)}
    return q, q_idx, _mm(x, lw["wi_w"]), rows


def index_scores(q_idx, w_idx, k_idx, dtype):
    """``sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])``: ``[B, T, S]`` float32."""
    s = _einsum("btjd,bsd->btjs", q_idx, k_idx, dtype)
    return (jax.nn.relu(s) * w_idx[..., None]).sum(2)


def kth_largest(x, k: int):
    """The ``k``-th largest of each row of float32 ``x [..., S]`` (``k <=
    S``), exactly, by bisection on the values' bit patterns: 32 passes that
    each count the row's entries at or above a candidate."""
    bits = jax.lax.bitcast_convert_type(
        jax.lax.stop_gradient(x).astype(F32), jnp.uint32)
    top = jnp.uint32(2 ** 31)
    # float order → unsigned integer order: a negative float's bits are
    # flipped, a positive one's get the top bit
    key = jnp.where(bits >= top, ~bits, bits | top)

    def step(i, found):
        cand = found | (top >> i.astype(jnp.uint32))
        enough = (key >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, found)

    found = jax.lax.fori_loop(
        0, 32, step, jnp.zeros(x.shape[:-1], jnp.uint32))
    back = jnp.where(found >= top, found ^ top, ~found)
    return jax.lax.bitcast_convert_type(back, F32)


def cut(score, k: int):
    """``(least, room)`` of each row of ``score [..., S]``: its ``k``-th
    largest value, and how many of the entries EQUAL to it the top-k holds
    (the rest of its ``k`` places go to larger ones)."""
    least = kth_largest(score, k)
    return least, k - (score > least[..., None]).sum(-1)


def chosen_by(score, least, room, before):
    """The top-k as a mask over a run of keys ``score [..., n]``: larger than
    the threshold, or equal to it while there is room, lower index first
    (``before`` counts the equals in earlier runs). Returns the mask and the
    count of equals so far."""
    equal = score == least[..., None]
    rank = jnp.cumsum(equal, -1) - equal + before[..., None]
    return (score > least[..., None]) | (equal & (rank < room[..., None])), \
        before + equal.sum(-1)


def _visible(q_index, key_valid, start, n):
    """``[B, T, n]``: key ``start + j`` is seen by the query at absolute index
    i when it is no later and a real token of the session."""
    j = start + jnp.arange(n)
    return (j[None, None, :] <= q_index[:, :, None]) & key_valid[:, None, :]


def _split_kv(rows, cfg):
    """Cache rows ``[..., W]`` → keys, values ``[..., KV, dh]``."""
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    lead = rows.shape[:-1]
    return (rows[..., :kv * dh].reshape(lead + (kv, dh)),
            rows[..., kv * dh:2 * kv * dh].reshape(lead + (kv, dh)))


def _grouped(q, cfg):
    b, t, h, dh = q.shape
    return q.reshape(b, t, cfg.n_kv_heads, h // cfg.n_kv_heads, dh)


def attend_full(q, q_idx, w_idx, ctx, q_index, key_valid, cfg, wdt):
    """The block is its own context: whole matrices under the selection
    mask."""
    b, t, h, dh = q.shape
    tc = key_valid.shape[1]
    seen = _visible(q_index, key_valid, 0, tc)
    with jax.named_scope("idx_score"):
        score = jnp.where(seen, index_scores(
            q_idx, w_idx, ctx["idx"][..., :cfg.index_head_dim], wdt), NEG)
    with jax.named_scope("idx_select"):
        chosen = seen
        if tc > cfg.index_topk:
            least, room = cut(score, cfg.index_topk)
            chosen &= chosen_by(score, least, room, jnp.zeros_like(room))[0]
    with jax.named_scope("sparse_attn"):
        k, v = _split_kv(ctx["kv"], cfg)
        s = _einsum("btngd,bsnd->bngts", _grouped(q, cfg), k, wdt)
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, NEG), axis=-1)
        return _einsum("bngts,bsnd->btngd", p, v, wdt).reshape(b, t, h * dh)


def attend_select(q, q_idx, w_idx, ctx, q_index, key_valid, cfg, wdt):
    """A turn: top-k of the index scores over the session's index rows, then
    attention over the selected key/value rows alone."""
    b, t, h, dh = q.shape
    tc = key_valid.shape[1]
    with jax.named_scope("idx_score"):
        k_idx = ctx["idx"]()[..., :cfg.index_head_dim]
        score = jnp.where(_visible(q_index, key_valid, 0, tc),
                          index_scores(q_idx, w_idx, k_idx, wdt), NEG)
    with jax.named_scope("idx_select"):
        best, at = jax.lax.top_k(score, min(cfg.index_topk, tc))
        chosen = best > NEG / 2
    with jax.named_scope("sparse_attn"):
        k, v = _split_kv(ctx["kv_rows"](at), cfg)       # [B, T, K, KV, dh]
        s = _einsum("btngd,btknd->btngk", _grouped(q, cfg), k, wdt)
        p = jax.nn.softmax(
            jnp.where(chosen[:, :, None, None, :], s, NEG), axis=-1)
        return _einsum("btngk,btknd->btngd", p, v, wdt).reshape(b, t, h * dh)


def attend_chunk(q, q_idx, w_idx, ctx, q_index, key_valid, cfg, wdt):
    """A piece of a long block against the cached prefix plus itself, a tile
    of keys at a time and only as far as the last visible key."""
    b, t, h, dh = q.shape
    tc, tile = key_valid.shape[1], cfg.index_kv_tile
    n_tiles = (key_valid.sum(-1).max() + tile - 1) // tile

    def seen_tile(i):
        valid = jax.lax.dynamic_slice_in_dim(key_valid, i * tile, tile, 1)
        return _visible(q_index, valid, i * tile, tile)

    with jax.named_scope("idx_score"):
        def score_tile(i, score):
            k_idx = ctx["idx_tile"](i)[..., :cfg.index_head_dim]
            part = jnp.where(seen_tile(i), index_scores(
                q_idx, w_idx, k_idx, wdt), NEG)
            return jax.lax.dynamic_update_slice_in_dim(
                score, part, i * tile, 2)

        score = jax.lax.fori_loop(
            0, n_tiles, score_tile, jnp.full((b, t, tc), NEG, F32))
    with jax.named_scope("idx_select"):
        least, room = cut(score, min(cfg.index_topk, tc))
    with jax.named_scope("sparse_attn"):
        qg = _grouped(q, cfg)
        n, g = qg.shape[2:4]

        def attend_tile(i, carry):
            top, mass, acc, equals = carry
            k, v = _split_kv(ctx["kv_tile"](i), cfg)     # [B, tile, KV, dh]
            part = jax.lax.dynamic_slice_in_dim(score, i * tile, tile, 2)
            chosen, equals = chosen_by(part, least, room, equals)
            chosen &= seen_tile(i)
            s = jnp.where(chosen[:, None, None],
                          _einsum("btngd,bsnd->bngts", qg, k, wdt), NEG)
            new_top = jnp.maximum(top, s.max(-1))
            p = jnp.exp(s - new_top[..., None])
            keep = jnp.exp(top - new_top)
            acc = acc * keep[..., None] + _einsum(
                "bngts,bsnd->bngtd", p, v, wdt)
            return new_top, mass * keep + p.sum(-1), acc, equals

        _, mass, acc, _ = jax.lax.fori_loop(0, n_tiles, attend_tile, (
            jnp.full((b, n, g, t), NEG, F32), jnp.zeros((b, n, g, t), F32),
            jnp.zeros((b, n, g, t, dh), F32), jnp.zeros((b, t), jnp.int32)))
        out = acc / jnp.maximum(mass, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1).reshape(b, t, h * dh)


ATTEND = {"full": attend_full, "select": attend_select, "chunk": attend_chunk}


def attention(x, h, lw, cfg, pos, q_index, context, form):
    """The attention half of the layer on the normed ``x``; ``context(rows)``
    takes the block's new rows and returns ``(ctx, key_valid, state)`` (the
    forms above say what ``ctx`` holds). Returns ``(h + attention, state)``."""
    wdt = lw["w_q"].dtype
    with jax.named_scope("gqa_proj"):
        q, q_idx, w_idx, rows = project(x, lw, cfg, pos)
    ctx, key_valid, state = context(rows)
    a = ATTEND[form](q, q_idx, w_idx, ctx, q_index, key_valid, cfg, wdt)
    with jax.named_scope("gqa_proj"):
        return h + _mm(a, lw["w_o"]), state


# -- the serving cache's side -----------------------------------------------------------

def cache_context(cache, geometry, pages, cfg, form):
    """``context`` for a serving step: the block's rows are written to the
    sessions' pages, and ``ctx`` reads the cache back through the page table
    as the form needs it."""
    _, _, write, read, key_valid = geometry
    page, tile = cfg.cache_page, cfg.index_kv_tile
    b = pages.shape[0]

    def put(arr, new):
        pad = arr.shape[-1] - new.shape[-1]
        return arr.at[write].set(jnp.pad(new, [(0, 0), (0, 0), (0, pad)]))

    def tile_rows(i):
        own = jax.lax.dynamic_slice_in_dim(
            pages, i * (tile // page), tile // page, 1)
        return (own[:, :, None] * page + jnp.arange(page)).reshape(b, tile)

    def context(rows):
        with jax.named_scope("idx_score"):
            idx = put(cache["idx"], rows["idx"])
        with jax.named_scope("sparse_attn"):
            kv = put(cache["kv"], rows["kv"])

        def kv_rows(at):
            # each position's page by compare-and-sum over the page table (a
            # gather of 32k scalars took 0.26 ms a layer on the v5e)
            own = jnp.where(
                (at // page)[..., None] == jnp.arange(pages.shape[1]),
                pages[:, None, None, :], 0).sum(-1)
            return kv[own * page + at % page]

        ctx = {"idx": lambda: idx[read], "kv_rows": kv_rows,
               "idx_tile": lambda i: idx[tile_rows(i)],
               "kv_tile": lambda i: kv[tile_rows(i)]}
        return ctx, key_valid, {"kv": kv, "idx": idx}

    return context


def block_context(valid, wdt):
    """``context`` when the block is its own context (``fit``, ``forward``)."""
    return lambda rows: (
        {k: v.astype(wdt) for k, v in rows.items()}, valid, None)


# -- the plain part: dense grouped-query attention, an "A" layer of a pattern -----------------

def dense_shapes(cfg) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    norms = {"norm_qh": ((dh,), True), "norm_kh": ((dh,), True)} \
        if cfg.qk_norm else {}
    return {**norms,
            "w_q": ((d, h * dh), False), "w_k": ((d, kv * dh), False),
            "w_v": ((d, kv * dh), False), "w_o": ((h * dh, d), False)}


def dense_row_layout(cfg) -> dict:
    return {"kv": _lanes(2 * cfg.n_kv_heads * cfg.head_dim)}


#: the score elements a head of one chunk of queries may make: the chunk is
#: ``Q_CHUNK`` queries up to this context, then a half and a quarter of it (a
#: 16k context would make 1 GB of float32 scores over the 32 heads of 512
#: queries)
CHUNK_SCORES = Q_CHUNK * 4096


def attend_dense(q, rows, q_index, key_valid, cfg, wdt, window: int = 0):
    """``q [B, T, H, dh]`` (scaled) over the context's key/value ``rows [B,
    Tc, W]``: causal softmax over every visible key, queries in chunks.
    With ``window`` (a ``"W"`` layer) ``key_valid`` is each key's POSITION
    in its session (negative: no key) and a query sees the keys at ``i -
    window < position <= i``; a long block whose context is ``lead`` rows
    from before it and then itself attends in a band: a chunk of queries
    reads the ``lead`` keys before its first and its own, no others (``lead
    >= window - 1``: what lies further back is out of every query's sight)."""
    b, t, h, dh = q.shape
    tc = rows.shape[1]
    k, v = _split_kv(rows, cfg)
    qn = Q_CHUNK
    while qn * tc > CHUNK_SCORES and qn > Q_CHUNK // 4:
        qn //= 2
    band = tc - t + qn if window and tc > t > qn else 0

    def chunk(args):
        qc, qi, *at = args
        kc, vc, valid = (
            jax.lax.dynamic_slice_in_dim(a, at[0], band, 1)
            for a in (k, v, key_valid)) if band else (k, v, key_valid)
        s = _einsum("btngd,bsnd->bngts", _grouped(qc, cfg), kc, wdt)
        if window:
            near = valid[:, None, :]
            seen = (near >= 0) & (near <= qi[:, :, None]) \
                & (near > qi[:, :, None] - window)
        else:
            seen = _visible(qi, valid, 0, valid.shape[1])
        p = jax.nn.softmax(jnp.where(seen[:, None, None], s, NEG), axis=-1)
        return _einsum("bngts,bsnd->btngd", p, vc, wdt).reshape(
            b, qc.shape[1], h * dh)

    if t <= qn:
        return chunk((q, q_index))
    n = t // qn
    out = jax.lax.map(chunk, (
        jnp.moveaxis(q.reshape(b, n, qn, h, dh), 1, 0),
        jnp.moveaxis(q_index.reshape(b, n, qn), 1, 0),
        *((jnp.arange(n) * qn,) if band else ())))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h * dh)


def rotary_rule(cfg, kind: str) -> tuple:
    """``(inv_freq or None, amplitude)`` of a pattern's attention letter: an
    ``"A"`` layer takes the config's ``rope_parameters`` where their
    ``rope_type`` is "yarn" (the scaled frequencies of
    models/reference/mla_moe.py's ``yarn_inv_freq`` over the whole head, cos
    and sin times the printed ``attention_factor``, else the rule's own);
    a ``"W"`` layer, and an ``"A"`` layer without them, plain angles at
    ``rope_theta``."""
    scaled = dict(cfg.rope_parameters)
    if kind != "A" or scaled.get("rope_type", "yarn") != "yarn" \
            or "factor" not in scaled:
        return None, 1.0
    return yarn_inv_freq(scaled, cfg.head_dim), float(
        scaled.get("attention_factor") or rope_amplitude(scaled))


def dense_layer(lw, h, cfg, q_index, context, pos=None, kind: str = "A"):
    """``h + W_o attention(norm(h))``; ``context(rows)`` takes the block's
    new key/value rows and returns ``(rows of the context, key_valid,
    state)``. Returns ``(h, state)``. With the config's ``qk_norm`` each
    head's q and k are RMS-normed (a gain each), with ``attention_rope`` both
    are rotated at ``pos`` (default ``q_index``: a token's index in its
    session) by the ``kind``'s ``rotary_rule``; the rows kept are the normed,
    rotated keys and the values. ``kind="W"``: the context's ``key_valid``
    is its keys' positions and a query sees the last ``sliding_window`` of
    them (``attend_dense``), under the scope ``win_attn``."""
    b, t, _ = h.shape
    wdt = lw["w_q"].dtype
    windowed = kind == "W"
    with jax.named_scope("gqa_proj"):
        x = rms_norm(h, lw["norm1"], cfg.rms_norm_eps)
        q = _mm(x, lw["w_q"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lw["norm_qh"], cfg.rms_norm_eps)
        q = q * cfg.head_dim ** -0.5
        k = _mm(x, lw["w_k"])
        if cfg.qk_norm or cfg.attention_rope:
            k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
            if cfg.qk_norm:
                k = rms_norm(k, lw["norm_kh"], cfg.rms_norm_eps)
            if cfg.attention_rope:
                pos = q_index if pos is None else pos
                rule = rotary_rule(cfg, kind)
                q, k = (rope(a, pos, cfg.rope_theta, *rule) for a in (q, k))
            k = k.reshape(b, t, -1)
        rows = jnp.concatenate([k, _mm(x, lw["w_v"])], -1).astype(wdt)
    with jax.named_scope("win_attn" if windowed else "gqa_attn"):
        ctx, key_valid, state = context(rows)   # cache write and gather
        a = attend_dense(q, ctx, q_index, key_valid, cfg, wdt,
                         cfg.sliding_window if windowed else 0)
    with jax.named_scope("gqa_proj"):
        return h + _mm(a, lw["w_o"]), state


def dense_step(lw, cache, counters, h, pages, offsets, counts, *, cfg, form):
    """An ``"A"`` layer of "extend a batch of sessions by a block each": the
    block's key/value rows go to the sessions' pages, every query attends
    over its session's cached rows, read a PAGE at a time through the page
    table (a session's context is whole pages; read a row at a time, 16k
    rows of 2 KB came at 95 GB/s on the v5e and a turn's time followed its
    session's length: PERF.md PR 46)."""
    q_index, _, write, _, key_valid = _block_geometry(
        pages, offsets, counts, h.shape[1], cfg.cache_page)

    def context(rows):
        width = cache["kv"].shape[-1]
        kv = cache["kv"].at[write].set(
            jnp.pad(rows, [(0, 0), (0, 0), (0, width - rows.shape[-1])]))
        ctx = kv.reshape(-1, cfg.cache_page, width)[pages]
        return ctx.reshape(pages.shape[0], -1, width), key_valid, {"kv": kv}

    h, cache = dense_layer(lw, h, cfg, q_index, context)
    return h, cache, counters


def ring_layout(cfg) -> dict:
    """What a ``"W"`` layer keeps for a session, ``{name: ((rows, width),
    dtype)}``: its last ``sliding_window`` key/value rows."""
    return {"ring": ((cfg.sliding_window, dense_row_layout(cfg)["kv"]),
                     jnp.dtype(cfg.weight_dtype))}


def window_step(lw, cache, counters, h, slots, offsets, counts, *, cfg, form):
    """A ``"W"`` layer of "extend a batch of sessions by a block each". The
    layer's ``ring`` is ``[slots, R, W]``, ``R = sliding_window`` rows a
    session, the row of position p at ``[slot, p % R]`` (read and written
    here as the rows ``slot * R + p % R`` of one ``[slots x R, W]`` array,
    the way the paged rows are). A block reads
    the ``R`` rows before its offset in position order (those before
    position 0 are no keys: a block that starts at offset 0 sees nothing of
    what its slot held), attends over them and itself, and leaves its last
    ``R`` real rows in the ring. Padding writes land in slot 0, which
    belongs to nobody."""
    r = cfg.sliding_window
    ring = cache["ring"].reshape(-1, cache["ring"].shape[-1])
    step = jnp.arange(h.shape[1])[None, :]
    q_index = offsets[:, None] + step
    token_valid = step < counts[:, None]
    before = offsets[:, None] - r + jnp.arange(r)[None, :]
    base = slots[:, None] * r

    def context(rows):
        rows = jnp.pad(rows, [(0, 0), (0, 0),
                              (0, ring.shape[-1] - rows.shape[-1])])
        keep = token_valid & (step >= counts[:, None] - r)
        new = ring.at[jnp.where(keep, base + q_index % r, step % r)].set(rows)
        key_pos = jnp.concatenate([
            jnp.where(before >= 0, before, -1),
            jnp.where(token_valid, q_index, -1)], 1)
        return jnp.concatenate([ring[base + before % r], rows], 1), \
            key_pos, {"ring": new.reshape(cache["ring"].shape)}

    h, cache = dense_layer(lw, h, cfg, q_index, context, kind="W")
    return h, cache, counters


# -- the serving ladder, and what a dispatch did, from the equations ------------------------

def serve_shapes(cfg) -> ServeShapes:
    """Turns go ONE session a dispatch in the ``select`` form, over a context
    bucket (powers of two from twice ``index_topk``, below which the
    selection leaves little out, to ``max_len``) that holds the session:
    every query slot of a turn gathers its own ``index_topk`` key/value rows,
    so a batch of sessions costs the device what its sessions cost one by one
    (measured: a layer 2.0 ms at batch 1, 9.0-11.5 at 4, 20.6 at 8; PERF.md
    PR 30) and doubles the buckets to compile. Anything longer is cut into
    pieces of ``PIECE_TILES`` key tiles in the ``chunk`` form."""
    full, tile = cfg.max_len, cfg.index_kv_tile
    contexts, c = [], -(-2 * cfg.index_topk // tile) * tile
    while c < full:
        contexts.append(c)
        c *= 2
    piece = min(PIECE_TILES * tile, full)
    return ServeShapes(
        "device-kv-index-cache",
        tuple(b for b in (SHORT_BLOCK,) if b < piece) + (piece,), (1,), False,
        tuple(contexts) + (full,), "select", "chunk", False)


def window_serve_shapes(cfg) -> ServeShapes:
    """The ladder of a pattern with ``"W"`` layers: short blocks batch over
    the smallest context (the piece's length doubled up to ``max_len``) that
    holds the longest session's ``"A"`` rows, batches past ``CONTEXT_BATCH``
    over the whole length; anything longer is cut into pieces of
    ``PIECE_TILES x index_kv_tile`` tokens, each resuming from the rings and
    the pages the pieces before it left, one session a dispatch."""
    full = cfg.max_len
    piece = min(PIECE_TILES * cfg.index_kv_tile, full)
    contexts, c = [], piece
    while c < full:
        contexts.append(c)
        c *= 2
    return ServeShapes(
        "device-window-kv-cache",
        tuple(b for b in (SHORT_BLOCK,) if b < piece) + (piece,),
        BATCH_LADDER, True, tuple(contexts) + (full,), "step",
        "band", False, CONTEXT_BATCH)


def count_dispatch(cfg, extents) -> None:
    """Advances the index's host counters by one dispatch's work (a layer)."""
    done = [rows_scored_selected(offset, count, cfg.index_topk)
            for offset, count in extents]
    _INDEX_SCORED.inc(sum(d[0] for d in done))
    _SPARSE_SELECTED.inc(sum(d[1] for d in done))


def rows_scored_selected(offset: int, count: int, topk: int) -> tuple:
    """Extending a session by ``count`` tokens at ``offset``: the query at
    absolute index i scores ``i + 1`` rows and attends ``min(i + 1, topk)``."""
    first, last = offset + 1, offset + count
    scored = (first + last) * count // 2
    below = max(0, min(last, topk) - first + 1)      # queries that see <= topk
    selected = (first + first + below - 1) * below // 2 + (count - below) * topk
    return scored, selected
