"""The latent-attention / routed-expert block of the sequence transformer
(``TransformerConfig(attention_kind="mla")``; equations:
models/reference/mla_moe.py, which this module is held to).

- RMSNorm, low-rank query and key/value projections with a decoupled rotary
  part: what a token leaves behind for later queries is its **latent**
  ``[kv_lora_rank + qk_rope_head_dim]`` row (normalised ``ckv`` ‖ rotated
  ``kr``), 320 values where full keys and values would be 8192;
- two forms of the same attention over a context of latent rows: the
  **up-projected** one rebuilds keys and values (long blocks), the
  **absorbed** one carries the query into the latent space and the weighted
  sum back out of it (short blocks against a long context);
- sigmoid-scored top-k routing over ``n_routed_experts`` with a
  selection-only bias and weights normalised over all k picks; gated-SiLU
  experts as one grouped matmul a matrix over the ``experts_held`` experts
  this chip holds, token-pick pairs sorted by expert, no capacity and no
  dropped token; picks that fall on an expert held elsewhere add nothing
  here. The grouped matmul is ops/grouped_matmul.py wherever the backend
  runs the package's kernels (one form on a TPU, at every served width) and
  ``jax.lax.ragged_dot`` where it runs none (``expert_form``: the code's own
  choice, the same sum either way). One shared expert beside them.

Weights live in ``weight_dtype`` (bfloat16 when served, float32 when ``fit``
trains a small instance); products accumulate in float32; norms, router and
softmax are float32.

Named scopes inside every executable, for the device trace: ``mla_proj``,
``mla_attn``, ``moe_router``, ``moe_experts``, ``moe_shared``, ``head_topk``.

The layer is composed from the config (``layer_apply``): attention kind x
router scoring x experts. ``attention_kind="gqa_sparse"`` takes its attention
half from models/sparse_gqa.py (grouped-query heads behind a learned sparse
index, a softmax router, no shared expert); the norms, the router, the
grouped expert matmul, the head and the serving steps below are this
module's for both kinds.

A config with a ``layer_pattern`` (``attention_kind="gqa"``) makes each layer
ONE thing behind one norm (``layer_kinds``): a state-space mixer (``"S"``,
models/state_space.py), dense grouped-query attention (``"A"``,
models/sparse_gqa.py's plain part; ``"W"``: the same over a window of the
last keys), this module's router and experts alone (``"E"``,
``expert_layer``), a gated short convolution (``"C"``) or a dense gated
feed-forward part (``"D"``, both models/short_conv.py). The experts'
activation is the config's: gated SiLU (three matrices) or squared ReLU (two).
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from incubator_predictionio_tpu.models.reference.mla_moe import (
    rope_amplitude,
    softmax_scale,
    yarn_inv_freq,
)
from incubator_predictionio_tpu.parallel.mesh import kernel_backend

F32 = jnp.float32
NEG = -1e30          # finite: a row with no visible key stays finite
Q_CHUNK = 512        # queries a chunk in the up-projected form
ATTENTION_SCOPES = ("mla_proj", "mla_attn")
MOE_SCOPES = ("moe_router", "moe_experts", "moe_shared", "head_topk")
SCOPES = ATTENTION_SCOPES + MOE_SCOPES
DEFAULT_FORM = "up"
#: per-layer device counters: held experts' routed picks, then picks that
#: fell on absent experts, then held experts that got at least one pick
#: (summed over dispatches)
N_EXTRA_COUNTERS = 2


def published(cfg) -> dict:
    """``TransformerConfig`` → the reference's dict, under the published
    config's key names."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
        "n_routed_experts": cfg.n_routed_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "n_shared_experts": cfg.n_shared_experts,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "experts_held": cfg.experts_held or cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
        "rope_parameters": dict(cfg.rope_parameters),
    }


def cache_width(cfg) -> int:
    """Values a token's row takes in the serving cache: the latent row
    padded to whole 128-lane tiles. (A row of 320 makes the TPU compiler lay
    the cache out column-major, and every layer call then copies all of it
    to scatter into it and copies it back: measured, PERF.md PR 26.)"""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def row_layout(cfg) -> dict:
    """Row kinds a token leaves in the serving cache, and their widths."""
    return {"latent": cache_width(cfg)}


@dataclasses.dataclass(frozen=True)
class ServeShapes:
    """What a block's serving ladder is made of: block lengths (the first is
    the short, batched one; a block longer than the last is cut into pieces
    of it), the batch sizes of short blocks (``batch_to_max``: the server's
    ``max_batch`` tops the list), the contexts a short block may take, and
    the attention forms."""
    path: str             # the status page's name for the serve path
    blocks: tuple
    batches: tuple
    batch_to_max: bool
    short_contexts: tuple
    short_form: str
    long_form: str
    whole_context: bool   # a long block may take a context of its own length
    # the widest batch whose short blocks take a context under the full one
    # (None: every batch does)
    context_batch: Optional[int] = None

    def contexts(self, batch: int) -> tuple:
        """The contexts a short dispatch of ``batch`` sessions may take."""
        if self.context_batch is None or batch <= self.context_batch:
            return self.short_contexts
        return self.short_contexts[-1:]

    def long_contexts(self, block: int) -> tuple:
        full = self.short_contexts[-1]
        if self.whole_context:
            return (block,) if block == full else (block, full)
        return tuple(c for c in self.short_contexts if c >= block)


BLOCK_LADDER = (16, 128, 512, 1024, 1536, 2048, 3072)
BATCH_LADDER = (1, 4, 16, 64)
#: batches up to this take a context bucket: a wider one nearly always holds
#: a long session, and every (batch, context) pair is a program to compile
CONTEXT_BATCH = 4


def serve_shapes(cfg) -> ServeShapes:
    """The latent block's ladder: short blocks batch and attend in the
    absorbed form over the smallest of a quarter, a half and the whole of
    ``max_len`` (whole pages, no shorter than the block) that holds the
    longest session of the dispatch; a long block runs whole, one session a
    dispatch, in the up-projected form."""
    full, page = cfg.max_len, cfg.cache_page
    blocks = tuple(b for b in BLOCK_LADDER if b < full) + (full,)

    def paged(rows):
        return -(-rows // page) * page

    contexts = sorted({max(paged(full // part), paged(blocks[0]))
                       for part in (4, 2, 1)})
    return ServeShapes(
        "device-latent-cache", blocks, BATCH_LADDER, True, tuple(contexts),
        "absorbed", "up", True, CONTEXT_BATCH)


def count_dispatch(cfg, extents) -> None:
    """Host counters of one serving dispatch beyond the shared ``pio_seq_*``
    (``extents``: ``(offset, new tokens)`` a session): this block has none."""


def block_of(cfg):
    """The module that holds the config's attention half: ``attention``,
    ``attention_shapes``, ``row_layout``, ``cache_context``,
    ``block_context``, ``serve_shapes``, ``count_dispatch``,
    ``ATTENTION_SCOPES``, ``DEFAULT_FORM``; a pattern's block has its
    letters' ``mixer_shapes``, ``mixer_layer``, ``STEPS``, ``STATEFUL``,
    ``state_layout`` and ``pattern_scopes`` instead of an attention half."""
    if cfg.attention_kind == "gqa_sparse":
        from incubator_predictionio_tpu.models import sparse_gqa

        return sparse_gqa
    if cfg.layer_pattern:
        from incubator_predictionio_tpu.models import state_space

        return state_space
    return sys.modules[__name__]


#: the layer of a config without a pattern: an attention half, then experts
LAYER = "layer"


def layer_kinds(cfg) -> tuple:
    """What each layer is: the pattern's letters (``"S"`` a state-space
    mixer, ``"A"`` attention, ``"W"`` window attention, ``"E"`` experts,
    ``"C"`` a gated short convolution, ``"D"`` a dense feed-forward part), or
    ``LAYER`` for each."""
    return tuple(cfg.layer_pattern) or (LAYER,) * cfg.n_layers


def scopes(cfg) -> tuple:
    """Every named scope the config's executables carry."""
    block = block_of(cfg)
    own = block.pattern_scopes(cfg) if cfg.layer_pattern \
        else block.ATTENTION_SCOPES
    return own + MOE_SCOPES


def experts_held(cfg) -> int:
    return cfg.experts_held or cfg.n_routed_experts


def attention_shapes(cfg) -> dict:
    """The latent attention half's arrays of one layer."""
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "norm_q": ((cfg.q_lora_rank,), True),
        "norm_kv": ((cfg.kv_lora_rank,), True),
        "w_dq": ((d, cfg.q_lora_rank), False),
        "w_uq": ((cfg.q_lora_rank, h * (dn + dr)), False),
        "w_dkv": ((d, cfg.kv_lora_rank + dr), False),
        "w_ukv": ((cfg.kv_lora_rank, h * (dn + dv)), False),
        "w_o": ((h * dv, d), False),
    }


def expert_shapes(cfg) -> dict:
    """The expert half's arrays: its norm, the router (its selection bias
    only where the scoring is sigmoid), the routed experts held here and the
    shared ones where there are any; gated experts have a third matrix. The
    routed experts' matrices are STORED with their width padded to whole
    128-lane tiles, the padding zeros (``pad_stored``): the TPU compiler
    lays a ``[e, d, f]`` array whose ``f`` is no multiple of 128 out
    column-major, and the grouped matmul then copies all of it at every call
    (638 MB a layer at 64 x 2688 x 1856; PERF.md PR 34); ``we2`` has the
    same number of zero rows, so the hidden activation is never cut to a
    width that is no whole number of tiles either (the zero columns of
    ``we1`` make zero activations, which meet zero rows). The width of the
    mathematics is ``moe_intermediate_size``. (A width under one tile is
    stored as it is: the toy sizes of the CPU tests, whose pinned programs
    stay what they were.)"""
    d = cfg.d_model
    f, e = cfg.moe_intermediate_size, experts_held(cfg)
    fs = cfg.shared_intermediate_size or f * cfg.n_shared_experts
    gated = cfg.expert_activation == "gated_silu"
    out = {"norm2": ((d,), True), "w_r": ((d, cfg.n_routed_experts), True)}
    if cfg.router_scoring == "sigmoid":
        out["b_r"] = ((cfg.n_routed_experts,), True)
    stored = f if f < LANES else -(-f // LANES) * LANES
    out["we1"] = ((e, d, stored), False)
    if gated:
        out["we3"] = out["we1"]
    out["we2"] = ((e, stored, d), False)
    if cfg.n_shared_experts:
        out["ws1"] = ((d, fs), False)
        if gated:
            out["ws3"] = ((d, fs), False)
        out["ws2"] = ((fs, d), False)
    return out


def layer_shapes(cfg, kind: str = LAYER) -> dict:
    """One layer's arrays: ``{name: (shape, float32-always?)}``. ``LAYER``:
    the two norms, the attention half of the config's kind and the expert
    half; a pattern's layer: one norm and its own part."""
    norm = {"norm1": ((cfg.d_model,), True)}
    if kind == "E":
        return expert_shapes(cfg)
    if kind != LAYER:
        return {**norm, **block_of(cfg).mixer_shapes(cfg, kind)}
    experts = expert_shapes(cfg)
    return {**norm, "norm2": experts.pop("norm2"),
            **block_of(cfg).attention_shapes(cfg), **experts}


def pad_stored(array, shape):
    """``array`` with zeros up to the stored ``shape`` (``expert_shapes``)."""
    return jnp.pad(array, [(0, n - m) for n, m in zip(shape, array.shape)])


def init_params(key, cfg) -> dict:
    """Trainable initial parameters (``fit``): norms one, router bias zero,
    matrices normal with fan-in scaling, embeddings and head at 0.02."""
    wdt = jnp.dtype(cfg.weight_dtype)
    keys = iter(jax.random.split(key, 2 + 16 * cfg.n_layers))

    def normal(shape, scale, dtype=wdt):
        return (jax.random.normal(next(keys), shape, F32) * scale).astype(dtype)

    layers = []
    special = getattr(block_of(cfg), "INIT", {})
    for kind in layer_kinds(cfg):
        lw = {}
        for name, (shape, f32) in layer_shapes(cfg, kind).items():
            if name in special:
                lw[name] = special[name](next(keys), shape)
            elif name.startswith("norm"):
                lw[name] = jnp.ones(shape, F32)
            elif name == "b_r":
                lw[name] = jnp.zeros(shape, F32)
            else:
                lw[name] = normal(shape, shape[-2] ** -0.5,
                                  F32 if f32 else wdt)
            f = cfg.moe_intermediate_size  # the stored padding is zeros
            if name in ("we1", "we3"):
                lw[name] = pad_stored(lw[name][..., :f], shape)
            elif name == "we2":
                lw[name] = pad_stored(lw[name][:, :f], shape)
        layers.append(lw)
    params = {"item_emb": normal((cfg.vocab_size, cfg.d_model), 0.02),
              "norm_f": jnp.ones((cfg.d_model,), F32), "layers": layers}
    if not cfg.tie_head:
        params["head"] = normal((cfg.vocab_size, cfg.d_model), 0.02)
    return params


def head_matrix(params: dict):
    return params.get("head", params["item_emb"])


# -- pieces ------------------------------------------------------------------------

def _precision(dtype):
    # float32 weights are the small trained / tested instance: exact products
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _mm(x, w):
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=F32,
                      precision=_precision(w.dtype))


def _einsum(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=F32, precision=_precision(dtype))


def rms_norm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotate(x, pos, cfg):
    """RoPE on the last axis of ``x`` ``[B, T, ..., dr]`` at ``pos``
    ``[B, T]``: pairs ``(2i, 2i+1)``, yarn frequencies."""
    rope = dict(cfg.rope_parameters)
    inv_freq = jnp.asarray(yarn_inv_freq(rope, cfg.qk_rope_head_dim))
    amp = rope_amplitude(rope)
    ang = pos.astype(F32)[..., None] * inv_freq
    shape = pos.shape + (1,) * (x.ndim - 3) + (inv_freq.shape[0],)
    cos, sin = (jnp.cos(ang) * amp).reshape(shape), \
        (jnp.sin(ang) * amp).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def project(x, lw, cfg, pos):
    """``x [B, T, d]`` → ``(q_nope [B, T, H, dn], q_rope [B, T, H, dr],
    latent [B, T, kvr + dr])``: queries carry the softmax scale and the
    long-context query factor; the latent row is what the cache keeps."""
    b, t, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kvr, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    rope = dict(cfg.rope_parameters)
    cq = rms_norm(_mm(x, lw["w_dq"]), lw["norm_q"], eps)
    q = _mm(cq, lw["w_uq"]).reshape(b, t, h, dn + dr)
    beta = float(rope.get("llama_4_scaling_beta", 0.0))
    orig = float(rope["original_max_position_embeddings"])
    factor = softmax_scale(published(cfg)) * (
        1.0 + beta * jnp.log1p(jnp.floor(pos.astype(F32) / orig)))
    q = q * factor[..., None, None]
    kv = _mm(x, lw["w_dkv"])
    ckv = rms_norm(kv[..., :kvr], lw["norm_kv"], eps)
    kr = _rotate(kv[..., kvr:], pos, cfg)
    wdt = lw["w_dkv"].dtype
    latent = jnp.concatenate([ckv, kr], -1).astype(wdt)
    return q[..., :dn], _rotate(q[..., dn:], pos, cfg), latent


def _visible(q_index, key_valid, tc):
    """``[B, 1, T, Tc]``: key j is seen by the query at absolute index i when
    ``j <= i`` and the key is a real token of the session."""
    causal = jnp.arange(tc)[None, None, :] <= q_index[:, :, None]
    return (causal & key_valid[:, None, :])[:, None]


def attend_up(q_nope, q_rope, ctx, q_index, key_valid, lw, cfg):
    """Up-projected form: keys and values of the whole context are rebuilt
    from its latent rows; queries go in chunks of ``Q_CHUNK`` so the score
    matrix of a long block is never whole."""
    b, t, h, dn = q_nope.shape
    tc, kvr, dv = ctx.shape[1], cfg.kv_lora_rank, cfg.v_head_dim
    wdt = lw["w_ukv"].dtype
    kvu = _mm(ctx[..., :kvr], lw["w_ukv"]).reshape(b, tc, h, dn + dv)
    k_nope, v = kvu[..., :dn], kvu[..., dn:]
    k_rope = ctx[..., kvr:kvr + cfg.qk_rope_head_dim]

    def chunk(args):
        qn, qr, qi = args
        s = (_einsum("bthd,bshd->bhts", qn, k_nope, wdt)
             + _einsum("bthr,bsr->bhts", qr, k_rope, wdt))
        p = jax.nn.softmax(
            jnp.where(_visible(qi, key_valid, tc), s, NEG), axis=-1)
        return _einsum("bhts,bshd->bthd", p, v, wdt)

    if t <= Q_CHUNK:
        out = chunk((q_nope, q_rope, q_index))
    else:
        n = t // Q_CHUNK

        def split(a):
            return jnp.moveaxis(
                a.reshape((b, n, Q_CHUNK) + a.shape[2:]), 1, 0)

        out = jax.lax.map(
            chunk, (split(q_nope), split(q_rope), split(q_index)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, t, h, dv)
    return out.reshape(b, t, h * dv)


def attend_absorbed(q_nope, q_rope, ctx, q_index, key_valid, lw, cfg):
    """Absorbed form: ``q_nope W_uk`` meets the latent rows directly and the
    values are up-projected after the weighted sum; no key or value of the
    context is ever rebuilt."""
    b, t, h, dn = q_nope.shape
    tc, kvr, dv = ctx.shape[1], cfg.kv_lora_rank, cfg.v_head_dim
    wdt = lw["w_ukv"].dtype
    w = lw["w_ukv"].reshape(kvr, h, dn + dv)
    q_lat = _einsum("bthd,khd->bthk", q_nope, w[..., :dn], wdt)
    q_all = jnp.concatenate([q_lat, q_rope], -1)
    # (a served context's rows are padded to whole tiles: zeros meet zeros)
    q_all = jnp.pad(q_all, [(0, 0)] * 3 + [(0, ctx.shape[-1] - q_all.shape[-1])])
    s = _einsum("bthk,bsk->bhts", q_all, ctx, wdt)
    p = jax.nn.softmax(
        jnp.where(_visible(q_index, key_valid, tc), s, NEG), axis=-1)
    o_lat = _einsum("bhts,bsk->bthk", p, ctx[..., :kvr], wdt)
    return _einsum("bthk,khd->bthd", o_lat, w[..., dn:], wdt).reshape(
        b, t, h * dv)


ATTEND = {"up": attend_up, "absorbed": attend_absorbed}


def moe_router(x, lw, cfg):
    """``x [N, d]`` → ``(idx [N, k], w [N, k])``. The scoring is the
    config's: sigmoid with a selection-only bias, or softmax over all the
    experts with none; the weights are the picks' scores normalised over the
    k picks."""
    logits = jnp.matmul(
        x.astype(F32), lw["w_r"], precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "softmax":
        g = ranked = jax.nn.softmax(logits, -1)
    else:
        g = jax.nn.sigmoid(logits)
        ranked = g + lw["b_r"]
    _, idx = jax.lax.top_k(ranked, cfg.experts_per_token)
    gi = jnp.take_along_axis(g, idx, -1)
    return idx, gi / gi.sum(-1, keepdims=True) * cfg.routed_scaling_factor


def _expert_hidden(x, lw, names, dot):
    """``silu(w1 x) * w3 x``, or ``relu(w1 x)^2`` where the layer has no
    third matrix (``expert_activation="relu2"``), at the stored width (the
    columns past the width of the mathematics are zeros, and stay zeros)."""
    w1, w3 = (lw.get(n) for n in names[:2])
    a = dot(x, w1)
    return jnp.square(jax.nn.relu(a)) if w3 is None \
        else jax.nn.silu(a) * dot(x, w3)


def _expert(x, lw, names, dot):
    """``w2 (silu(w1 x) * w3 x)`` or ``w2 relu(w1 x)^2``."""
    w2 = lw[names[2]]
    return dot(_expert_hidden(x, lw, names, dot).astype(w2.dtype), w2)


#: the lanes of a tile: a stored width under it is one of the toy sizes
LANES = 128


def expert_form(stored) -> str:
    """Which grouped matmul the routed experts run, from what the code can
    see: ``"kernel"`` (ops/grouped_matmul.py, whose tiles are divisors of
    the stored ``[held, d, f]`` widths themselves) wherever the backend runs
    the package's kernels and ``f`` is at least one lane tile (under that a
    matrix is a single partial tile: the toy sizes whose programs the CPU
    tests pin); ``"ragged"`` (``jax.lax.ragged_dot``) everywhere else: a
    process with no such backend, the kernel's reference and its backward."""
    return "kernel" if stored[2] >= LANES and kernel_backend() else "ragged"


def moe_experts(x, idx, w, token_valid, lw, cfg):
    """The routed experts held here: token-pick pairs sorted by expert, one
    grouped matmul per expert matrix (``expert_form`` says which), unsorted,
    weighted, summed per token; pairs of padding tokens or of experts held
    elsewhere sort behind the last group, where the grouped matmul does no
    work. Returns ``(y [N, d], counters [held + 2])``."""
    n, k = idx.shape
    held = experts_held(cfg)
    wdt = lw["we1"].dtype
    local = idx - cfg.expert_offset
    here = (local >= 0) & (local < held) & token_valid[:, None]
    key = jnp.where(here, local, held).reshape(n * k)
    order = jnp.argsort(key)   # stable
    group_sizes = jnp.zeros(held + 1, jnp.int32).at[key].add(1)[:held]
    xs = x.astype(wdt)[order // k]
    form = expert_form(lw["we1"].shape)

    def dot(a, m):
        if form == "kernel":
            # (imported here: a process whose experts keep ``ragged_dot``
            # loads no Pallas and stays, module for module, what it was)
            from incubator_predictionio_tpu.ops.grouped_matmul import (
                grouped_matmul,
            )

            return grouped_matmul(a, m, group_sizes,
                                  interpret=kernel_backend() == "interpret")
        return jax.lax.ragged_dot(a, m, group_sizes,
                                  preferred_element_type=F32,
                                  precision=_precision(wdt))

    out = _expert(xs, lw, ("we1", "we3", "we2"), dot)
    weight = jnp.where(here, w, 0.0).reshape(n * k)[order]
    out = jnp.where((key[order] < held)[:, None], out, 0.0) * weight[:, None]
    y = jnp.zeros((n * k, x.shape[-1]), F32).at[order].set(
        out, unique_indices=True)
    picks = token_valid.sum() * k
    counters = jnp.concatenate([
        group_sizes,
        jnp.stack([picks - group_sizes.sum(),
                   (group_sizes > 0).sum()]).astype(jnp.int32)])
    return y.reshape(n, k, -1).sum(1), counters


def moe_shared(x, lw):
    return _expert(x.astype(lw["ws1"].dtype), lw, ("ws1", "ws3", "ws2"), _mm)


def attention(x, h, lw, cfg, pos, q_index, context, form):
    """The latent attention half on the normed ``x``: ``context(latent)``
    takes the block's new latent rows and returns ``(ctx [B, Tc, kvr + dr],
    key_valid [B, Tc], state)``. Returns ``(h + attention, state)``."""
    with jax.named_scope("mla_proj"):
        q_nope, q_rope, latent = project(x, lw, cfg, pos)
    with jax.named_scope("mla_attn"):
        ctx, key_valid, state = context(latent)  # cache write and gather
        a = ATTEND[form](q_nope, q_rope, ctx, q_index, key_valid, lw, cfg)
    with jax.named_scope("mla_proj"):
        return h + _mm(a, lw["w_o"]), state


def expert_layer(lw, h, cfg, token_valid):
    """The expert half on ``h [B, T, d]``: norm, router, the routed experts
    held here and the shared ones. Returns ``(h, counters)``."""
    b, t, d = h.shape
    x = rms_norm(h, lw["norm2"], cfg.rms_norm_eps).reshape(b * t, d)
    with jax.named_scope("moe_router"):
        idx, w = moe_router(x, lw, cfg)
    with jax.named_scope("moe_experts"):
        y, counters = moe_experts(x, idx, w, token_valid.reshape(b * t), lw,
                                  cfg)
    if "ws1" in lw:
        with jax.named_scope("moe_shared"):
            y = y + moe_shared(x, lw)
    return h + y.reshape(b, t, d), counters


def layer_apply(lw, h, cfg, pos, q_index, token_valid, context, form=None):
    """One block on ``h [B, T, d]`` (float32): the attention half of the
    config's kind, then router and experts. ``context(rows)`` takes the
    block's new cache rows and returns ``(ctx, key_valid [B, Tc], state)``:
    the block itself while training, the session cache after the write while
    serving. Returns ``(h, counters, state)``."""
    block = block_of(cfg)
    x = rms_norm(h, lw["norm1"], cfg.rms_norm_eps)
    h, state = block.attention(
        x, h, lw, cfg, pos, q_index, context, form or block.DEFAULT_FORM)
    h, counters = expert_layer(lw, h, cfg, token_valid)
    return h, counters, state


def block_context(valid, wdt):
    """``context`` when the block is its own context (``fit``, ``forward``)."""
    return lambda latent: (latent.astype(wdt), valid, None)


def cache_context(cache, geometry, pages, cfg, form):
    """``context`` for a serving step: the block's latent rows are written
    to the sessions' pages, then the whole cached context is read back."""
    _, _, write, read, key_valid = geometry

    def context(latent):
        rows = cache["latent"]
        pad = rows.shape[-1] - latent.shape[-1]
        new = rows.at[write].set(jnp.pad(latent, [(0, 0), (0, 0), (0, pad)]))
        return new[read], key_valid, {"latent": new}

    return context


def forward(params, tokens, positions, cfg):
    """Training forward over left-padded rows ``tokens [B, L]`` (0 =
    padding; ``positions`` count a row's real tokens from 0) → final-normed
    hidden ``[B, L, d]``. Padding is no key and is routed nowhere."""
    wdt = params["item_emb"].dtype
    h = params["item_emb"][tokens].astype(F32)
    valid = tokens != 0
    q_index = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    block = block_of(cfg)
    context = block.block_context(valid, wdt)
    for kind, lw in zip(layer_kinds(cfg), params["layers"]):
        if kind == LAYER:
            h, _, _ = layer_apply(lw, h, cfg, positions, q_index, valid,
                                  context)
        elif kind == "E":
            h, _ = expert_layer(lw, h, cfg, valid)
        else:
            h, _ = block.mixer_layer(kind, lw, h, cfg, q_index, valid,
                                     context, positions)
    return rms_norm(h, params["norm_f"], cfg.rms_norm_eps)


def xent_sum(h, head, targets, weights):
    """``sum_t weights[t] * xent(h[t] head^T, targets[t])`` with float32
    logits."""
    lp = jax.nn.log_softmax(_mm(h, head.T), -1)
    return -(weights * jnp.take_along_axis(lp, targets[:, None], -1)[:, 0]
             ).sum()


def real_positions(tokens: np.ndarray) -> np.ndarray:
    """Positions of left-padded rows: the first real token is position 0."""
    n_pad = (tokens == 0).sum(1, keepdims=True)
    return np.maximum(np.arange(tokens.shape[1])[None, :] - n_pad, 0).astype(
        np.int32)


# -- serving steps: serving/latent_cache.py compiles each as an executable of
# its own a long bucket (called a layer at a time: one layer compiled whatever
# the depth) and all of them into one a short bucket (``turn_step`` there) -------

def _block_geometry(pages, offsets, counts, t, page):
    """From a dispatch's page table: the absolute index of every block
    token, which of them are real, where each is written and the flat rows
    of the whole context. Padding tokens write to page 0, which no session
    owns."""
    b, pc = pages.shape
    step = jnp.arange(t)[None, :]
    q_index = offsets[:, None] + step
    token_valid = step < counts[:, None]
    slot = jnp.take_along_axis(
        pages, jnp.clip(q_index // page, 0, pc - 1), 1) * page \
        + q_index % page
    write = jnp.where(token_valid, slot, step % page)
    read = (pages[:, :, None] * page + jnp.arange(page)).reshape(b, pc * page)
    key_valid = jnp.arange(pc * page)[None, :] < (offsets + counts)[:, None]
    return q_index, token_valid, write, read, key_valid


def slot_rows(kept, slots):
    """``kept[slots]`` of a per-session ``[slots, values]`` array as one
    slice a session: a gather over rows of 2 MB made the TPU compiler pass
    over the WHOLE array (539 MB at 257 slots; PERF.md PR 34)."""
    return jnp.concatenate([
        jax.lax.dynamic_slice_in_dim(kept, slots[i], 1)
        for i in range(slots.shape[0])])


def put_slot_rows(kept, slots, rows):
    for i in range(slots.shape[0]):
        kept = jax.lax.dynamic_update_slice_in_dim(
            kept, rows[i:i + 1].astype(kept.dtype), slots[i], 0)
    return kept


def embed_step(item_emb, tok_cache, tokens, pages, offsets, counts, *, page):
    """Embeds a block and notes its tokens beside the latent cache (the
    history mask of ``head_step`` reads them back)."""
    _, _, write, _, _ = _block_geometry(
        pages, offsets, counts, tokens.shape[1], page)
    return item_emb[tokens].astype(F32), tok_cache.at[write].set(tokens)


def layer_step(lw, cache, counters, h, pages, offsets, counts, *, cfg, form):
    """One layer of "extend a batch of sessions by a block each": the
    block's new rows are written to the sessions' pages (``cache`` is the
    layer's ``{row kind: array}``), then every query attends over its
    session's cached context as the block's kind and ``form`` read it."""
    geometry = _block_geometry(pages, offsets, counts, h.shape[1],
                               cfg.cache_page)
    q_index, token_valid = geometry[:2]
    context = block_of(cfg).cache_context(cache, geometry, pages, cfg, form)
    h, layer_counters, cache = layer_apply(
        lw, h, cfg, q_index, q_index, token_valid, context, form)
    return h, cache, counters + layer_counters


def expert_step(lw, cache, counters, h, slots, offsets, counts, *, cfg, form):
    """An ``"E"`` layer of a pattern: no context, nothing cached."""
    token_valid = jnp.arange(h.shape[1])[None, :] < counts[:, None]
    h, layer_counters = expert_layer(lw, h, cfg, token_valid)
    return h, cache, counters + layer_counters


def step_of(kind: str, cfg):
    """The serving step of one layer kind: ``(lw, cache, counters, h, own,
    offsets, counts, *, cfg, form) -> (h, cache, counters)``; ``cache`` is
    what the kind keeps and ``own`` where the batch's sessions keep it
    (per-token rows by ``pages [B, P]``; a mixer's per-session state by
    ``slots [B]``; the experts' nothing) and ``counters`` the experts' (``()``
    for a layer without any)."""
    if kind == LAYER:
        return layer_step
    return expert_step if kind == "E" else block_of(cfg).STEPS[kind]


def head_step(norm_f, head, tok_cache, h, pages, offsets, counts, *, cfg, k):
    """Logits of each session's last real position over this chip's slice
    of the vocabulary, padding and the session's own items masked, top-k."""
    with jax.named_scope("head_topk"):
        b, t, _ = h.shape
        _, _, _, read, key_valid = _block_geometry(
            pages, offsets, counts, t, cfg.cache_page)
        last = jnp.clip(counts - 1, 0, t - 1)
        x = rms_norm(h[jnp.arange(b), last], norm_f, cfg.rms_norm_eps)
        logits = _mm(x, head.T)
        # (the padding item has a column of its own: a context full of real
        # keys has no invalid key to stand for it)
        seen = jnp.concatenate([
            jnp.where(key_valid, tok_cache[read], 0),
            jnp.zeros((b, 1), tok_cache.dtype)], axis=1)
        logits = logits.at[jnp.arange(b)[:, None], seen].set(-jnp.inf)
        return jax.lax.top_k(logits, k)
