"""Quantized full-catalog retrieval scoring — a Pallas TPU kernel.

The recommendation serving hot path scores a user batch against the whole
item catalog: ``scores[B, N] = (q[B, D] @ items[N, D]ᵀ) * scale + bias + mask``
then top-k. At large N the item table dominates HBM traffic, so the catalog
is stored **int8 row-quantized** (4× smaller than fp32) and dequantization is
fused into the matmul inside VMEM: each grid step streams one item block
HBM→VMEM, upcasts to bf16, hits the MXU against the (resident) query block,
and applies scale/bias/mask on the VPU — the [B, N] score matrix is the only
fp32 HBM write.

Fallback: the same math in plain jnp (CPU tests run the kernel in interpret
mode as the correctness oracle of the *kernel*, and the jnp path serves
non-TPU deployments).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ITEM_BLOCK = 512  # catalog rows per grid step (int8 [512, D] ≤ 128KB for D≤256)

#: Widest rank for which int8×int8 products summed over a row fit a float32
#: mantissa EXACTLY: every partial product is ≤ 127² = 16129, so a D-dim dot
#: is ≤ 127²·D < 2²⁴ for D ≤ 1040 — f32 BLAS over the int8-valued operands
#: therefore computes the int32 accumulation bit-exactly (every intermediate
#: sum is an integer below the mantissa limit, associativity-free). This is
#: what lets the CPU host path share the TPU kernel's int8×int8→int32
#: contract without an int8 GEMM in numpy.
INT8_EXACT_MAX_RANK = (1 << 24) // (127 * 127)


def quantize_rows(items: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 quantization: returns (int8 rows, fp32 scales)."""
    amax = np.abs(items).max(axis=1, keepdims=True)
    scale = (amax / 127.0 + 1e-12).astype(np.float32)
    q = np.clip(np.round(items / scale), -127, 127).astype(np.int8)
    return q, scale[:, 0]


def _quantize_rows_traced(rows: jax.Array) -> tuple[jax.Array, jax.Array]:
    """:func:`quantize_rows` inside a jitted program: the same symmetric
    per-row contract, ``(int8 rows, f32 scales [rows])``."""
    amax = jnp.abs(rows).max(axis=1, keepdims=True)
    scale = (amax / 127.0 + 1e-12).astype(jnp.float32)
    q = jnp.clip(jnp.round(rows / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


@jax.jit
def quantize_catalog_device(
    item_emb: jax.Array, item_bias: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Device-side :func:`quantize_rows` + :func:`pad_catalog` in one jitted
    program — the deploy path for device-resident towers never round-trips
    the catalog through host numpy. Returns ``(items_q, scales, bias, mask)``
    padded to the :data:`ITEM_BLOCK` multiple (padding masked with -inf)."""
    n, _ = item_emb.shape
    q, scale = _quantize_rows_traced(item_emb)
    pad = (-n) % ITEM_BLOCK
    return (
        jnp.pad(q, ((0, pad), (0, 0))),
        jnp.pad(scale, (0, pad)),
        jnp.pad(item_bias.astype(jnp.float32), (0, pad)),
        jnp.pad(jnp.zeros(n, jnp.float32), (0, pad),
                constant_values=-jnp.inf),
    )


def _score_kernel(q_ref, items_ref, scale_ref, bias_ref, mask_ref, out_ref):
    q = q_ref[:].astype(jnp.bfloat16)                    # [B, D] resident
    block = items_ref[:].astype(jnp.bfloat16)            # [NB, D] int8→bf16
    scores = jax.lax.dot_general(
        q, block, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [B, NB] on the MXU
    scores = scores * scale_ref[:] + bias_ref[:] + mask_ref[:]
    out_ref[:] = scores


def _score_kernel_rowmask(q_ref, items_ref, scale_ref, bias_ref, mask_ref,
                          rowmask_ref, out_ref):
    """The rule-filtered variant: a per-row [B, NB] mask block streams in
    alongside the catalog block — each query in the batch carries its own
    business-rule filter (whitelist/blacklist/category/seen) while the
    shared [NB] mask keeps covering catalog padding."""
    q = q_ref[:].astype(jnp.bfloat16)
    block = items_ref[:].astype(jnp.bfloat16)
    scores = jax.lax.dot_general(
        q, block, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scores = scores * scale_ref[:] + bias_ref[:] + mask_ref[:] + rowmask_ref[:]
    out_ref[:] = scores


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_catalog_quantized(q, items_q, scales, bias, mask, row_mask=None, *,
                            interpret=False):
    """q [B, D] fp32; items_q [N, D] int8; scales/bias/mask [N] fp32;
    optional row_mask [B, N] fp32 (per-query -inf filters) → [B, N]."""
    b, d = q.shape
    n = items_q.shape[0]
    if n % ITEM_BLOCK:
        raise ValueError(f"catalog rows ({n}) must be padded to {ITEM_BLOCK}")
    if row_mask is not None and row_mask.shape != (b, n):
        raise ValueError(
            f"row_mask shape {row_mask.shape} != (batch, catalog) {(b, n)}")
    grid = (n // ITEM_BLOCK,)
    row = lambda j: (j, 0)
    col = lambda j: (0, j)
    in_specs = [
        pl.BlockSpec((b, d), lambda j: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((ITEM_BLOCK, d), row, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, ITEM_BLOCK), col, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, ITEM_BLOCK), col, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, ITEM_BLOCK), col, memory_space=pltpu.VMEM),
    ]
    args = [q, items_q, scales.reshape(1, n), bias.reshape(1, n),
            mask.reshape(1, n)]
    kernel = _score_kernel
    if row_mask is not None:
        in_specs.append(
            pl.BlockSpec((b, ITEM_BLOCK), col, memory_space=pltpu.VMEM))
        args.append(row_mask)
        kernel = _score_kernel_rowmask
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, ITEM_BLOCK), col,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(*args)


def score_catalog_reference(q, items_q, scales, bias, mask, row_mask=None):
    """Same math in plain jnp (the non-TPU serving path + test oracle)."""
    deq = items_q.astype(jnp.bfloat16)
    scores = jax.lax.dot_general(
        q.astype(jnp.bfloat16), deq, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scores = scores * scales[None, :] + bias[None, :] + mask[None, :]
    if row_mask is not None:
        scores = scores + row_mask
    return scores


def int8_matmul_exact(a_q: np.ndarray, b_q: np.ndarray) -> np.ndarray:
    """Exact ``a_q [M, D] int8 @ b_q [N, D] int8 ᵀ → [M, N]`` accumulation on
    host, returned as f32 holding exact integer values.

    For D ≤ :data:`INT8_EXACT_MAX_RANK` the f32 BLAS GEMM over the upcast
    operands IS the int32 result (see the constant's docstring) — and being
    exact integers, the result is identical no matter how BLAS blocks the
    reduction, so batched (GEMM) and per-query (GEMV) reranks score
    bit-identically. Wider ranks fall back to f64 (exact to 2⁵³)."""
    d = a_q.shape[1]
    acc_dtype = np.float32 if d <= INT8_EXACT_MAX_RANK else np.float64
    out = a_q.astype(acc_dtype) @ b_q.astype(acc_dtype).T
    return out.astype(np.float32, copy=False)


# -- int8 coarse stage (centroid scoring) ------------------------------------
#
# The IVF coarse stage scores each query against the bias-augmented centroid
# table (serving/ann.py). With the catalog already int8 row-quantized, the
# centroid embeddings quantize the same way (quantize_rows per-row scales);
# the mean-member-bias column stays fp32 and is added AFTER the one rescale,
# so bias precision never rides an int8 scale. The kernel runs int8×int8 on
# the MXU with an int32 accumulator — the true quantized-retrieval contract —
# and the host/reference paths reproduce it exactly via int8_matmul_exact.


def _coarse_kernel(q_ref, cent_ref, qs_ref, cs_ref, cb_ref, out_ref):
    q = q_ref[:]                                         # [B, D] int8 resident
    block = cent_ref[:]                                  # [CB, D] int8
    acc = jax.lax.dot_general(
        q, block, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                                    # [B, CB] int32 MXU
    scores = acc.astype(jnp.float32) * (qs_ref[:] * cs_ref[:]) + cb_ref[:]
    out_ref[:] = scores


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_centroids_quantized(q_q, q_scales, cent_q, cent_scales, cent_bias,
                              *, interpret=False):
    """q_q [B, D] int8; q_scales [B] f32; cent_q [C, D] int8;
    cent_scales/cent_bias [C] f32 → [B, C] f32 coarse scores.

    ``C`` must be padded to the :data:`ITEM_BLOCK` multiple
    (:func:`pad_centroids` — padding carries -inf bias so padded centroids
    are never probed)."""
    b, d = q_q.shape
    c = cent_q.shape[0]
    if c % ITEM_BLOCK:
        raise ValueError(
            f"centroid rows ({c}) must be padded to {ITEM_BLOCK}")
    grid = (c // ITEM_BLOCK,)
    col = lambda j: (0, j)
    # scope and kernel name are what a device trace shows (the custom call
    # is named by the innermost of them: keep the executable's own)
    with jax.named_scope("score"):
        return pl.pallas_call(
            _coarse_kernel,
            name="score_centroids_quantized",
            grid=grid,
            in_specs=[
                pl.BlockSpec((b, d), lambda j: (0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((ITEM_BLOCK, d), lambda j: (j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((b, 1), lambda j: (0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, ITEM_BLOCK), col, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, ITEM_BLOCK), col, memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((b, ITEM_BLOCK), col,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
            interpret=interpret,
        )(q_q, cent_q, q_scales.reshape(b, 1), cent_scales.reshape(1, c),
          cent_bias.reshape(1, c))


def score_centroids_reference(q_q, q_scales, cent_q, cent_scales, cent_bias):
    """Same int8×int8→int32 math in plain jnp (non-TPU path + test oracle)."""
    acc = jax.lax.dot_general(
        q_q, cent_q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc.astype(jnp.float32)
            * (q_scales[:, None] * cent_scales[None, :])
            + cent_bias[None, :])


def pad_centroids(cent_q: np.ndarray, cent_scales: np.ndarray,
                  cent_bias: np.ndarray, block: int = ITEM_BLOCK):
    """Pad the quantized centroid table to the kernel block multiple.
    Padded rows carry zero embeddings/scales and **-inf bias**, so they can
    never win a probe slot."""
    c = cent_q.shape[0]
    pad = (-c) % block
    if not pad:
        return cent_q, cent_scales, cent_bias
    return (
        np.concatenate([cent_q, np.zeros((pad, cent_q.shape[1]), np.int8)]),
        np.concatenate([cent_scales, np.zeros(pad, np.float32)]),
        np.concatenate([cent_bias, np.full(pad, -np.inf, np.float32)]),
    )


# -- two-stage retrieval as one device leg -----------------------------------
#
# serving/ann.IVFIndex.search_device: the queries are gathered and quantized
# on the device (quantize_user_rows), the coarse kernel above scores them
# against the centroids, and two_stage_rerank does everything the host
# routine (IVFIndex.search) does after it — the three run back to back with
# their intermediates left on the device and ONE device_get at the end. The
# coarse kernel stays its own executable: a device trace finds it by name.


@jax.jit
def quantize_user_rows(uidx, ue_tab, ub_tab):
    """Rows ``uidx`` of the resident user tower as int8 queries:
    ``(q_q [B, D] int8, q_scales [B] f32, user_bias [B] f32)`` — the
    :func:`quantize_rows` contract, so both stages share one quantization
    as they do on the host."""
    with jax.named_scope("gather"):
        rows = ue_tab[uidx].astype(jnp.float32)
        ubias = ub_tab[uidx].astype(jnp.float32)
    with jax.named_scope("quantize"):
        q_q, q_scales = _quantize_rows_traced(rows)
    return q_q, q_scales, ubias


def _rerank_kernel(probe_ref, sizes_ref, q_ref, qs_ref, ub_ref, emb_ref,
                   scale_ref, bias_ref, mask_ref, out_ref):
    """One probed partition of one query: its ``[L, D]`` int8 member block
    (picked by the prefetched ``probe``) against the query's int8 row, into
    the query's row of the probe slot's ``[B, L]`` output block."""
    j, i = pl.program_id(0), pl.program_id(1)
    size = sizes_ref[probe_ref[i, j]]
    acc = jax.lax.dot_general(
        q_ref[:], emb_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )[0:1, :]                                            # [1, L] int32 MXU
    scores = (acc.astype(jnp.float32) * (qs_ref[:] * scale_ref[:])
              + bias_ref[:])
    scores = scores + ub_ref[:] + mask_ref[:]
    member = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    out_ref[pl.ds(i, 1), :] = jnp.where(member < size, scores, -jnp.inf)


#: Sublanes the rerank kernel's query block is repeated to (an int8 MXU
#: operand of one row is no tile; the coarse kernel's least bucket is 8 too)
_QUERY_ROWS = 8


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "interpret"))
def two_stage_rerank(coarse, q_q, q_scales, ubias, mean, sizes, emb_p,
                     scales_p, bias_p, ids_p, mask_p, row_mask=None, *,
                     nprobe, k, interpret=False):
    """Second stage of two-stage retrieval over a partition-padded member
    table: ``coarse [B, C]`` f32 centroid scores → top-``nprobe`` partitions
    → their members' int8×int8→int32 scores with one f32 rescale → masks →
    top-``k``. Returns ``(ids [B, k] int32, scores [B, k] f32, counts [B]
    int32)``; ``counts`` is the candidates the probe gathered per row.

    The member tables hold partition ``p`` in rows ``[p, :sizes[p]]`` of a
    fixed length ``L`` (≥ the largest partition): ``emb_p [P, L, D]`` int8;
    ``scales_p``, ``bias_p``, ``mask_p`` f32 and ``ids_p`` int32
    ``[P, 1, L]``. The kernel's grid is (probe slot, query): each step has
    its partition's block brought in by the pipeline (block index = the
    probed partition id, prefetched to SMEM), so a probed partition is one
    contiguous read and nothing is gathered row by row; rows past a
    partition's length come out -inf. ``mask_p`` is the additive 0/-inf
    ``exclude`` mask in member order, ``row_mask [B, n_items]`` the
    per-query one in catalog order, gathered at the candidates' ids.
    Arithmetic and mask order are ``IVFIndex._int8_partition_scores`` /
    ``IVFIndex.search``'s."""
    b, d = q_q.shape
    length = emb_p.shape[1]
    with jax.named_scope("probe_select"):
        _, probe = jax.lax.top_k(coarse, nprobe)             # [B, nprobe]
        # centroid padding (-inf bias) is never probed while nprobe ≤ P
        probe = jnp.minimum(probe, emb_p.shape[0] - 1).astype(jnp.int32)
        counts = sizes[probe].sum(axis=1)
    part = lambda j, i, probe, sizes: (probe[i, j], 0, 0)
    query = lambda j, i, probe, sizes: (i, 0, 0)
    aux = pl.BlockSpec((None, 1, length), part, memory_space=pltpu.VMEM)
    one = pl.BlockSpec((None, 1, 1), query, memory_space=pltpu.VMEM)
    with jax.named_scope("rerank"):
        scores = pl.pallas_call(
            _rerank_kernel,
            name="rerank_members_quantized",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(nprobe, b),
                in_specs=[
                    pl.BlockSpec((None, _QUERY_ROWS, d), query,
                                 memory_space=pltpu.VMEM),
                    one, one,
                    pl.BlockSpec((None, length, d), part,
                                 memory_space=pltpu.VMEM),
                    aux, aux, aux,
                ],
                # a slot's block stays put while the queries fill its rows
                out_specs=pl.BlockSpec(
                    (b, length), lambda j, i, probe, sizes: (0, j),
                    memory_space=pltpu.VMEM),
            ),
            out_shape=jax.ShapeDtypeStruct((b, nprobe * length),
                                           jnp.float32),
            interpret=interpret,
        )(probe, sizes,
          jnp.broadcast_to(q_q[:, None, :], (b, _QUERY_ROWS, d)),
          q_scales.reshape(b, 1, 1), (ubias + mean).reshape(b, 1, 1),
          emb_p, scales_p, bias_p, mask_p)
        if row_mask is not None:
            with jax.named_scope("gather"):
                ids = ids_p[probe].reshape(b, nprobe * length)
            scores = scores + jnp.take_along_axis(row_mask, ids, axis=1)
    with jax.named_scope("topk"):
        top_scores, top = jax.lax.top_k(scores, k)
        part_of = jnp.take_along_axis(probe, top // length, axis=1)
        top_ids = ids_p[part_of, 0, top % length]
    return top_ids, top_scores, counts


def pad_catalog(items_q: np.ndarray, *vectors: np.ndarray,
                block: int = ITEM_BLOCK):
    """Pad catalog rows to the block multiple; padded mask rows get -inf."""
    n = items_q.shape[0]
    n_pad = ((n + block - 1) // block) * block
    if n_pad == n:
        return (items_q, *vectors)
    pad = n_pad - n
    out = [np.concatenate([items_q, np.zeros((pad, items_q.shape[1]), items_q.dtype)])]
    for i, v in enumerate(vectors):
        fill = -np.inf if i == len(vectors) - 1 else 0.0  # last vector = mask
        out.append(np.concatenate([v, np.full(pad, fill, v.dtype)]))
    return tuple(out)


# -- the index build ---------------------------------------------------------
#
# serving/ann.build_ivf_fused: Lloyd's k-means over a sample of the fused
# item rows ``[n, D+1]`` (embedding + bias column), then every row's
# partition, then the member-order tables. The host keeps what needs its
# generator (which rows are sampled, which seed a centroid, which replace a
# dead one), the loop over iterations and the argsort; each program's shapes
# are (rows, sample, partitions, D) alone, so a catalog's first build
# compiles them and no later one does.

#: Rows and centroids ONE product of the assignment scores: the score block
#: a pass holds is ``[ASSIGN_ROWS, CENTROID_BLOCK]`` float32 (16 MB) at any
#: catalog, and a catalog's first build compiles in seconds. Over the whole
#: of a catalog the TPU compiler takes 12-26 s for one float32 product at
#: full precision (476,002 x 690, compiled for a v5e without the chip: 23 s;
#: in these blocks under 3 s at every served shape).
ASSIGN_ROWS = 16_384
CENTROID_BLOCK = 256


def _nearest_centroid(x: jax.Array, cent: jax.Array,
                      half: jax.Array) -> jax.Array:
    """The euclidean-nearest centroid of each row: ``argmax(x·c - |c|²/2)``,
    in float32 at full precision (the MXU's default rounds the operands to
    bfloat16, which moves near-tie rows to another partition). ``cent
    [blocks, CENTROID_BLOCK, D+1]`` and ``half [blocks, CENTROID_BLOCK]``
    (``|c|²/2``, +inf where a block is padded) go a block at a time; of
    equal scores the first centroid wins, within a block and across them,
    as ``np.argmax`` over the whole row."""
    x = x.astype(jnp.float32)

    def block(cent_half):
        cent, half = cent_half
        scores = jnp.dot(x, cent.T,
                         precision=jax.lax.Precision.HIGHEST) - half
        return scores.max(axis=1), jnp.argmax(scores, axis=1)

    best, arg = jax.lax.map(block, (cent, half))           # [blocks, n]
    first = jnp.argmax(best, axis=0)
    within = jnp.take_along_axis(arg, first[None, :], axis=0)[0]
    return (first * CENTROID_BLOCK + within).astype(jnp.int32)


@jax.jit
def ivf_sample(rows, sel, init):
    """The training sample ``rows[sel]`` as float32 and its rows ``init`` as
    the first centroids: ``(train [S, D+1], cent [C, D+1])``."""
    with jax.named_scope("gather"):
        train = rows[sel].astype(jnp.float32)
        return train, train[init]


@functools.partial(jax.jit, static_argnames=("n",))
def ivf_assign(rows, cent, *, n):
    """The partition ``[n] int32`` of each of the first ``n`` rows: the
    assignment of a Lloyd iteration over the sample, and the catalog's.
    Row blocks of equal length, :data:`ASSIGN_ROWS` at most; the last one
    starts early where the rows do not divide and only its tail is kept,
    so nothing is padded and no copy of the table is made."""
    blocks = -(-n // ASSIGN_ROWS)
    length = -(-n // blocks)
    pad = -cent.shape[0] % CENTROID_BLOCK
    with jax.named_scope("assign"):
        half = jnp.pad(0.5 * jnp.einsum("cd,cd->c", cent, cent), (0, pad),
                       constant_values=jnp.inf).reshape(-1, CENTROID_BLOCK)
        cent = jnp.pad(cent, ((0, pad), (0, 0))).reshape(
            -1, CENTROID_BLOCK, cent.shape[1])
        parts = jax.lax.map(
            lambda lo: _nearest_centroid(
                jax.lax.dynamic_slice_in_dim(rows, lo, length), cent, half),
            jnp.minimum(jnp.arange(blocks) * length, n - length))
        tail = n - (blocks - 1) * length
        return jnp.concatenate(
            [parts[:-1].reshape(-1), parts[-1, length - tail:]])


@functools.partial(jax.jit, static_argnames=("c",))
def ivf_update(train, assign, *, c):
    """The update of a Lloyd iteration: ``(centroids [c, D+1], members [c]
    f32)``. A centroid is its members' mean, bias column included; one with
    no member comes back as zeros beside a zero count, for the host to
    re-seed. (A program of its own: assignment and update in one executable
    compile in 18 s where the two take 3.)"""
    with jax.named_scope("update"):
        sums = jax.ops.segment_sum(train, assign, num_segments=c)
        counts = jax.ops.segment_sum(
            jnp.ones(train.shape[0], jnp.float32), assign, num_segments=c)
        return sums / jnp.maximum(counts, 1.0)[:, None], counts


@functools.partial(jax.jit, static_argnames=("quantize",))
def ivf_layout(rows, order, *, quantize):
    """The member-order rerank tables: rows ``order`` of the fused table as
    ``(int8 embeddings, f32 scales, f32 bias)`` under the
    :func:`quantize_rows` contract, or ``(f32 embeddings, None, bias)``."""
    with jax.named_scope("gather"):
        # whole rows, then the split: a gather of the 128 embedding columns
        # out of the 129 (``rows[order, :-1]``) took 2-3 us a row on the
        # chip where this one takes 25 ns
        members = rows[order].astype(jnp.float32)
        emb, bias = members[:, :-1], members[:, -1]
    if not quantize:
        return emb, None, bias
    with jax.named_scope("quantize"):
        q, scales = _quantize_rows_traced(emb)
    return q, scales, bias
