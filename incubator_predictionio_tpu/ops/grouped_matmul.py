"""Grouped matmul over rows sorted by group — a Pallas TPU kernel.

``out[i] = lhs[i] @ rhs[g]`` for the rows ``i`` of group ``g``, groups laid
end to end in ``lhs`` by ``group_sizes``; rows past the last group come back
zero. This is ``jax.lax.ragged_dot``, which is the reference here and the
kernel's backward.

The routed experts call it (models/latent_moe.py) at every served width on
a backend that runs the package's kernels. XLA's own grouped kernel walks an
expert's matrix 128 lanes at a time unless both widths are multiples of 256,
and at 2688 x 1920 (21 and 15 lane tiles) that costs 7-14 x the time of
reading the matrices it touches (measured on the v5e, PERF.md PR 35); where
both are (2048 x 1792, 4096 x 2048, 2048 x 768) it still takes 1.7-3.1 x
this kernel's time from 256 to 4,096 sorted rows, 1.1-2.6 x at 12,288 and
16,384 (where the operations bind both) and 1.1-1.7 x on a lone turn's
(PERF.md PR 40). Here a tile is a divisor of the width itself (384, 640,
896 ... up to the whole matrix), chosen from ``(m, k, n)`` by ``tiles``:
each grid step streams one ``[tk, tn]`` piece of ONE group's matrix HBM→VMEM
and multiplies the row tile that holds the group's rows against it; a group
that has no row is never visited, and a row tile that several groups share
is visited once a group, each visit keeping only its own rows (the grid's
middle dimension is the number of such visits, known on the device only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: bytes one streamed piece of a group's matrix may take (two are in flight):
#: a whole ``[2688, 1920]`` bfloat16 matrix, one contiguous read a group
PIECE_BYTES = 12 << 20
#: ... and under a lone row tile (a turn's picks: a handful of groups, so
#: nothing hides the wait for the first piece) a third of that
LONE_TILE_PIECE_BYTES = 4 << 20


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """``jax.lax.ragged_dot`` with float32 accumulation; float32 operands
    multiply exactly (the small trained / tested instances). On a TPU its
    rows past the groups are not zeros (measured, PERF.md PR 35): compare
    the rows the groups hold."""
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
        if rhs.dtype == jnp.float32 else None)


def _divisor_tile(width: int, most: int) -> int:
    """The widest whole-lane-tile divisor of ``width`` that is at most
    ``most`` (``width`` itself where it has none: a single partial tile)."""
    if width % LANES:
        return width
    tiles = width // LANES
    best = max((t for t in range(1, tiles + 1)
                if tiles % t == 0 and t * LANES <= most), default=1)
    return best * LANES


def tiles(m: int, k: int, n: int, itemsize: int = 2) -> tuple:
    """``(tm, tk, tn)`` for ``[m, k] x [G, k, n]``, from the widths
    themselves: a row tile of 128 (the matrix unit's own; the whole of ``m``
    under that); the contraction whole where a ``[k, 3 lane tiles]`` piece
    fits the streamed budget; the output as wide as the budget then allows.
    Measured on the v5e at 2688 x 1920 and back (PERF.md PR 35): the whole
    matrix a piece is the fastest from 768 rows up (1.17 x the time of
    reading the touched experts once; pieces of a third 1.28 x, of a fifth
    1.39 x), a third under a lone turn's 96 rows with a few experts touched;
    row tiles of 64 to 256 read alike, 512 is 1.5 x slower (every visit
    multiplies the whole row tile). At the other three served widths
    (PERF.md PR 40) the same rule gives the whole ``[2048, 1792]`` and
    ``[2048, 768]`` matrix and a half of ``[4096, 2048]`` a piece: 1.15-1.45
    x the touched experts' bytes up to 2,048 rows, and under a lone row tile
    (halves of ``[2048, 1792]``, eighths of ``[4096, 2048]``) 1.2-1.45 x;
    the whole matrix under a lone tile and a row tile of 256 read the
    same."""
    tm = min(m, LANES)
    budget = LONE_TILE_PIECE_BYTES if m <= LANES else PIECE_BYTES
    tk = _divisor_tile(k, max(LANES, budget // (3 * LANES * itemsize)))
    tn = _divisor_tile(n, max(LANES, budget // (tk * itemsize)))
    return tm, tk, tn


def _visits(group_sizes, m: int, tm: int):
    """Which (group, row tile) pairs the grid visits, in group order:
    ``(offsets [G + 1], group of a visit, row tile of a visit, count)``; a
    group takes one visit for every row tile that holds one of its rows
    (``m`` is whole row tiles)."""
    n_groups, row_tiles = group_sizes.shape[0], m // tm
    most = row_tiles + n_groups - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(n_tiles)
    group = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), n_tiles,
                       total_repeat_length=most)
    tile = first[group] + jnp.arange(most, dtype=jnp.int32) \
        - (visit_ends - n_tiles)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), group,
            jnp.clip(tile, 0, row_tiles - 1).astype(jnp.int32),
            visit_ends[-1].astype(jnp.int32))


def _kernel(offsets, group, tile, lhs, rhs, out, acc, *, tm, tiles_k):
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _():
        # (this visit's rows only: the tile's other rows are another
        # group's, written by its own visit, or past the groups)
        g = group[visit]
        row = tile[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out[...] = jnp.where(mine, acc[...], out[...])


def _grouped_matmul(lhs, rhs, group_sizes, tiling, interpret):
    rows, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiling or tiles(rows, k, n, rhs.dtype.itemsize)
    if k % tk or n % tn:
        raise ValueError(f"tiles {(tk, tn)} do not divide {(k, n)}")
    m = -(-rows // tm) * tm
    lhs = jnp.pad(lhs, ((0, m - rows), (0, 0)))   # whole row tiles
    offsets, group, tile, count = _visits(group_sizes, m, tm)
    tiles_k = k // tk
    # two of each streamed block in flight, the accumulator, and room
    vmem = 2 * (tm * tk * lhs.dtype.itemsize + tk * tn * rhs.dtype.itemsize
                + tm * tn * 4) + tm * tn * 4
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, count, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n_i, v, k_i, o, g, t: (t[v], k_i)),
                pl.BlockSpec((None, tk, tn),
                             lambda n_i, v, k_i, o, g, t: (g[v], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, o, g, t: (t[v], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=interpret,
        name="grouped_matmul",
    )(offsets, group, tile, lhs, rhs)
    row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    return jnp.where(row < offsets[-1], out[:rows], 0.0)


_op = jax.custom_vjp(_grouped_matmul, nondiff_argnums=(3, 4))


def _op_fwd(lhs, rhs, group_sizes, tiling, interpret):
    return (_grouped_matmul(lhs, rhs, group_sizes, tiling, interpret),
            (lhs, rhs, group_sizes))


def _op_bwd(tiling, interpret, saved, g):
    lhs, rhs, group_sizes = saved
    _, vjp = jax.vjp(
        lambda a, b: grouped_matmul_reference(a, b, group_sizes), lhs, rhs)
    return (*vjp(g), None)


_op.defvjp(_op_fwd, _op_bwd)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def grouped_matmul(lhs, rhs, group_sizes, *, tiling=None, interpret=False):
    """``lhs [M, k]`` (rows sorted by group) x ``rhs [G, k, n]`` by
    ``group_sizes [G]`` (int32) → ``[M, n]`` float32, accumulated in
    float32; rows past ``sum(group_sizes)`` are zero. ``tiling``: ``(tm, tk,
    tn)`` dividing ``(M, k, n)``; left out, ``tiles`` chooses. The gradient
    is ``jax.lax.ragged_dot``'s own."""
    return _op(lhs, rhs, group_sizes.astype(jnp.int32), tiling, interpret)
