"""ops/grouped_matmul.py under the Pallas interpreter against
``jax.lax.ragged_dot``: the widths of the visitor cell's routed experts cut
to CPU size with the same divisibility (2688 = 21 lane tiles, 1920 = 15, and
1856 = 14.5: a contraction that is no whole number of them) and the other
three sequence cells' (whole multiples of 256 lanes, 16 groups of which half
have no row), every group layout the sorted picks of a serving block make,
the routed experts through it with padding tokens and picks on experts held
elsewhere, the gradient, and the rule that chooses it
(``latent_moe.expert_form``: the backend, and a floor of one lane tile)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.ops.grouped_matmul import (
    grouped_matmul,
    grouped_matmul_reference,
    tiles,
)
from tests.fixtures.ssm_tiny import config

TOL = 2e-4


def _operands(m, k, n, groups, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(keys[0], (m, k), dtype),
            (jax.random.normal(keys[1], (groups, k, n)) * k ** -0.5
             ).astype(dtype))


@pytest.mark.parametrize("m, k, n, sizes, tiling", [
    # k of 21 sublane-tile pieces into n of 15 lane tiles, and back
    (96, 336, 1920, [0, 5, 0, 7, 20, 0, 1, 3], (32, 336, 640)),
    (96, 240, 2688, [0, 5, 0, 7, 20, 0, 1, 3], (96, 240, 896)),
    # a contraction that is no whole number of lane tiles: one whole piece
    (96, 232, 384, [4, 0, 9, 0, 30, 2], None),
    # the contraction in pieces (three of 128)
    (96, 384, 256, [4, 0, 9, 0, 30, 2], (32, 128, 128)),
    # no group has a row: nothing is visited, every row is past the groups
    (96, 256, 384, [0, 0, 0, 0], None),
    # one group holds every row
    (96, 256, 384, [96], (32, 256, 128)),
    # every row in the last group
    (96, 256, 384, [0, 0, 0, 96], (32, 256, 384)),
    # a lone turn: few rows at the front, the rest past the groups
    (96, 256, 384, [1, 0, 2, 0, 0, 1, 1, 0], (16, 256, 384)),
    # one partial row tile (100 rows in a tile of 128)
    (100, 256, 384, [30, 0, 0, 0, 0, 50], None),
    # several row tiles and a partial one; groups that span and share them
    (300, 256, 384, [30, 100, 0, 0, 1, 150], (64, 256, 128)),
    (300, 256, 384, [1, 1, 1, 1, 1, 200], (128, 128, 384)),
    # bfloat16 operands, float32 sums (what serving runs)
    (288, 256, 640, [17, 0, 60, 3, 0, 90], None),
    # widths that are whole multiples of 256 lanes (the feed, Mistral and
    # lifelong cells' kind): 16 groups, half of them with no row, half of
    # the rows past the groups (picks on the absent chip's experts)
    (64, 256, 512, [0, 9, 0, 0, 1, 0, 14, 0, 2, 0, 3, 0, 0, 2, 0, 1], None),
    (64, 512, 256, [0, 9, 0, 0, 1, 0, 14, 0, 2, 0, 3, 0, 0, 2, 0, 1], None),
    (512, 256, 768, [40, 0, 0, 31, 0, 70, 0, 2, 0, 55, 0, 1, 30, 0, 27, 0],
     None),
    (512, 768, 256, [40, 0, 0, 31, 0, 70, 0, 2, 0, 55, 0, 1, 30, 0, 27, 0],
     (128, 256, 256)),
    (576, 512, 512, [40, 0, 0, 31, 0, 70, 0, 2, 0, 55, 0, 1, 30, 0, 59, 0],
     None),
    # the histories cell's experts at their own widths (18 lane tiles into
    # 7 and back), a turn's picks on a few of the 16 held: a whole matrix a
    # piece by the rule
    (64, 2304, 896, [0, 9, 0, 0, 1, 0, 14, 0, 2, 0, 3, 0, 0, 2, 0, 1], None),
    (64, 896, 2304, [0, 9, 0, 0, 1, 0, 14, 0, 2, 0, 3, 0, 0, 2, 0, 1], None),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, (list, tuple))
    else str(v))
def test_the_kernel_is_ragged_dot(m, k, n, sizes, tiling):
    dtype = jnp.bfloat16 if m in (288, 576) else jnp.float32
    lhs, rhs = _operands(m, k, n, len(sizes), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, rhs, sizes, tiling=tiling, interpret=True)
    want = grouped_matmul_reference(lhs, rhs, sizes)
    assert got.dtype == jnp.float32 and got.shape == (m, n)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    total = int(sizes.sum())
    assert not np.asarray(got[total:]).any()       # zeros, as ragged_dot's
    if total:
        assert np.abs(np.asarray(got[:total])).max() > 0.1


@pytest.mark.parametrize("m, k, n, want", [
    # a lone turn is one row tile, its pieces a third of a matrix
    (96, 2688, 1920, (96, 2688, 640)),
    (96, 1920, 2688, (96, 1920, 896)),
    # from two row tiles on, the whole matrix a piece
    (768, 2688, 1920, (128, 2688, 1920)),
    (12_288, 1920, 2688, (128, 1920, 2688)),
    (96, 232, 384, (96, 232, 384)),        # no lane-tile divisor: whole
    (2048, 4096, 2048, (128, 4096, 1024)),  # the latent block's: a half
    # the feed cell's: the whole 7.3 MB matrix a piece from two row tiles
    # on, two pieces an expert under a lone turn's row tile
    (512, 2048, 1792, (128, 2048, 1792)),
    (64, 2048, 1792, (64, 2048, 896)),
    (512, 1792, 2048, (128, 1792, 2048)),
    # the sparse-index block's: the whole 3.1 MB matrix at every row count
    (16_384, 2048, 768, (128, 2048, 768)),
    (64, 2048, 768, (64, 2048, 768)),
    # the histories cell's: the whole 4.1 MB matrix at every row count
    (32, 2304, 896, (32, 2304, 896)),
    (4096, 2304, 896, (128, 2304, 896)),
    (4096, 896, 2304, (128, 896, 2304)),
])
def test_tiles_divide_the_widths_they_are_given(m, k, n, want):
    tm, tk, tn = tiles(m, k, n)
    assert (tm, tk, tn) == want
    assert k % tk == 0 and n % tn == 0
    assert tk * tn * 2 <= 12 << 20         # two such pieces in flight


@pytest.mark.parametrize("activation", ["relu2", "gated_silu"])
def test_the_routed_experts_through_the_kernel_are_the_grouped_sum(
        activation, monkeypatch):
    """Sorted picks through the kernel against the same through
    ``ragged_dot``: the same sum, the same counters, for a share (picks on
    experts held elsewhere sort past the groups) with padding tokens."""
    cfg = config(expert_activation=activation, experts_held=4, expert_offset=2)
    lw = lm.init_params(jax.random.key(2), cfg)["layers"][1]
    lw["b_r"] = 0.1 * jax.random.normal(jax.random.key(3), lw["b_r"].shape)
    x = jax.random.normal(jax.random.key(7), (70, cfg.d_model))
    valid = jnp.arange(70) < 61
    idx, w = lm.moe_router(x, lw, cfg)
    assert lm.expert_form(lw["we1"].shape) == "ragged"    # no TPU here
    ragged = lm.moe_experts(x, idx, w, valid, lw, cfg)
    monkeypatch.setattr(lm, "kernel_backend", lambda: "interpret")
    assert lm.expert_form(lw["we1"].shape) == "kernel"
    kernel = lm.moe_experts(x, idx, w, valid, lw, cfg)
    np.testing.assert_allclose(kernel[0], ragged[0], atol=TOL, rtol=0)
    np.testing.assert_array_equal(kernel[1], ragged[1])
    assert np.abs(ragged[0][:61]).max() > 0.1
    assert not np.asarray(kernel[0][61:]).any()
    assert int(ragged[1][4]) > 0         # picks that fell on absent experts


def test_the_gradient_is_ragged_dots():
    lhs, rhs = _operands(96, 256, 384, 6)
    sizes = jnp.asarray([4, 0, 9, 0, 30, 2], jnp.int32)
    weight = jax.random.normal(jax.random.key(5), (96, 384))

    def loss(op):
        return lambda a, b: jnp.sum(jnp.square(op(a, b, sizes)) * weight)

    got = jax.grad(loss(lambda a, b, s: grouped_matmul(
        a, b, s, interpret=True)), (0, 1))(lhs, rhs)
    want = jax.grad(loss(grouped_matmul_reference), (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-3, rtol=1e-4)
    assert np.abs(np.asarray(got[1])).max() > 0.1
    assert not np.asarray(got[0][45:]).any()     # rows past the groups
    assert not np.asarray(got[1][1]).any()       # a group with no row


@pytest.mark.parametrize("stored, a_tile", [
    ((64, 2688, 1920), True),      # the visitor cell: 21 and 15 lane tiles
    ((64, 2688, 1856), True),
    ((64, 2048, 1920), True),
    ((64, 2688, 2048), True),
    ((32, 4096, 2048), True),      # the latent block's experts
    ((128, 2048, 768), True),      # the sparse-index block's
    ((16, 2048, 1792), True),      # the feed cell's (7 x 256)
    ((16, 2304, 896), True),       # the histories cell's (18 and 7 tiles)
    ((8, 64, 256), True),          # tests/fixtures/ssm_tiny.py
    ((8, 64, 32), False),          # under one tile: the pinned toy programs
    ((4, 32, 16), False),
], ids=str)
@pytest.mark.parametrize("backend", [None, "interpret", "mosaic"])
def test_the_expert_form_is_chosen_from_the_widths_and_the_backend(
        stored, a_tile, backend, monkeypatch):
    """One form on a backend that runs the package's kernels, at every
    stored width of at least a lane tile; ``ragged_dot`` with no such
    backend and for the toys under a tile."""
    monkeypatch.setattr(lm, "kernel_backend", lambda: backend)
    want = "kernel" if a_tile and backend else "ragged"
    assert lm.expert_form(stored) == want
