"""The train loop's carry (ISSUE 43): ``_train_epochs`` takes and returns the
fused ``[rows, rank + 1]`` tables every other module reads. Where the bias
column costs a lane tile of its own (rank 128) the loop scans over each table
split into its embedding ``[rows, rank]`` and its bias ``[rows]``; elsewhere
over the fused table (``two_tower._carry_cols``). Either way it moves where the
bias column lives, not what dense adam computes: the plain reference's step
loop (``benchmarks/reference/two_tower_ref.py``, the fused layout, float32
throughout) is the oracle."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import two_tower_ref as ref
from incubator_predictionio_tpu.models import two_tower as tt
from incubator_predictionio_tpu.utils.optim import adam_tree_init

N_USERS, N_ITEMS, BATCH, N_BATCHES, EPOCHS = 96, 40, 32, 3, 2
LR, REG = 0.03, 0.05


def _problem(rank: int, seed: int = 0):
    """Seeded tables (biases not zero: the last column has to be read) and
    user-sorted batches that leave the upper third of each table untouched."""
    rng = np.random.default_rng(seed)
    p = {"ue": rng.normal(size=(N_USERS, rank + 1)).astype(np.float32) * 0.1,
         "ie": rng.normal(size=(N_ITEMS, rank + 1)).astype(np.float32) * 0.1}
    shape = (N_BATCHES, BATCH)
    ub = np.sort(rng.integers(0, 2 * N_USERS // 3, shape), axis=1)
    ib = rng.integers(0, 2 * N_ITEMS // 3, shape)
    wb = np.ones(shape, np.float32)
    wb[-1, -5:] = 0.0   # the last batch's padding
    return p, (ub.astype(np.int32), ib.astype(np.int32),
               rng.normal(size=shape).astype(np.float32), wb)


def _reference(p, batches, moments: str):
    """The reference's ``_step`` over the same batches, as its ``train``
    loops it: tables, both moments, the last epoch's mean loss."""
    dt = jnp.dtype(moments)
    p = jax.tree.map(jnp.asarray, p)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, dt), p)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, dt), p)
    count = jnp.zeros((), jnp.int32)
    for _ in range(EPOCHS):
        losses = []
        for bu, bi, br, bw in zip(*batches):
            p, m, v, count, loss = ref._step(
                p, m, v, count, bu, bi, br, bw, LR, REG, dt)
            losses.append(loss)
    return p, m, v, float(jnp.mean(jnp.stack(losses)))


@pytest.mark.parametrize("moments", ["float32", "bfloat16"])
@pytest.mark.parametrize("rank", [8, 64, 128])
def test_split_carry_trains_as_the_reference_does(rank, moments):
    p0, batches = _problem(rank)
    p = jax.tree.map(jnp.asarray, p0)
    p, (count, m, v), loss = tt._train_epochs(
        p, adam_tree_init(p, moments), *batches, LR, REG, EPOCHS)
    rp, rm, rv, rloss = _reference(p0, batches, moments)
    assert int(count) == EPOCHS * N_BATCHES
    # the program multiplies the embeddings in bfloat16 where the reference
    # multiplies in float32, and adam turns a small gradient's last digits
    # into a step's: the comparison is the reference's own, by norms (the
    # train cell's; these sizes read 4e-4 / 1.4e-4 / 6e-4 at most, a bias
    # read as an embedding column or the reverse reads 1e-2 and more)
    numbers = ref.training_numbers(float(loss), p, {
        "loss": rloss, "tables": rp, "init": jax.tree.map(jnp.asarray, p0),
        "touched": {"ue": np.unique(batches[0]), "ie": np.unique(batches[1])}})
    assert numbers["loss_gap"] <= 2e-3
    assert numbers["dnorm_gap"] <= 1e-3
    assert numbers["row_rms_gap"] <= 3e-3
    # rows no triple names: dense adam leaves them where they were
    assert numbers["untouched_max"] == 0.0
    for k in ("ue", "ie"):
        assert p[k].shape == p0[k].shape and p[k].dtype == jnp.float32
        assert m[k].dtype == v[k].dtype == jnp.dtype(moments)
        for got, want in ((m[k], rm[k]), (v[k], rv[k])):
            got, want = (jnp.linalg.norm(x.astype(jnp.float32))
                         for x in (got, want))
            assert float(got) == pytest.approx(float(want), rel=2e-3)
        rows = N_USERS if k == "ue" else N_ITEMS
        assert not np.asarray(m[k], np.float32)[2 * rows // 3:].any()


@pytest.mark.parametrize("moments", ["float32", "bfloat16"])
@pytest.mark.parametrize("rank", [8, 64, 128])
def test_split_carry_equals_the_fused_step_to_rounding(rank, moments):
    """Against the SAME arithmetic in the fused layout (the parent's step:
    bfloat16 product, bias in the last column), the split changes nothing
    beyond the order of a sum: tables, both moments and the loss to 1e-6."""
    from incubator_predictionio_tpu.utils.optim import adam_apply

    def fused_loss(p, bu, bi, br, bw):
        gu, gi = p["ue"][bu], p["ie"][bi]
        ue = gu[:, :-1].astype(jnp.bfloat16)
        ie = gi[:, :-1].astype(jnp.bfloat16)
        pred = (jnp.sum(ue * ie, axis=-1).astype(jnp.float32)
                + gu[:, -1] + gi[:, -1])
        denom = jnp.maximum(jnp.sum(bw), 1.0)
        return jnp.sum((pred - br) ** 2 * bw) / denom + REG * (
            jnp.sum(ue.astype(jnp.float32) ** 2)
            + jnp.sum(ie.astype(jnp.float32) ** 2)) / denom

    @jax.jit
    def fused_step(p, o, batch):
        loss, g = jax.value_and_grad(fused_loss)(p, *batch)
        return adam_apply(p, g, o, LR), loss

    p0, batches = _problem(rank, seed=1)
    want = jax.tree.map(jnp.asarray, p0)
    wo = adam_tree_init(want, moments)
    for _ in range(EPOCHS):
        losses = []
        for batch in zip(*batches):
            (want, wo), loss = fused_step(want, wo, batch)
            losses.append(loss)
    p = jax.tree.map(jnp.asarray, p0)
    p, o, loss = tt._train_epochs(
        p, adam_tree_init(p, moments), *batches, LR, REG, EPOCHS)
    assert float(loss) == pytest.approx(float(jnp.mean(jnp.stack(losses))),
                                        abs=1e-6)
    for got, exp in zip(jax.tree.leaves((p, o)), jax.tree.leaves((want, wo))):
        assert got.shape == exp.shape and got.dtype == exp.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32), atol=1e-6)


def _lowered(rank: int, rows_u: int = 72, rows_i: int = 48):
    p = {"ue": jnp.zeros((rows_u, rank + 1)), "ie": jnp.zeros((rows_i, rank + 1))}
    idx, val = jnp.zeros((4, 16), jnp.int32), jnp.zeros((4, 16))
    return tt._train_epochs.lower(
        p, adam_tree_init(p, "float32"), idx, idx, val, val, 0.03, 0.01, 2)


@pytest.mark.parametrize("rank, cols", [(128, 128), (256, 256), (8, 9),
                                        (64, 65), (127, 128), (200, 201)])
def test_the_loop_carries_the_table_of_fewer_lane_tiles(rank, cols):
    """Where the bias column costs a 128-lane tile of its own the ``while``
    carries embedding and bias apart and no ``[rows, rank + 1]`` array;
    elsewhere the fused table (tests/test_program_spans.py pins that program
    to the digest of the one before the split existed). What comes out is
    fused either way."""
    assert tt._carry_cols(rank) == cols
    lowered = _lowered(rank)
    whiles = re.findall(r"stablehlo\.while.*", lowered.as_text())
    assert len(whiles) == 2   # epochs of steps
    for line in whiles:
        # p, m, v of the user table
        assert len(re.findall(rf"tensor<72x{cols}xf32>", line)) >= 3
        if cols == rank:
            assert len(re.findall(r"tensor<72xf32>", line)) >= 3
            assert f"x{rank + 1}xf32" not in line
        else:
            assert "tensor<72xf32>" not in line
    out_p, (_, out_m, out_v), _ = lowered.out_info
    for tree in (out_p, out_m, out_v):
        assert {k: v.shape for k, v in tree.items()} == {
            "ue": (72, rank + 1), "ie": (48, rank + 1)}


@pytest.mark.multichip
def test_split_carry_on_a_model_axis_equals_the_single_device_loop(mesh8):
    """Tables row-sharded over ``model`` as ``ShardedTable`` places them,
    batches over ``data``: the loop returns what one device returns, and
    returns it row-sharded as it came (``checkpointed_epochs`` feeds a
    chunk's output to the next chunk, serving reads the layout)."""
    from incubator_predictionio_tpu.sharding.table import array_model_shards

    p0, batches = _problem(128, seed=2)
    one = jax.tree.map(jnp.asarray, p0)
    one, (_, m1, v1), loss1 = tt._train_epochs(
        one, adam_tree_init(one, "float32"), *batches, LR, REG, EPOCHS)

    p = {k: mesh8.put(t, "model", None) for k, t in p0.items()}
    staged = [mesh8.put(b, None, mesh8.data_axis) for b in batches]
    p, (_, m, v), loss = tt._train_epochs(
        p, adam_tree_init(p, "float32"), *staged, LR, REG, EPOCHS)
    assert float(loss) == pytest.approx(float(loss1), abs=1e-6)
    for k in ("ue", "ie"):
        for got, want in ((p[k], one[k]), (m[k], m1[k]), (v[k], v1[k])):
            assert array_model_shards(got) == 4
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6)


@pytest.mark.parametrize("rank, attrs", [
    (128, {"carry_cols": 128, "carry_pad_pct": 0.0}),
    (8, {"carry_cols": 9, "carry_pad_pct": 1322.2})])
def test_fit_reports_the_width_its_loop_carries(rank, attrs):
    """``train.fit.compute`` says what the loop scanned: the carried
    table's columns and the lanes an (8, 128) tile pads per 100 live ones
    (the fused carry at rank 128 would read 129 and 98.4)."""
    from incubator_predictionio_tpu.obs import trace
    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    assert tt._carry_pad_pct(129) == pytest.approx(98.4, abs=0.05)
    rng = np.random.default_rng(3)
    n = 512
    trace.TRACES.clear()
    tt.TwoTowerMF(tt.TwoTowerConfig(rank=rank, epochs=1, batch_size=256)).fit(
        MeshContext.create(), rng.integers(0, 50, n).astype(np.int32),
        rng.integers(0, 30, n).astype(np.int32),
        rng.random(n).astype(np.float32), 50, 30)
    (sp,) = [s for s in trace.TRACES.spans()
             if s["name"] == "train.fit.compute"]
    assert sp["attrs"] == attrs
