"""The within-batch user sort of ``TwoTowerMF.fit`` on the device (ISSUE 25):
``two_tower._order_batches``, over the fixed-shape blocks a fit stages,
against the host oracle it replaced, ``_sort_batches_by_entity``, value for
value — on one device and on the 8 virtual CPU devices — and the fit around
it against a fit ordered on the host.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from incubator_predictionio_tpu.models import two_tower as tt
from incubator_predictionio_tpu.models.two_tower import (
    TwoTowerConfig,
    TwoTowerMF,
)
from incubator_predictionio_tpu.obs import trace
from incubator_predictionio_tpu.parallel.mesh import MeshContext

# (n, batch, n_users): the shapes of ISSUE 25's bullet (a), then those the
# blocks add: a batch width that is no power of two, several blocks with a
# short last one, blocks that end with the batches
CASES = {
    "n_below_batch": (700, 1024, 50),
    "n_multiple_of_batch": (4096, 1024, 300),
    "n_multiple_plus_one": (4097, 1024, 300),
    "many_duplicate_users": (6000, 2048, 7),
    "single_user": (3000, 1024, 1),
    "width_not_a_power_of_two": (2500, 600, 90),
    "three_blocks": (40 * (2 * tt._ORDER_ROWS + 3) - 17, 40, 25),
    "two_full_blocks": (2 * tt._ORDER_ROWS * 32, 32, 25),
}


def _triples(n: int, n_users: int, seed: int = 11):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_users, n).astype(np.int32),
            rng.integers(0, 400, n).astype(np.int32),
            (1 + 4 * rng.random(n)).astype(np.float32))


def _host_staged(users, items, ratings, batch: int, seed: int):
    """The parent's staging, all on the host: permutation + padding from the
    seed, concatenated, each batch sorted by user, ``w`` riding along."""
    n = len(users)
    n_batches = max(1, (n + batch - 1) // batch)
    n_pad = n_batches * batch
    rng = np.random.default_rng(seed)
    order = np.concatenate(
        [rng.permutation(n), rng.integers(0, max(n, 1), n_pad - n)])
    w = np.concatenate(
        [np.ones(n, np.float32), np.zeros(n_pad - n, np.float32)])
    unsorted = order.reshape(n_batches, batch)
    order, w = tt._sort_batches_by_entity(order, w, users, n_batches, batch)
    mean = float(ratings.mean())
    shape = (n_batches, batch)
    return {
        "order": unsorted.reshape(-1), "centred": ratings - mean,
        "sorted": (users[order].reshape(shape), items[order].reshape(shape),
                   (ratings - mean)[order].reshape(shape), w.reshape(shape)),
    }


def _device_ordered(ctx: MeshContext, users, items, host, batch: int):
    """What ``fit`` does between the host's shuffle and ``train.fit.init``."""
    blocks = tt._stage_blocks(ctx, host["order"], batch, users, items,
                              host["centred"])
    assert {a.shape for blk in blocks for a in blk} == {
        (tt._ORDER_ROWS, ctx.pad_to_batch_multiple(
            1 << (batch - 1).bit_length()))}  # one shape, whatever n
    return tt._order_blocks(ctx, blocks, len(users), batch)


def _assert_same(got, want):
    for name, g, w in zip(("ub", "ib", "rb", "wb"), got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("case", list(CASES))
def test_device_order_equals_the_host_oracle(case):
    n, batch, n_users = CASES[case]
    users, items, ratings = _triples(n, n_users)
    host = _host_staged(users, items, ratings, batch, seed=5)
    ctx = MeshContext.create(devices=jax.devices()[:1])
    got = _device_ordered(ctx, users, items, host, batch)
    _assert_same(got, host["sorted"])
    assert int(np.asarray(got[3]).sum()) == n


@pytest.mark.parametrize("data", [2, 3, 8])
@pytest.mark.parametrize("case", ["n_multiple_plus_one",
                                  "many_duplicate_users",
                                  "width_not_a_power_of_two", "three_blocks"])
def test_device_order_on_a_mesh_equals_the_host_oracle(case, data):
    n, batch, n_users = CASES[case]
    users, items, ratings = _triples(n, n_users)
    ctx = MeshContext.create(axes={"data": data},
                             devices=jax.devices()[:data])
    batch = ctx.pad_to_batch_multiple(batch)  # as fit makes its global batch
    host = _host_staged(users, items, ratings, batch, seed=5)
    got = _device_ordered(ctx, users, items, host, batch)
    _assert_same(got, host["sorted"])
    want = ctx.sharding(None, "data")
    for g in got:  # split as the host staging split it
        assert g.sharding.is_equivalent_to(want, g.ndim)


@pytest.mark.parametrize("case", list(CASES))
def test_every_row_of_ub_is_non_decreasing(case):
    """``_train_epochs`` gathers with ``indices_are_sorted=True``: a wrong
    sort is undefined behaviour there, not an error."""
    n, batch, n_users = CASES[case]
    users, items, ratings = _triples(n, n_users, seed=23)
    host = _host_staged(users, items, ratings, batch, seed=9)
    ctx = MeshContext.create(devices=jax.devices()[:1])
    ub, _, _, wb = (np.asarray(a) for a in
                    _device_ordered(ctx, users, items, host, batch))
    assert (np.diff(ub, axis=1) >= 0).all()
    # the padding is in the last batch alone and keeps zero weight
    assert (wb[:-1] == 1).all()
    assert set(np.unique(wb)) <= {0.0, 1.0}


def _host_order_batches(ub, ib, rb, n_real, batch, out):
    """``_order_batches`` back on the host: the oracle over one block the
    fit staged, put back under the same sharding."""
    ub, ib, rb = (np.asarray(a) for a in (ub, ib, rb))
    rows, width = ub.shape
    row, col = np.divmod(np.arange(rows * width), width)
    w = ((col < batch) & (row * batch + col < n_real)).astype(np.float32)
    order, w = tt._sort_batches_by_entity(
        np.arange(rows * width), w, ub.reshape(-1), rows, width)
    return tuple(jax.device_put(a.reshape(-1)[order].reshape(ub.shape), out)
                 for a in (ub, ib, rb)) + (
        jax.device_put(w.reshape(ub.shape), out),)


TABLES = ("user_emb", "item_emb", "user_bias", "item_bias")


@pytest.mark.parametrize("axes", [None, {"data": 2}, {"data": 2, "model": 4}],
                         ids=["one_device", "data2", "data2_model4"])
def test_fit_equals_the_host_ordered_fit(axes, monkeypatch):
    users, items, ratings = _triples(6000, 300, seed=0)
    cfg = TwoTowerConfig(rank=8, epochs=2, batch_size=1024, seed=3,
                         gather="host")

    def fit(order_batches):
        devs = jax.devices()
        ctx = (MeshContext.create(devices=devs[:1]) if axes is None else
               MeshContext.create(
                   axes=axes, devices=devs[:int(np.prod(list(axes.values())))]))
        staged = []
        join = tt._join_batches

        def spy(*args):
            staged.append(join(*args))
            return staged[-1]

        monkeypatch.setattr(tt, "_order_batches", order_batches)
        monkeypatch.setattr(tt, "_join_batches", spy)
        model = TwoTowerMF(cfg).fit(ctx, users, items, ratings, 300, 400)
        monkeypatch.undo()
        (batches,) = staged  # once a fit
        return model, tuple(np.asarray(a) for a in batches)

    device, got = fit(tt._order_batches)
    host, want = fit(_host_order_batches)
    assert device.final_loss == host.final_loss
    for name in TABLES:  # bit for bit
        np.testing.assert_array_equal(
            np.asarray(getattr(device, name)), np.asarray(getattr(host, name)),
            err_msg=name)
    _assert_same(got, want)
    # and both are the parent's staging, value for value: the in-place
    # shuffle draws what rng.permutation draws
    _assert_same(got, _host_staged(users, items, ratings, got[0].shape[1],
                                   cfg.seed)["sorted"])


def test_a_fit_orders_twice_and_its_second_order_span_says_what():
    users, items, ratings = _triples(6000, 300, seed=0)
    trace.TRACES.clear()
    TwoTowerMF(TwoTowerConfig(rank=8, epochs=1, batch_size=1024)).fit(
        MeshContext.create(devices=jax.devices()[:1]),
        users, items, ratings, 300, 400)
    fit = sorted((s for s in trace.TRACES.spans()
                  if s["name"].startswith("train.fit.")),
                 key=lambda s: s["startUnix"])
    assert [s["name"] for s in fit] == [
        "train.fit.order", "train.fit.h2d", "train.fit.order",
        "train.fit.init", "train.fit.compute", "train.fit.gather"]
    assert fit[2]["attrs"] == {"n_batches": 6, "batch": 1024}


@pytest.mark.parametrize("batch", [1024, 600])
def test_one_sort_executable_serves_every_event_count(batch):
    """REVIEW of PR 25: the sort is dear to compile on a TPU, so a table
    that grew, or a small fit of another size, may not compile it again."""
    ctx = MeshContext.create(devices=jax.devices()[:1])

    def order(n):
        users, items, ratings = _triples(n, 50)
        host = _host_staged(users, items, ratings, min(batch, n), seed=1)
        _device_ordered(ctx, users, items, host, min(batch, n))

    order(5 * batch)
    before = tt._order_batches._cache_size()
    for n in (5 * batch + 1, 9 * batch - 3, batch * (tt._ORDER_ROWS + 2),
              batch - 1, 7 * batch // 8):  # widths of one power of two
        order(n)
    assert tt._order_batches._cache_size() == before
