"""Sharded embedding subsystem (docs/sharding.md).

Pins the acceptance contract of the sharded train/serve arc:

- ShardSpec/ShardedTable layout math, per-shard init keys, and the
  simulated ``PIO_SHARD_HBM_BUDGET`` bound (the doesn't-fit-one-chip
  proof the MULTICHIP dryrun relies on);
- sharded-vs-single-host parity: per-shard top-k + cross-shard merge is
  BITWISE the single-host oracle for exact retrieval — host blocks vs the
  host-numpy oracle, and the shard_map device path vs the single-device
  executable — through every rule-mask kind;
- the composed per-shard-IVF + merge-rerank path holds the recall@10 ≥
  0.95 floor with all rule-mask kinds, and under-coverage falls back to
  sharded-exact (counted, never a short answer);
- streaming delta rows route to their OWNING shard (other shards' arrays
  are shared untouched; the receiver keeps serving its own view);
- train→save→deploy: a fit on a data×model mesh keeps sharded tables,
  serves through the sharded path with ZERO full-table host gathers, and
  round-trips through RecModel.save/load straight into the sharded layout.
"""

import numpy as np
import pytest

from incubator_predictionio_tpu.models.two_tower import (
    TwoTowerConfig,
    TwoTowerMF,
    TwoTowerModel,
)
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.sharding import shard_metrics
from incubator_predictionio_tpu.sharding.table import (
    HBMBudgetExceeded,
    ShardSpec,
    ShardedTable,
    check_budget,
    hbm_budget,
    parse_bytes,
    requires_sharding,
)

RANK = 16


def _towers(seed=1, n_users=160, n_items=6000, rank=RANK, n_concepts=64,
            sigma=0.5):
    """Mixture-of-concepts towers (the geometry trained MF factors have —
    same recipe as tests/test_two_stage_retrieval.py; the recall floor is
    specified over this, not iid noise)."""
    rng = np.random.default_rng(seed)
    concepts = rng.standard_normal((n_concepts, rank)).astype(np.float32)
    item = concepts[rng.integers(0, n_concepts, n_items)] \
        + sigma * rng.standard_normal((n_items, rank)).astype(np.float32)
    user = concepts[rng.integers(0, n_concepts, n_users)] \
        + sigma * rng.standard_normal((n_users, rank)).astype(np.float32)
    return (user.astype(np.float32), item.astype(np.float32),
            (rng.standard_normal(n_users) * 0.1).astype(np.float32),
            (rng.standard_normal(n_items) * 0.1).astype(np.float32))


def _model(seed=1, n_users=160, n_items=6000, **kw):
    user, item, ub, ib = _towers(seed, n_users, n_items, **kw)
    return TwoTowerModel(user_emb=user, item_emb=item, user_bias=ub,
                         item_bias=ib, mean=3.0,
                         config=TwoTowerConfig(rank=RANK))


def _masks(rng, b, n_items, kind):
    """One of the rule-mask kinds recommend_batch supports."""
    exclude = row_mask = None
    if kind in ("exclude", "both"):
        exclude = rng.choice(n_items, max(20, n_items // 50),
                             replace=False).astype(np.int64)
    if kind in ("row_mask", "both"):
        row_mask = np.zeros((b, n_items), np.float32)
        hits = max(50, b * n_items // 400)
        row_mask[rng.integers(0, b, hits),
                 rng.integers(0, n_items, hits)] = -np.inf
    return exclude, row_mask


MASK_KINDS = ("none", "exclude", "row_mask", "both")


# -- layout / budget ---------------------------------------------------------

def test_shard_spec_layout_math():
    spec = ShardSpec("ie", 103, 17, 4)
    assert spec.padded_rows == 104 and spec.rows_per_shard == 26
    assert spec.shard_bounds(0) == (0, 26)
    assert spec.shard_bounds(3) == (78, 103)  # real rows clipped
    assert spec.shard_row_counts() == [26, 26, 26, 25]
    assert spec.owner_of(0) == 0 and spec.owner_of(78) == 3
    with pytest.raises(ValueError):
        spec.owner_of(103)
    with pytest.raises(ValueError):
        spec.shard_bounds(4)
    d = spec.to_dict()
    assert d["rows_per_shard"] == 26 and d["shard_rows"][-1] == 25
    # single shard degenerates cleanly
    one = ShardSpec("ue", 10, 17, 1)
    assert one.shard_bounds(0) == (0, 10)


def test_parse_bytes_and_budget(shard_env):
    assert parse_bytes("1024") == 1024
    assert parse_bytes("64KB") == 64 * 1024
    assert parse_bytes("1.5MiB") == int(1.5 * (1 << 20))
    assert parse_bytes("2g") == 2 << 30
    with pytest.raises(ValueError):
        parse_bytes("lots")
    assert hbm_budget() is None
    shard_env.setenv("PIO_SHARD_HBM_BUDGET", "1MB")
    assert hbm_budget() == 1 << 20
    # training residency = table + BOTH adam moments (bf16 moments shrink it)
    spec = ShardSpec("ie", 10_000, RANK + 1, 1)
    assert spec.train_bytes_per_shard() == 10_000 * 17 * 12
    assert spec.train_bytes_per_shard("bfloat16") == 10_000 * 17 * 8
    assert requires_sharding(10_000, RANK + 1)      # 2MB > 1MB budget
    assert not requires_sharding(1_000, RANK + 1)
    with pytest.raises(HBMBudgetExceeded, match="model.*mesh axis"):
        check_budget(spec)
    check_budget(ShardSpec("ie", 10_000, RANK + 1, 4))  # per-shard fits


def test_sharded_table_init_per_shard_keys(mesh8):
    """Per-shard fold_in keys: a shard's block depends only on (key,
    shard, rows_per_shard) — and the budget is enforced at init."""
    import jax

    key = jax.random.key(7)
    t = ShardedTable.init_train(mesh8, "ue", 100, RANK, key, 0.25)
    assert t.spec.n_shards == 4 and t.axis == "model"
    assert t.array.shape == (100, RANK + 1)
    host = np.asarray(jax.device_get(t.array))
    assert np.all(host[:, RANK] == 0.0)  # bias column zero
    # block s equals a direct fold_in render of the same shard
    s = 2
    lo, hi = t.spec.shard_bounds(s)
    expect = np.asarray(jax.random.normal(
        jax.random.fold_in(key, s), (t.spec.rows_per_shard, RANK))) * 0.25
    np.testing.assert_array_equal(host[lo:hi, :RANK], expect)
    # data-only mesh → single shard, legacy one-key formula
    ctx1 = MeshContext.create(axes={"data": 8})
    t1 = ShardedTable.init_train(ctx1, "ue", 100, RANK, key, 0.25)
    assert t1.spec.n_shards == 1 and t1.axis is None
    legacy = np.asarray(jax.random.normal(key, (100, RANK))) * 0.25
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t1.array))[:, :RANK], legacy)


def test_init_train_enforces_budget(mesh8, shard_env):
    import jax

    shard_env.setenv("PIO_SHARD_HBM_BUDGET", "64KB")
    key = jax.random.key(0)
    # 4 shards: 2000/4 × 17 × 12B ≈ 102KB per shard > 64KB
    with pytest.raises(HBMBudgetExceeded):
        ShardedTable.init_train(mesh8, "ue", 2000, RANK, key, 0.25)
    ShardedTable.init_train(mesh8, "ue", 500, RANK, key, 0.25)  # fits


# -- sharded-exact parity (host blocks vs host oracle) -----------------------

@pytest.mark.parametrize("kind", MASK_KINDS)
def test_host_sharded_exact_bitwise_parity(kind, shard_env):
    """Per-shard top-k + merge over virtual host shards answers BITWISE
    the single-host numpy oracle — ids and scores — for every mask kind."""
    oracle = _model()
    shard_env.setenv("PIO_SHARD_SERVE", "0")
    shard_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    oracle.prepare_for_serving()
    assert oracle._host_items is not None

    m = _model()
    shard_env.setenv("PIO_SHARD_SERVE", "1")
    shard_env.setenv("PIO_SHARD_SERVE_SHARDS", "5")  # uneven on purpose
    m.prepare_for_serving()
    assert m._sharded is not None and m._sharded.device is None
    assert m.serving_info()["path"] == "sharded-host-numpy"

    rng = np.random.default_rng(5)
    users = rng.integers(0, 160, 13).astype(np.int32)
    exclude, row_mask = _masks(rng, 13, 6000, kind)
    oi, osc = TwoTowerMF.recommend_batch(oracle, users, 10, exclude, row_mask)
    si, ssc = TwoTowerMF.recommend_batch(m, users, 10, exclude, row_mask)
    np.testing.assert_array_equal(oi, si)
    np.testing.assert_array_equal(
        np.asarray(osc, np.float32).view(np.int32),
        np.asarray(ssc, np.float32).view(np.int32))


def test_host_sharded_num_edge_cases(shard_env):
    m = _model(n_items=40)
    shard_env.setenv("PIO_SHARD_SERVE", "1")
    shard_env.setenv("PIO_SHARD_SERVE_SHARDS", "7")
    shard_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    m.prepare_for_serving()
    users = np.arange(3, dtype=np.int32)
    # num > rows_per_shard (40/7 → 6 per shard) and num > n_items both work
    idx, sc = TwoTowerMF.recommend_batch(m, users, 25)
    assert idx.shape == (3, 25) and len(set(idx[0])) == 25
    idx, sc = TwoTowerMF.recommend_batch(m, users, 100)
    assert idx.shape == (3, 40)
    idx, sc = TwoTowerMF.recommend_batch(m, users, 0)
    assert idx.shape == (3, 0)


# -- sharded-exact parity (device shard_map vs single-device oracle) ---------

@pytest.fixture
def sharded_fit(mesh8):
    """One deterministic device-mode fit on the data×model mesh (tables
    stay model-axis sharded) + an identically-seeded twin for the oracle."""
    rng = np.random.default_rng(0)
    n, n_users, n_items = 4096, 500, 4000
    args = (rng.integers(0, n_users, n).astype(np.int32),
            rng.integers(0, n_items, n).astype(np.int32),
            (1 + 4 * rng.random(n)).astype(np.float32))
    cfg = TwoTowerConfig(rank=RANK, epochs=2, batch_size=1024, seed=1,
                         gather="device")

    def fit():
        return TwoTowerMF(cfg).fit(mesh8, *args, n_users=n_users,
                                   n_items=n_items)

    return fit


@pytest.mark.multichip
@pytest.mark.parametrize("kind", MASK_KINDS)
def test_device_sharded_exact_bitwise_parity(kind, sharded_fit, shard_env):
    """The shard_map per-shard top-k + merge executable answers BITWISE
    the single-device exact executable, for every mask kind."""
    from incubator_predictionio_tpu.sharding.table import array_model_shards

    oracle = sharded_fit()
    shard_env.setenv("PIO_SHARD_SERVE", "0")
    oracle.prepare_for_serving(host_max_elements=0)
    assert oracle._device_items is not None

    m = sharded_fit()
    assert m.device_resident
    assert array_model_shards(m._tables["ie"]) == 4  # trained sharded
    shard_env.setenv("PIO_SHARD_SERVE", "1")
    m.prepare_for_serving(host_max_elements=0)
    assert m._sharded is not None and m._sharded.device is not None
    assert m.serving_info()["path"] == "sharded-device-bf16"

    rng = np.random.default_rng(4)
    users = rng.integers(0, 500, 9).astype(np.int32)
    exclude, row_mask = _masks(rng, 9, 4000, kind)
    oi, osc = TwoTowerMF.recommend_batch(oracle, users, 7, exclude, row_mask)
    si, ssc = TwoTowerMF.recommend_batch(m, users, 7, exclude, row_mask)
    np.testing.assert_array_equal(np.asarray(oi), np.asarray(si))
    np.testing.assert_array_equal(
        np.asarray(osc, np.float32).view(np.int32),
        np.asarray(ssc, np.float32).view(np.int32))


@pytest.mark.multichip
def test_device_sharded_serving_never_gathers_full_table(sharded_fit,
                                                         shard_env):
    """The acceptance claim: sharded deploy + warmup + queries + a delta
    apply complete with ZERO full-table host gathers."""
    m = sharded_fit()
    shard_env.setenv("PIO_SHARD_SERVE", "1")
    shard_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    before = shard_metrics.FULL_GATHERS._default().value
    m.prepare_for_serving(host_max_elements=0)
    m.warmup(max_batch=8)
    TwoTowerMF.recommend_batch(m, np.arange(12, dtype=np.int32), 10)
    new = m.with_row_updates(
        user_rows={3: np.ones(RANK + 1, np.float32)},
        item_rows={17: np.ones(RANK + 1, np.float32)})
    TwoTowerMF.recommend_batch(new, np.arange(4, dtype=np.int32), 5)
    assert shard_metrics.FULL_GATHERS._default().value == before
    assert m.user_emb is None and m.item_emb is None


# -- composed per-shard IVF + merge rerank -----------------------------------

@pytest.fixture
def two_stage_sharded_env(shard_env):
    shard_env.setenv("PIO_RETRIEVAL_MODE", "two_stage")
    shard_env.setenv("PIO_RETRIEVAL_NPROBE", "16")
    shard_env.setenv("PIO_SHARD_SERVE", "1")
    shard_env.setenv("PIO_SHARD_SERVE_SHARDS", "4")
    # fp32 rerank baseline for these tests; the int8 compose test
    # opts back in explicitly (int8 is the serving default)
    shard_env.setenv("PIO_RETRIEVAL_QUANTIZE", "0")
    return shard_env


def _recall(a, b):
    return np.mean([len(set(x) & set(y)) / len(x) for x, y in zip(a, b)])


@pytest.mark.parametrize("kind", MASK_KINDS)
def test_sharded_ivf_recall_floor_all_mask_kinds(kind, two_stage_sharded_env):
    """Per-shard IVF prune + cross-shard merge rerank holds recall@10 ≥
    0.95 vs the exact oracle through every rule-mask kind."""
    n_items = 20_000
    oracle = _model(n_items=n_items)
    two_stage_sharded_env.setenv("PIO_SHARD_SERVE", "0")
    two_stage_sharded_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    oracle.prepare_for_serving()

    m = _model(n_items=n_items)
    two_stage_sharded_env.setenv("PIO_SHARD_SERVE", "1")
    two_stage_sharded_env.setenv("PIO_RETRIEVAL_MODE", "two_stage")
    m.prepare_for_serving()
    assert m._shard_ivf is not None and len(m._shard_ivf) == 4
    assert all(i is not None for i in m._shard_ivf)

    rng = np.random.default_rng(6)
    users = rng.integers(0, 160, 32).astype(np.int32)
    exclude, row_mask = _masks(rng, 32, n_items, kind)
    before = shard_metrics.SHARD_BATCHES._default().value
    from incubator_predictionio_tpu.serving import ann as ann_mod

    retrieval_before = ann_mod.TWO_STAGE_BATCHES._default().value
    oi, _ = TwoTowerMF.recommend_batch(oracle, users, 10, exclude, row_mask)
    gi, gs = TwoTowerMF.recommend_batch(m, users, 10, exclude, row_mask)
    assert _recall(oi, gi) >= 0.95
    assert np.isfinite(gs).all()
    assert shard_metrics.SHARD_BATCHES._default().value > before
    # the batch is accounted ONCE in pio_shard_*, never once-per-shard in
    # the single-host pio_retrieval_* counters
    assert ann_mod.TWO_STAGE_BATCHES._default().value == retrieval_before
    # masked items can never be served
    if exclude is not None:
        assert not np.isin(gi, exclude).any()
    if row_mask is not None:
        rows = np.arange(32)[:, None]
        assert np.all(row_mask[rows, gi] == 0.0)


def test_sharded_ivf_undercoverage_falls_back_to_exact(two_stage_sharded_env):
    """A whitelist mask so narrow a shard cannot fill num finite-scored
    candidates ⇒ counted fallback; the answer is the sharded-EXACT one
    (never a short or masked-padded result)."""
    n_items = 20_000
    m = _model(n_items=n_items)
    m.prepare_for_serving()
    rng = np.random.default_rng(7)
    users = rng.integers(0, 160, 4).astype(np.int32)
    # whitelist: only 12 items near one shard survive for every row
    keep = np.arange(100, 112)
    row_mask = np.full((4, n_items), -np.inf, np.float32)
    row_mask[:, keep] = 0.0
    before = shard_metrics.SHARD_FALLBACKS._default().value
    gi, gs = TwoTowerMF.recommend_batch(m, users, 10, row_mask=row_mask)
    assert shard_metrics.SHARD_FALLBACKS._default().value > before
    assert np.isin(gi, keep).all() and np.isfinite(gs).all()
    # exact-path agreement (sharded exact is bitwise the host oracle)
    oracle = _model(n_items=n_items)
    two_stage_sharded_env.setenv("PIO_SHARD_SERVE", "0")
    two_stage_sharded_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    oracle.prepare_for_serving()
    oi, _ = TwoTowerMF.recommend_batch(oracle, users, 10, row_mask=row_mask)
    np.testing.assert_array_equal(oi, gi)


# -- streaming deltas route to the owning shard ------------------------------

def test_delta_rows_route_to_owning_shard(two_stage_sharded_env):
    n_items = 20_000
    m = _model(n_items=n_items)
    m.prepare_for_serving()
    sh = m._sharded
    routed_before = shard_metrics.DELTA_ROUTED._default().value
    boost = np.concatenate([np.full(RANK, 5.0), [3.0]]).astype(np.float32)
    target = 7  # owned by shard 0
    new = m.with_row_updates(item_rows={target: boost})
    assert shard_metrics.DELTA_ROUTED._default().value == routed_before + 1
    # only the owning shard's block was rebuilt; others are SHARED arrays
    owner = sh.spec.owner_of(target)
    for s in range(sh.n_shards):
        same = new._sharded.blocks[s].bias is sh.blocks[s].bias
        assert same == (s != owner)
        # IVF overlay landed only on the owner
        stale = new._sharded.ivf[s].stale_count
        assert stale == (1 if s == owner else 0)
    # the boosted row now dominates; the RECEIVER is untouched
    users = np.arange(6, dtype=np.int32)
    ni, _ = TwoTowerMF.recommend_batch(new, users, 5)
    assert (ni == target).any()
    oi, _ = TwoTowerMF.recommend_batch(m, users, 5)
    assert not (oi == target).any()
    # out-of-range rows refused
    with pytest.raises(ValueError):
        m.with_row_updates(item_rows={n_items: boost})
    with pytest.raises(ValueError, match=r"shape|width"):
        m.with_row_updates(item_rows={1: np.ones(RANK, np.float32)})


def test_stale_overlay_reclusters_past_threshold(two_stage_sharded_env):
    """Past PIO_STREAM_STALE_REBUILD_FRAC of a shard stale, the delta
    apply re-clusters THAT shard from current rows — the overlay cannot
    grow without bound (the per-shard twin of the single-host rebuild)."""
    n_items = 20_000
    two_stage_sharded_env.setenv("PIO_STREAM_STALE_REBUILD_FRAC", "0.001")
    m = _model(n_items=n_items)
    m.prepare_for_serving()
    rows_per_shard = m._sharded.spec.rows_per_shard
    # 10 rows in shard 0 (> 0.1% of 5000) and none elsewhere
    item_rows = {i: np.ones(RANK + 1, np.float32) for i in range(10)}
    new = m.with_row_updates(item_rows=item_rows)
    assert new._sharded.ivf[0].stale_count == 0      # re-clustered
    assert new._sharded.ivf[0] is not m._sharded.ivf[0]
    assert new._sharded.ivf[1] is m._sharded.ivf[1]  # untouched, shared
    assert rows_per_shard == 5000


def test_serve_shards_fewer_than_trained(shard_env):
    """Serving with FEWER shards than the table trained over (its padding
    multiple exceeds the serve one) must re-pad, not crash."""
    from incubator_predictionio_tpu.sharding.serve import ShardedServing

    import jax
    import jax.numpy as jnp

    n_items, n_users = 100, 90  # pads to 104/96 over 8 train shards
    rng = np.random.default_rng(2)
    ue = jnp.asarray(np.pad(
        rng.normal(size=(n_users, RANK + 1)).astype(np.float32),
        ((0, 6), (0, 0))))
    ie = jnp.asarray(np.pad(
        rng.normal(size=(n_items, RANK + 1)).astype(np.float32),
        ((0, 4), (0, 0))))
    sh = ShardedServing.build_device(
        {"ue": ue, "ie": ie}, n_users, n_items, RANK, 1.0, 10, 4)
    assert sh.device.n_p == 100  # serve padding, not the trained 104
    m = TwoTowerModel(mean=1.0, config=TwoTowerConfig(rank=RANK))
    m._tables = {"ue": ue, "ie": ie}
    m._n_users, m._n_items = n_users, n_items
    idx, sc = sh.search_exact(m, np.arange(5, dtype=np.int32), 10)
    assert idx.shape == (5, 10) and np.isfinite(np.asarray(sc)).all()
    assert int(np.asarray(idx).max()) < n_items
    del jax


def test_restore_shards_clamps_forced_count(shard_env):
    """A forced shard count above the device count must clamp on the
    restore path exactly like a fresh prepare does — the same persisted
    model has to redeploy under the env that served it in-process."""
    from incubator_predictionio_tpu.sharding import serve as shard_serve
    from incubator_predictionio_tpu.utils.checkpoint import row_sharding_for

    shard_env.setenv("PIO_SHARD_SERVE", "1")
    shard_env.setenv("PIO_SHARD_SERVE_SHARDS", "16")  # > the 8 devices
    s = shard_serve.restore_shards(1_000_000, RANK, trained_shards=8)
    assert s == 8
    ctx = MeshContext.create(axes={"data": 8})
    sharding = row_sharding_for(ctx, 1_000_000 - 1_000_000 % 8,
                                serve_shards=s)
    assert not sharding.is_fully_replicated  # landed sharded, no crash


def test_device_delta_keeps_persisted_whole_catalog_ivf(sharded_fit,
                                                       shard_env):
    """A delta on a device-sharded model must not drop a persisted
    whole-catalog _ivf (kept, overlaid, for a later mode flip)."""
    from incubator_predictionio_tpu.serving import ann

    m = sharded_fit()
    # a whole-catalog index persisted from a pre-sharding deployment
    m._ivf = ann.build_ivf(*m._host_item_table(),
                           key=ann.build_key(m.n_items))
    shard_env.setenv("PIO_SHARD_SERVE", "1")
    shard_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    m.prepare_for_serving(host_max_elements=0)
    assert m._sharded is not None and m._sharded.device is not None
    new = m.with_row_updates(item_rows={5: np.ones(RANK + 1, np.float32)})
    assert new._ivf is not None
    assert new._ivf.stale_count == 1  # moved row overlaid, not stale-served


def test_format_index_stats_handles_sharded_models(two_stage_sharded_env):
    """pio-tpu index on a sharded deployment renders the per-shard IVF
    summary instead of crashing on the list-shaped index stats."""
    from incubator_predictionio_tpu.tools.cli import format_index_stats

    m = _model(n_items=20_000)
    m.prepare_for_serving()
    assert isinstance(m.serving_info()["index"], list)

    class FakeRec:
        def serving_info(self):
            return m.serving_info()

    text = "\n".join(format_index_stats([FakeRec()]))
    assert "per-shard IVF over 4 shards" in text
    assert "pio-tpu shards" in text


# -- train → save → deploy ---------------------------------------------------

@pytest.mark.multichip
def test_sharded_fit_save_load_serve_roundtrip(sharded_fit, shard_env,
                                               tmp_path, monkeypatch):
    """RecModel.save/load round-trips the sharded tables (orbax) + the
    per-shard IVF sidecar; the restored model lands straight in a sharded
    layout and serves identically."""
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    from incubator_predictionio_tpu.data.bimap import BiMap
    from incubator_predictionio_tpu.templates.recommendation import RecModel

    shard_env.setenv("PIO_SHARD_SERVE", "1")
    shard_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    mf = sharded_fit()
    maps = (BiMap({f"u{i}": i for i in range(mf.n_users)}),
            BiMap({f"i{i}": i for i in range(mf.n_items)}))
    model = RecModel(mf, *maps)
    ctx = MeshContext.create(axes={"data": 2, "model": 4})
    assert model.save("shard_inst", None, ctx) is True
    loaded = RecModel.load("shard_inst", None, ctx)
    assert loaded.mf.device_resident
    assert loaded.mf._shard_spec is not None
    mf.prepare_for_serving(host_max_elements=0)
    loaded.mf.prepare_for_serving(host_max_elements=0)
    assert loaded.mf._sharded is not None
    users = np.arange(8, dtype=np.int32)
    ia, sa = TwoTowerMF.recommend_batch(mf, users, 5)
    ib, sb = TwoTowerMF.recommend_batch(loaded.mf, users, 5)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_array_equal(
        np.asarray(sa, np.float32).view(np.int32),
        np.asarray(sb, np.float32).view(np.int32))


def test_persisted_shard_ivf_skips_recluster(two_stage_sharded_env):
    """Pickle round trip keeps the slim per-shard clustering; a fresh
    prepare rehydrates (no re-cluster) when the build keys still match."""
    import pickle

    n_items = 20_000
    m = _model(n_items=n_items)
    m.prepare_for_serving()
    keys = [i.key for i in m._shard_ivf]
    blob = pickle.dumps(m)
    back = pickle.loads(blob)
    assert back._shard_ivf is not None
    assert all(not i.hydrated for i in back._shard_ivf)  # slim persisted
    back.prepare_for_serving()
    assert [i.key for i in back._shard_ivf] == keys
    # same object identity ⇒ rehydrated, not rebuilt
    assert all(a is b for a, b in zip(back._shard_ivf, back._sharded.ivf))
    users = np.arange(4, dtype=np.int32)
    ia, _ = TwoTowerMF.recommend_batch(m, users, 10)
    ib, _ = TwoTowerMF.recommend_batch(back, users, 10)
    assert _recall(ia, ib) >= 0.95


# -- reporting / CLI ---------------------------------------------------------

def test_shard_info_and_cli_formatting(two_stage_sharded_env):
    from incubator_predictionio_tpu.tools.cli import format_shard_stats

    n_items = 20_000
    m = _model(n_items=n_items)
    m.prepare_for_serving()
    info = m.shard_info()
    assert info["sharded"] and info["n_shards"] == 4
    assert info["items"]["n_rows"] == n_items
    assert info["merge_fanin"] == 4 * min(m._plan.serve_k, info["items"]["rows_per_shard"])

    class FakeRec:
        def shard_info(self):
            return info

        def serving_info(self):
            return m.serving_info()

    lines = format_shard_stats([FakeRec()])
    text = "\n".join(lines)
    assert "SHARDED ×4" in text
    assert "merge fan-in" in text and "per-shard IVF" in text

    # unsharded model renders the single-chip plan + budget verdict
    two_stage_sharded_env.setenv("PIO_SHARD_SERVE", "0")
    two_stage_sharded_env.setenv("PIO_SHARD_HBM_BUDGET", "1MB")
    um = _model(n_items=n_items)
    info_u = um.shard_info()
    assert not info_u["sharded"] and info_u["requires_sharding"]
    lines = format_shard_stats([type("R", (), {
        "shard_info": lambda self: info_u})()])
    assert any("UNSHARDED" in ln for ln in lines)
    assert any("EXCEEDS one chip" in ln for ln in lines)


def test_health_sharding_summary(two_stage_sharded_env):
    """The query server's /health deployment block names per-model shard
    state (what fleet tooling reads)."""
    from incubator_predictionio_tpu.server.query_server import QueryServer

    m = _model(n_items=20_000)
    m.prepare_for_serving()

    class Deployed:
        models = [type("R", (), {"serving_info": staticmethod(
            lambda: m.serving_info())})()]

    qs = QueryServer.__new__(QueryServer)
    qs.deployed = Deployed()
    out = qs._sharding_summary()
    assert out == [{"nShards": 4, "mode": "host",
                    "mergeFanin": m._sharded.info()["merge_fanin"],
                    # fleet tooling reads the row split per shard id
                    # (pio-tpu shards / health coverage rows)
                    "shardIds": [0, 1, 2, 3],
                    "rows": [[0, 5000], [5000, 10000],
                             [10000, 15000], [15000, 20000]]}]


def test_auto_mode_stays_off_for_small_and_unsharded(shard_env):
    """auto must not disturb existing serving paths: small catalogs stay
    host; replicated device tables stay on the single-device path."""
    m = _model(n_items=300)
    m.prepare_for_serving()
    assert m._sharded is None and m._host_items is not None
    info = m.shard_info()
    assert not info["sharded"] and not info["requires_sharding"]


# -- int8 per-shard scoring composes with shard-serve (ISSUE 18) -------------

def test_sharded_int8_recall_floor_zero_full_gathers(two_stage_sharded_env):
    """PIO_SHARD_SERVE=1 + PIO_RETRIEVAL_QUANTIZE=1: every shard scores
    int8 coarse + int8 rerank, holds the 0.95 recall@10 floor vs the exact
    oracle, performs ZERO full-table gathers, and reports the quantization
    mode + bytes saved through shard info."""
    from incubator_predictionio_tpu.serving import ann as ann_mod

    n_items = 20_000
    oracle = _model(n_items=n_items)
    two_stage_sharded_env.setenv("PIO_SHARD_SERVE", "0")
    two_stage_sharded_env.setenv("PIO_RETRIEVAL_MODE", "exact")
    oracle.prepare_for_serving()

    two_stage_sharded_env.setenv("PIO_SHARD_SERVE", "1")
    two_stage_sharded_env.setenv("PIO_RETRIEVAL_MODE", "two_stage")
    two_stage_sharded_env.setenv("PIO_RETRIEVAL_QUANTIZE", "1")
    m = _model(n_items=n_items)
    m.prepare_for_serving()
    assert m._shard_ivf is not None and len(m._shard_ivf) == 4
    assert all(i is not None and i.quantized for i in m._shard_ivf)

    rng = np.random.default_rng(6)
    users = rng.integers(0, 160, 32).astype(np.int32)
    gathers0 = shard_metrics.FULL_GATHERS._default().value
    rerank0 = ann_mod.INT8_RERANK._default().value
    oi, _ = TwoTowerMF.recommend_batch(oracle, users, 10)
    gi, gs = TwoTowerMF.recommend_batch(m, users, 10)
    assert np.mean([len(set(a) & set(b)) / 10
                    for a, b in zip(oi, gi)]) >= 0.95
    assert np.isfinite(gs).all()
    # zero full-table gathers; and the batch is accounted in pio_shard_*,
    # never once-per-shard in the single-host int8 counters
    assert shard_metrics.FULL_GATHERS._default().value == gathers0
    assert ann_mod.INT8_RERANK._default().value == rerank0

    info = m.shard_info()
    assert info.get("quantized")
    assert info.get("rerank_bytes_saved", 0) > 0
    # pio-tpu shards renders the mode + per-shard HBM savings
    from incubator_predictionio_tpu.tools.cli import format_shard_stats

    class FakeRec:
        def shard_info(self):
            return info

        def serving_info(self):
            return m.serving_info()

    text = "\n".join(format_shard_stats([FakeRec()]))
    assert "int8 rerank/shard" in text
