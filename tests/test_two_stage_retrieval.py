"""Two-stage retrieval (IVF coarse pruning + exact rerank) vs the exact
full-catalog path as the recall oracle, plus the grouped_topk tie-parity
suite and the recommend_batch degenerate-num / scratch-buffer satellites.

All catalogs here are SMALL and seeded (tier-1 fast); the two-stage path is
forced on via ``PIO_RETRIEVAL_MODE`` so the auto threshold keeps every other
suite's toy models on the bitwise-parity exact path.
"""

import pickle

import numpy as np
import pytest

from incubator_predictionio_tpu.models.two_tower import (
    TwoTowerConfig,
    TwoTowerMF,
    TwoTowerModel,
)
from incubator_predictionio_tpu.serving import ann
from incubator_predictionio_tpu.serving.topk import grouped_topk, topk_row


def _clustered_model(seed=1, n_users=160, n_items=4000, rank=16,
                     n_concepts=64, sigma=0.5):
    """Mixture-of-concepts towers — the geometry trained MF factors have
    (items cluster; users live in the same space), which is what IVF
    pruning exploits. IID-gaussian catalogs are the no-structure worst
    case and are NOT what the recall floor is specified over."""
    rng = np.random.default_rng(seed)
    concepts = rng.standard_normal((n_concepts, rank)).astype(np.float32)
    item = concepts[rng.integers(0, n_concepts, n_items)] \
        + sigma * rng.standard_normal((n_items, rank)).astype(np.float32)
    user = concepts[rng.integers(0, n_concepts, n_users)] \
        + sigma * rng.standard_normal((n_users, rank)).astype(np.float32)
    return TwoTowerModel(
        user_emb=user.astype(np.float32),
        item_emb=item.astype(np.float32),
        user_bias=(rng.standard_normal(n_users) * 0.1).astype(np.float32),
        item_bias=(rng.standard_normal(n_items) * 0.1).astype(np.float32),
        mean=3.0,
        config=TwoTowerConfig(rank=rank),
    )


@pytest.fixture
def two_stage_env(monkeypatch):
    """Force the two-stage path with a pinned, comfortable probe width."""
    monkeypatch.setenv("PIO_RETRIEVAL_MODE", "two_stage")
    monkeypatch.setenv("PIO_RETRIEVAL_NPROBE", "16")
    # these tests exercise the fp32 exact-math rerank (the recall oracle
    # path); int8 is the serving default, so opt out explicitly
    monkeypatch.setenv("PIO_RETRIEVAL_QUANTIZE", "0")
    monkeypatch.delenv("PIO_RETRIEVAL_PARTITIONS", raising=False)


def _exact_oracle(seed=1):
    """An exact-path twin: prepared with the mode pinned to ``exact`` so no
    index is built — its recommend_batch stays full-catalog even while the
    surrounding test forces two_stage."""
    import os

    model = _clustered_model(seed=seed)
    prev = os.environ.get("PIO_RETRIEVAL_MODE")
    os.environ["PIO_RETRIEVAL_MODE"] = "exact"
    try:
        model.prepare_for_serving()
    finally:
        if prev is None:
            os.environ.pop("PIO_RETRIEVAL_MODE", None)
        else:
            os.environ["PIO_RETRIEVAL_MODE"] = prev
    assert model._ivf is None
    return model


# -- satellite: num <= 0 ----------------------------------------------------

def test_num_nonpositive_returns_empty_host_and_device():
    from incubator_predictionio_tpu.utils import jitstats

    users = np.asarray([0, 3, 7], np.int32)
    host_m = _clustered_model()
    host_m.prepare_for_serving()
    dev_m = _clustered_model()
    dev_m.prepare_for_serving(host_max_elements=0)  # force the device path
    jitstats.reset()
    for model in (host_m, dev_m):
        for num in (0, -5):
            idx, scores = TwoTowerMF.recommend_batch(model, users, num)
            assert idx.shape == (3, 0) and scores.shape == (3, 0)
    # the device path must NOT have dispatched (pre-fix it passed k=num
    # straight into top-k); empty answers are host-side constants
    assert jitstats.count() == 0
    idx, scores = TwoTowerMF.recommend(host_m, 0, 0)
    assert idx.shape == (0,) and scores.shape == (0,)


# -- satellite: row-mask pad scratch buffer ---------------------------------

def test_row_mask_pad_buffer_reused_and_zeroed():
    from incubator_predictionio_tpu.models.two_tower import (
        _row_mask_pad_buffer,
    )

    a = _row_mask_pad_buffer(8, 100)
    a[3, 50] = -np.inf
    b = _row_mask_pad_buffer(8, 100)
    assert b is a  # same per-thread scratch, not a fresh allocation
    assert np.all(b == 0.0)  # and re-zeroed — no stale mask rows
    c = _row_mask_pad_buffer(16, 100)
    assert c is not a and c.shape == (16, 100)


def test_row_mask_dispatches_no_stale_leakage():
    """Two consecutive row-masked device dispatches with different masks:
    the second result must reflect ONLY its own mask (the scratch reuse
    must never leak the first batch's -inf rows)."""
    model = _clustered_model(seed=9)
    model.prepare_for_serving(host_max_elements=0)
    users = np.asarray([1, 2, 3], np.int32)
    n = model.n_items
    base_idx, _ = TwoTowerMF.recommend_batch(model, users, 5)
    m1 = np.zeros((3, n), np.float32)
    m1[:, base_idx[0]] = -np.inf  # ban row 0's favorites everywhere
    i1, _ = TwoTowerMF.recommend_batch(model, users, 5, row_mask=m1)
    assert not (set(base_idx[0].tolist()) & set(np.unique(i1).tolist()))
    m2 = np.zeros((3, n), np.float32)  # second batch: NO bans
    i2, s2 = TwoTowerMF.recommend_batch(model, users, 5, row_mask=m2)
    np.testing.assert_array_equal(i2, base_idx)


# -- satellite: grouped_topk tie-resolution parity --------------------------

def _serial_chain(row: np.ndarray, num: int):
    part = np.argpartition(-row, num - 1)[:num]
    order = np.argsort(-row[part])
    top = part[order]
    return top, row[top]


@pytest.mark.parametrize("case", ["heavy_ties", "all_neginf", "num_eq_ncols"])
def test_grouped_topk_tie_parity_adversarial(case):
    rng = np.random.default_rng(42)
    b, n = 12, 64
    if case == "heavy_ties":
        # scores drawn from 3 distinct values: ties everywhere
        scored = rng.integers(0, 3, (b, n)).astype(np.float32)
        nums = [int(x) for x in rng.integers(1, n + 1, b)]
    elif case == "all_neginf":
        scored = np.full((b, n), -np.inf, np.float32)
        scored[0, 5] = 1.0  # one row with a single finite survivor
        nums = [10] * b
    else:
        scored = rng.standard_normal((b, n)).astype(np.float32)
        scored[:, ::7] = 0.5  # tie stripes
        nums = [n] * b
    got = grouped_topk(scored, nums)
    for r in range(b):
        want_idx, want_scores = _serial_chain(scored[r], nums[r])
        np.testing.assert_array_equal(got[r][0], want_idx)
        np.testing.assert_array_equal(got[r][1], want_scores)


def test_grouped_topk_nonpositive_and_mixed_nums():
    scored = np.arange(12, dtype=np.float32).reshape(2, 6)
    out = grouped_topk(scored, [0, -3])
    assert all(len(i) == 0 and len(s) == 0 for i, s in out)
    out = grouped_topk(scored, [2, 6])
    np.testing.assert_array_equal(out[0][0], [5, 4])
    np.testing.assert_array_equal(out[1][0], [5, 4, 3, 2, 1, 0])


def test_topk_row_matches_grouped_chain():
    rng = np.random.default_rng(3)
    scores = rng.integers(0, 4, 50).astype(np.float32)  # heavy ties
    for num in (1, 7, 50, 60):
        got = topk_row(scores, num)
        want, _ = _serial_chain(scores, min(num, 50))
        np.testing.assert_array_equal(got, want)
    assert topk_row(scores, 0).shape == (0,)


# -- IVF build ---------------------------------------------------------------

def test_ivf_build_partitions_cover_catalog(two_stage_env):
    model = _clustered_model()
    model.prepare_for_serving()
    ivf = model._ivf
    assert ivf is not None
    # every catalog row lands in exactly one partition
    np.testing.assert_array_equal(
        np.sort(ivf.member_ids), np.arange(model.n_items))
    assert ivf.offsets[0] == 0 and ivf.offsets[-1] == model.n_items
    assert np.all(np.diff(ivf.offsets) >= 0)
    stats = ivf.stats()
    assert stats["n_partitions"] == ivf.n_partitions
    assert stats["partition_size_min"] >= 0
    assert stats["empty_partitions"] == int(
        (np.diff(ivf.offsets) == 0).sum())
    assert stats["default_nprobe"] == 16  # pinned by the fixture
    # rerank rows really are the catalog rows in member order
    np.testing.assert_allclose(
        ivf.emb_m, np.asarray(model.item_emb)[ivf.member_ids])


# -- the clustering's programs against the numpy reference (ISSUE 44) --------
#
# build_ivf clusters with jitted programs (ops/retrieval.py ivf_*); the numpy
# Lloyd loop it ran before is tests/fixtures/ivf_reference.py. Same key, same
# generator, same order of draws: the index is the same index, up to rows a
# float32 segment sum moves across a near-tie where float64 bincount did not.

def _parity_catalog(case):
    rows, rank, partitions, sample = {
        "4096x33": (4096, 32, 64, 65_536),
        "20000x129": (20_000, 128, 141, 8192),
        "dead_cluster": (4096, 32, 64, 65_536),
    }[case]
    model = _clustered_model(seed=3, n_items=rows, rank=rank)
    emb, bias = model.item_emb.copy(), model.item_bias.copy()
    if case == "dead_cluster":
        # a third of the catalog is ONE row: the seeds draw it many times
        # over, and of centroids that are equal only the first gets members
        twin = np.random.default_rng(4).choice(rows, rows // 3, replace=False)
        emb[twin], bias[twin] = emb[twin[0]], bias[twin[0]]
    key = dict(ann.build_key(rows), n_partitions=partitions, quantize=False,
               train_sample=sample)
    return emb, bias, key


def _partition_of(ivf):
    out = np.empty(ivf.n_items, np.int32)
    out[ivf.member_ids] = np.repeat(
        np.arange(ivf.n_partitions), np.diff(ivf.offsets))
    return out


def _reference_index(emb, bias, key):
    """The index the numpy reference's clustering gives, laid out by the
    package's own rehydrate."""
    from tests.fixtures import ivf_reference

    cent, assign, reseeded = ivf_reference.cluster(emb, bias, key)
    sizes = np.bincount(assign, minlength=len(cent))
    index = ann.IVFIndex(
        centroids=cent,
        member_ids=np.argsort(assign, kind="stable").astype(np.int32),
        offsets=np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64),
        bias_m=None, key=dict(key)).rehydrate(emb, bias)
    return index, assign, reseeded


@pytest.mark.parametrize("case", ["4096x33", "20000x129", "dead_cluster"])
def test_clustering_programs_match_the_numpy_reference(case):
    from incubator_predictionio_tpu.obs import trace
    from incubator_predictionio_tpu.ops import retrieval
    from tests.fixtures import ivf_reference

    emb, bias, key = _parity_catalog(case)
    n = len(emb)
    trace.TRACES.clear()
    ivf = ann.build_ivf(emb, bias, key=key)
    ref, ref_assign, ref_reseeded = _reference_index(emb, bias, key)

    # a partition of the catalog, the same one
    np.testing.assert_array_equal(np.sort(ivf.member_ids), np.arange(n))
    assert ivf.offsets[0] == 0 and ivf.offsets[-1] == n
    assert np.all(np.diff(ivf.offsets) >= 0)
    assert ivf.centroids.shape == ref.centroids.shape
    assign = _partition_of(ivf)
    assert np.mean(assign == ref_assign) >= 0.995
    aug = np.concatenate([emb, bias[:, None]], axis=1).astype(np.float64)
    inertia = ((aug - ivf.centroids[assign]) ** 2).sum()
    ref_inertia = ((aug - ref.centroids[ref_assign]) ** 2).sum()
    assert inertia == pytest.approx(ref_inertia, rel=1e-4)
    # (a float32 sum over a partition's rows, thousands of them, against
    # float64's: the mean keeps four digits and more)
    np.testing.assert_allclose(ivf.centroids, ref.centroids, rtol=1e-4,
                               atol=2e-5)

    # the generator's draws: as many dead centroids re-seeded, iteration by
    # iteration (the span says how many)
    (cluster,) = [s for s in trace.TRACES.spans()
                  if s["name"] == "train.index.cluster"]
    assert cluster["attrs"]["reseeded"] == sum(ref_reseeded)
    assert sum(ref_reseeded) > 0 or case != "dead_cluster"
    assert cluster["attrs"]["rows"] == n
    assert cluster["attrs"]["partitions"] == len(ref.centroids)

    # the int8 form of the same build: the reference's members, quantized
    # by the host routine (a rounding at .5 may fall the other way)
    from incubator_predictionio_tpu.ops.retrieval import quantize_rows

    int8 = ann.build_ivf(emb, bias, key=dict(key, quantize=True))
    np.testing.assert_array_equal(int8.member_ids, ivf.member_ids)
    want_q, want_scales = quantize_rows(emb[int8.member_ids])
    assert int8.emb_q.dtype == np.int8 and int8.emb_m is None
    assert np.abs(int8.emb_q.astype(np.int32) - want_q).max() <= 1
    assert np.mean(int8.emb_q != want_q) < 1e-5
    np.testing.assert_allclose(int8.scales_m, want_scales, rtol=1e-6)
    np.testing.assert_array_equal(int8.bias_m, bias[int8.member_ids])

    # one iteration alone: a centroid's last column is its members' mean
    # bias, and one without members comes back empty for the host to re-seed
    sel = np.random.default_rng(5).choice(n, 2048, replace=False)
    train = aug[sel].astype(np.float32)
    members = ivf_reference.assign(train, ref.centroids)
    np.testing.assert_array_equal(
        retrieval.ivf_assign(train, ref.centroids, n=len(train)), members)
    cent, counts = retrieval.ivf_update(train, members, c=len(ref.centroids))
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(members, minlength=len(cent)))
    live = np.asarray(counts) > 0
    mean_bias = np.bincount(members, weights=bias[sel], minlength=len(cent))
    np.testing.assert_allclose(
        np.asarray(cent)[live, -1], mean_bias[live] / np.asarray(counts)[live],
        atol=1e-6)
    assert not np.asarray(cent)[~live].any()


def test_assignment_in_blocks_is_the_whole_products_argmax(monkeypatch):
    """The assignment goes through in blocks of rows (the last one
    overlapping where the rows do not divide) and of centroids: the numpy
    reference's answer, row for row, with rows past ``n`` left out and the
    first of equal centroids winning across blocks."""
    import jax

    from incubator_predictionio_tpu.ops import retrieval
    from tests.fixtures import ivf_reference

    rng = np.random.default_rng(0)
    rows = rng.standard_normal((10_007, 33)).astype(np.float32)
    cent = rng.standard_normal((50, 33)).astype(np.float32)
    cent[[7, 23, 41]] = cent[3]      # twins: the first one gets the rows
    want = ivf_reference.assign(rows[:10_000], cent)
    assert (want == 3).any() and not np.isin(want, (7, 23, 41)).any()
    np.testing.assert_array_equal(
        retrieval.ivf_assign(rows, cent, n=10_000), want)
    # 4 blocks of 2,500 rows and 4 of 16 centroids; then rows that do not
    # divide: 4 blocks of 2,502 over 10,007
    monkeypatch.setattr(retrieval, "ASSIGN_ROWS", 3000)
    monkeypatch.setattr(retrieval, "CENTROID_BLOCK", 16)
    blocked = jax.jit(retrieval.ivf_assign.__wrapped__, static_argnames="n")
    np.testing.assert_array_equal(blocked(rows, cent, n=10_000), want)
    np.testing.assert_array_equal(
        blocked(rows, cent, n=10_007), ivf_reference.assign(rows, cent))


def test_clustering_programs_keep_the_reference_indexs_recall(two_stage_env):
    """recall@10 of ``IVFIndex.search`` against the exact scores, over the
    index the programs build and over the reference's: the same to 0.005."""
    model = _clustered_model()
    key = ann.build_key(model.n_items)
    built = ann.build_ivf(model.item_emb, model.item_bias, key=key)
    ref, _, _ = _reference_index(model.item_emb, model.item_bias, key)
    exact = model.user_emb @ model.item_emb.T + model.item_bias[None, :]
    oracle = np.argsort(-exact, axis=1, kind="stable")[:, :10]
    recalls = []
    for index in (built, ref):
        got, _ = index.search(model.user_emb, model.user_bias, model.mean,
                              10, nprobe=8, observe=False)
        recalls.append(_recall(oracle, got))
    assert min(recalls) >= 0.9
    assert abs(recalls[0] - recalls[1]) <= 0.005


def test_small_catalog_auto_mode_stays_exact_parity(monkeypatch):
    """Below PIO_RETRIEVAL_MIN_ITEMS the auto mode must not build an index
    — small templates keep bitwise parity with the seed behavior."""
    monkeypatch.delenv("PIO_RETRIEVAL_MODE", raising=False)
    model = _clustered_model()
    model.prepare_for_serving()
    assert model._ivf is None
    oracle = _exact_oracle()
    users = np.arange(32, dtype=np.int32)
    i1, s1 = TwoTowerMF.recommend_batch(model, users, 10)
    i2, s2 = TwoTowerMF.recommend_batch(oracle, users, 10)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(s1, s2)


# -- recall floor + rule-filter correctness through both stages -------------

RECALL_FLOOR = 0.95


def _recall(oracle_idx, got_idx):
    k = oracle_idx.shape[1]
    return np.mean([
        len(set(oracle_idx[r]) & set(got_idx[r])) / k
        for r in range(len(oracle_idx))])


def _filter_cases(oracle_model, users):
    """The four rule-filter kinds recommend_batch carries: shared exclude,
    per-row ban mask, per-row whitelist mask, and exclude+row-mask
    combined (plus unfiltered as the baseline case)."""
    n = oracle_model.n_items
    b = len(users)
    rng = np.random.default_rng(7)
    exclude = rng.choice(n, 40, replace=False).astype(np.int64)
    ban = np.zeros((b, n), np.float32)
    for r in range(b):
        ban[r, rng.choice(n, 25, replace=False)] = -np.inf
    white = np.full((b, n), -np.inf, np.float32)
    for r in range(b):
        white[r, rng.choice(n, 400, replace=False)] = 0.0
    return {
        "none": (None, None),
        "exclude": (exclude, None),
        "row_ban": (None, ban),
        "row_whitelist": (None, white),
        "exclude_plus_row": (exclude, ban),
    }


@pytest.mark.parametrize(
    "kind", ["none", "exclude", "row_ban", "row_whitelist",
             "exclude_plus_row"])
def test_two_stage_recall_floor_and_mask_correctness(two_stage_env, kind):
    oracle = _exact_oracle()
    model = _clustered_model()
    model.prepare_for_serving()
    assert model._ivf is not None
    users = np.arange(64, dtype=np.int32)
    exclude, row_mask = _filter_cases(oracle, users)[kind]
    oi, oscores = TwoTowerMF.recommend_batch(
        oracle, users, 10, exclude=exclude, row_mask=row_mask)
    gi, gscores = TwoTowerMF.recommend_batch(
        model, users, 10, exclude=exclude, row_mask=row_mask)
    assert gi.shape == (64, 10)
    # (1) recall floor against the exact oracle
    assert _recall(oi, gi) >= RECALL_FLOOR
    # (2) masked items NEVER appear with a finite score: a filtered
    # candidate must not displace an unfiltered one
    for r in range(64):
        finite = np.isfinite(gscores[r])
        if exclude is not None:
            assert not (set(exclude.tolist()) & set(gi[r][finite].tolist()))
        if row_mask is not None:
            assert np.all(row_mask[r, gi[r][finite]] == 0.0)
    # (3) wherever the oracle's whole top-k survives pruning, the
    # two-stage answer IS the oracle's answer
    q = np.asarray(model.user_emb, np.float32)
    checked = 0
    for r in range(64):
        cands = set(model._ivf.candidate_ids(q[users[r]], 16).tolist())
        if set(oi[r].tolist()) <= cands and np.isfinite(oscores[r]).all():
            np.testing.assert_array_equal(gi[r], oi[r])
            np.testing.assert_allclose(gscores[r], oscores[r],
                                       rtol=1e-5, atol=1e-5)
            checked += 1
    assert checked > 0  # the property was actually exercised


def test_two_stage_quantized_rerank(two_stage_env, monkeypatch):
    """int8 rerank storage (quantize_rows machinery): a coarser score, so a
    slightly looser floor — and mask correctness must be unaffected."""
    monkeypatch.setenv("PIO_RETRIEVAL_QUANTIZE", "1")
    oracle = _exact_oracle()
    model = _clustered_model()
    model.prepare_for_serving()
    assert model._ivf.quantized and model._ivf.emb_m is None
    users = np.arange(48, dtype=np.int32)
    exclude = np.arange(0, 30, dtype=np.int64)
    oi, _ = TwoTowerMF.recommend_batch(oracle, users, 10, exclude=exclude)
    gi, gs = TwoTowerMF.recommend_batch(model, users, 10, exclude=exclude)
    assert _recall(oi, gi) >= 0.9
    for r in range(48):
        finite = np.isfinite(gs[r])
        assert not (set(range(30)) & set(gi[r][finite].tolist()))


def test_two_stage_falls_back_when_candidates_short(two_stage_env,
                                                    monkeypatch):
    """num bigger than the probe can cover → the exact path answers (and
    the fallback counter says so); results equal the exact oracle's."""
    monkeypatch.setenv("PIO_RETRIEVAL_NPROBE", "1")
    model = _clustered_model()
    model.prepare_for_serving()
    ivf = model._ivf
    num = int(np.diff(ivf.offsets).max()) + 1  # beats ANY single partition
    before = ann.FALLBACKS._default().value
    users = np.arange(8, dtype=np.int32)
    gi, gs = TwoTowerMF.recommend_batch(model, users, num)
    assert ann.FALLBACKS._default().value == before + 1
    oracle = _exact_oracle()
    oi, oscores = TwoTowerMF.recommend_batch(oracle, users, num)
    np.testing.assert_array_equal(gi, oi)
    np.testing.assert_allclose(gs, oscores, rtol=1e-5, atol=1e-5)


def test_two_stage_narrow_whitelist_falls_back_not_masked(two_stage_env):
    """A whitelist narrower than the probe's coverage: the probed
    partitions hold plenty of RAW candidates but fewer than ``num``
    finite-scored ones after the filter — the pruned path must fall back
    to the exact path (which sees the whole catalog), never pad the
    answer with masked (-inf) items."""
    oracle = _exact_oracle()
    model = _clustered_model()
    model.prepare_for_serving()
    users = np.arange(8, dtype=np.int32)
    n = model.n_items
    q = np.asarray(model.user_emb, np.float32)
    rng = np.random.default_rng(3)
    white = np.full((len(users), n), -np.inf, np.float32)
    for r, u in enumerate(users):
        cands = set(model._ivf.candidate_ids(q[u], 16).tolist())
        inside = np.asarray(sorted(cands))
        outside = np.asarray(sorted(set(range(n)) - cands))
        # 2 probe-reachable + 10 unreachable whitelisted items: the probe
        # can never place num=10 finite candidates; the catalog trivially can
        pick = np.concatenate([rng.choice(inside, 2, replace=False),
                               rng.choice(outside, 10, replace=False)])
        white[r, pick] = 0.0
    before = ann.FALLBACKS._default().value
    gi, gs = TwoTowerMF.recommend_batch(model, users, 10, row_mask=white)
    assert ann.FALLBACKS._default().value == before + 1
    oi, oscores = TwoTowerMF.recommend_batch(oracle, users, 10, row_mask=white)
    np.testing.assert_array_equal(gi, oi)
    np.testing.assert_allclose(gs, oscores, rtol=1e-5, atol=1e-5)
    for r in range(len(users)):
        # zero masked items in the served answer, finite-scored or not
        assert np.all(white[r, gi[r]] == 0.0)


def test_search_num_nonpositive_public_api(two_stage_env):
    """IVFIndex.search is exported via serving/__init__ — the num <= 0 edge
    must answer empty there too, not only behind recommend_batch's guard."""
    model = _clustered_model()
    model.prepare_for_serving()
    q = np.asarray(model.user_emb, np.float32)[:3]
    ub = np.asarray(model.user_bias, np.float32)[:3]
    for num in (0, -5):
        idx, scores = model._ivf.search(q, ub, model.mean, num)
        assert idx.shape == (3, 0) and scores.shape == (3, 0)


def test_train_builds_index_for_persistence(two_stage_env):
    """The standard lifecycle is train → persist → deploy: the index must
    exist BEFORE persistence (ALSAlgorithm.train builds it when the catalog
    qualifies), or 'redeploys skip the re-cluster' could never engage —
    RecModel.save / default pickling run at train time, deploy never
    re-saves."""
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.templates.recommendation import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        TrainingData,
    )

    rng = np.random.default_rng(5)
    n, n_users, n_items = 600, 40, 80
    td = TrainingData(
        user_idx=rng.integers(0, n_users, n).astype(np.int32),
        item_idx=rng.integers(0, n_items, n).astype(np.int32),
        ratings=(1 + 4 * rng.random(n)).astype(np.float32),
        user_vocab=np.asarray([f"u{i}" for i in range(n_users)]),
        item_vocab=np.asarray([f"i{i}" for i in range(n_items)]),
    )
    ctx = MeshContext.create()  # all host devices on the data axis
    algo = ALSAlgorithm(ALSAlgorithmParams(
        rank=4, num_iterations=1, batch_size=256))
    model = algo.train(ctx, td)
    assert model.mf._ivf is not None  # built at train end (mode forced here)
    assert model.mf.user_emb is None or model.mf._tables is None, \
        "index build must not ensure_host a device-gather model"
    clone = pickle.loads(pickle.dumps(model))  # the default persistence path
    assert clone.mf._ivf is not None
    assert clone.mf._ivf.matches(model.mf._ivf.key)
    clone.mf.prepare_for_serving()  # rehydrates the slim-persisted index
    assert clone.mf._ivf.hydrated
    users = np.arange(8, dtype=np.int32)
    i1, _ = TwoTowerMF.recommend_batch(model.mf, users, 5)
    i2, _ = TwoTowerMF.recommend_batch(clone.mf, users, 5)
    np.testing.assert_array_equal(i1, i2)


# -- persistence, reuse, warmup, metrics ------------------------------------

def test_index_persists_with_model_and_is_reused(two_stage_env):
    model = _clustered_model()
    model.prepare_for_serving()
    first = model._ivf
    assert first is not None
    model.prepare_for_serving()  # same knobs → reused, not re-clustered
    assert model._ivf is first
    clone = pickle.loads(pickle.dumps(model))
    assert clone._ivf is not None and clone._ivf.matches(first.key)
    np.testing.assert_array_equal(clone._ivf.member_ids, first.member_ids)
    # slim persistence: only the clustering pickles — the member-order
    # rerank tables (a full catalog copy) rehydrate at prepare time
    assert not clone._ivf.hydrated and clone._ivf.emb_m is None
    clone.prepare_for_serving()  # persisted index satisfies the build key
    assert clone._ivf.hydrated
    np.testing.assert_array_equal(clone._ivf.bias_m, first.bias_m)
    np.testing.assert_array_equal(
        clone._ivf.centroids, first.centroids)
    users = np.arange(16, dtype=np.int32)
    i1, s1 = TwoTowerMF.recommend_batch(model, users, 10)
    i2, s2 = TwoTowerMF.recommend_batch(clone, users, 10)
    np.testing.assert_array_equal(i1, i2)


def test_build_index_opt_out(two_stage_env):
    """Templates whose serving path never calls recommend_batch (ecommerce)
    opt out of the deploy-time clustering."""
    model = _clustered_model()
    model.prepare_for_serving(build_index=False)
    assert model._ivf is None


def test_index_rebuilds_when_knobs_change(two_stage_env, monkeypatch):
    model = _clustered_model()
    model.prepare_for_serving()
    first = model._ivf
    monkeypatch.setenv("PIO_RETRIEVAL_PARTITIONS", "13")
    model.prepare_for_serving()
    assert model._ivf is not first and model._ivf.n_partitions == 13


def test_warmup_primes_two_stage_without_new_executables(two_stage_env):
    from incubator_predictionio_tpu.utils import jitstats

    model = _clustered_model()
    model.prepare_for_serving(serve_k=10, host_max_elements=0)
    jitstats.reset()
    warmed = model.warmup(max_batch=4)
    assert warmed == 3  # buckets 1/2/4
    # the EXACT executables (the two-stage fallback) must still have been
    # pre-compiled: plain + row-mask variant per bucket
    assert jitstats.count() == 6
    before = ann.TWO_STAGE_BATCHES._default().value
    users = np.arange(16, dtype=np.int32)
    idx, _ = TwoTowerMF.recommend_batch(model, users, 10)
    assert idx.shape == (16, 10)
    # the two-stage dispatch is host-side: the executable gauge stays flat
    assert jitstats.count() == 6
    assert ann.TWO_STAGE_BATCHES._default().value == before + 1


def test_retrieval_metrics_recorded(two_stage_env):
    model = _clustered_model()
    model.prepare_for_serving()
    coarse0 = ann.COARSE_SEC._default().snapshot()[2]
    rerank0 = ann.RERANK_SEC._default().snapshot()[2]
    cand0 = ann.CANDIDATES._default().snapshot()[2]
    users = np.arange(12, dtype=np.int32)
    TwoTowerMF.recommend_batch(model, users, 10)
    assert ann.COARSE_SEC._default().snapshot()[2] == coarse0 + 1
    assert ann.RERANK_SEC._default().snapshot()[2] == rerank0 + 1
    assert ann.CANDIDATES._default().snapshot()[2] == cand0 + 12  # per query


def test_serving_info_reports_two_stage(two_stage_env):
    model = _clustered_model()
    model.prepare_for_serving()
    info = model.serving_info()
    assert info["retrieval_mode"] == "two_stage"
    assert info["index"]["n_items"] == model.n_items


def test_cli_index_stats_formatting(two_stage_env):
    from incubator_predictionio_tpu.tools.cli import format_index_stats

    indexed = _clustered_model()
    indexed.prepare_for_serving()
    plain = _exact_oracle()
    lines = format_index_stats([indexed, plain])
    text = "\n".join(lines)
    assert "retrieval=two_stage pruned=host-routine" in text
    assert "retrieval=exact pruned=none" in text
    assert f"over {indexed.n_items} items" in text
    assert "no partition index" in text  # the exact model's row


# -- int8 end to end: coarse + rerank (ISSUE 18) ----------------------------

@pytest.fixture
def int8_env(two_stage_env, monkeypatch):
    monkeypatch.setenv("PIO_RETRIEVAL_QUANTIZE", "1")


@pytest.mark.parametrize(
    "kind", ["none", "exclude", "row_ban", "row_whitelist",
             "exclude_plus_row"])
def test_int8_end_to_end_recall_floor_all_mask_kinds(int8_env, kind):
    """int8 coarse + int8 rerank (both stages quantized, one fp32 rescale
    each) holds the SAME 0.95 recall@10 floor as the fp32 two-stage path,
    through every rule-filter kind — and masked items never surface."""
    oracle = _exact_oracle()
    model = _clustered_model()
    model.prepare_for_serving()
    ivf = model._ivf
    assert ivf.quantized and ivf.emb_m is None
    assert ivf.stats()["quant_coarse"]  # the coarse stage follows storage
    users = np.arange(64, dtype=np.int32)
    exclude, row_mask = _filter_cases(oracle, users)[kind]
    coarse0 = ann.INT8_COARSE._default().value
    rerank0 = ann.INT8_RERANK._default().value
    oi, _ = TwoTowerMF.recommend_batch(
        oracle, users, 10, exclude=exclude, row_mask=row_mask)
    gi, gs = TwoTowerMF.recommend_batch(
        model, users, 10, exclude=exclude, row_mask=row_mask)
    assert gi.shape == (64, 10)
    assert _recall(oi, gi) >= RECALL_FLOOR
    # the int8 engines really served the batch (counted, attributable)
    assert ann.INT8_COARSE._default().value == coarse0 + 1
    assert ann.INT8_RERANK._default().value == rerank0 + 1
    for r in range(64):
        finite = np.isfinite(gs[r])
        if exclude is not None:
            assert not (set(exclude.tolist()) & set(gi[r][finite].tolist()))
        if row_mask is not None:
            assert np.all(row_mask[r, gi[r][finite]] == 0.0)


def test_int8_fallbacks_answer_from_exact_path(int8_env, monkeypatch):
    """Both under-coverage fallbacks (probe too narrow for num; whitelist
    narrower than the probe) keep answering from the EXACT path under int8
    — bitwise the exact oracle, never a short or quantized answer."""
    oracle = _exact_oracle()
    # (a) num bigger than any single partition at nprobe=1
    monkeypatch.setenv("PIO_RETRIEVAL_NPROBE", "1")
    model = _clustered_model()
    model.prepare_for_serving()
    num = int(np.diff(model._ivf.offsets).max()) + 1
    before = ann.FALLBACKS._default().value
    users = np.arange(8, dtype=np.int32)
    gi, gs = TwoTowerMF.recommend_batch(model, users, num)
    assert ann.FALLBACKS._default().value == before + 1
    oi, oscores = TwoTowerMF.recommend_batch(oracle, users, num)
    np.testing.assert_array_equal(gi, oi)
    np.testing.assert_allclose(gs, oscores, rtol=1e-5, atol=1e-5)
    # (b) whitelist narrower than probe coverage
    monkeypatch.setenv("PIO_RETRIEVAL_NPROBE", "16")
    model = _clustered_model()
    model.prepare_for_serving()
    n = model.n_items
    q = np.asarray(model.user_emb, np.float32)
    rng = np.random.default_rng(3)
    white = np.full((8, n), -np.inf, np.float32)
    for r, u in enumerate(users):
        cands = set(model._ivf.candidate_ids(q[u], 16).tolist())
        inside = np.asarray(sorted(cands))
        outside = np.asarray(sorted(set(range(n)) - cands))
        pick = np.concatenate([rng.choice(inside, 2, replace=False),
                               rng.choice(outside, 10, replace=False)])
        white[r, pick] = 0.0
    before = ann.FALLBACKS._default().value
    gi, gs = TwoTowerMF.recommend_batch(model, users, 10, row_mask=white)
    assert ann.FALLBACKS._default().value == before + 1
    oi, oscores = TwoTowerMF.recommend_batch(oracle, users, 10,
                                             row_mask=white)
    np.testing.assert_array_equal(gi, oi)
    np.testing.assert_allclose(gs, oscores, rtol=1e-5, atol=1e-5)


def test_int8_stats_report_bytes_saved(int8_env):
    model = _clustered_model()
    model.prepare_for_serving()
    stats = model._ivf.stats()
    n, d = model.n_items, model.config.rank
    assert stats["quantized"] and stats["quant_coarse"]
    assert stats["rerank_bytes"] == n * d + n * 4  # int8 rows + f32 scales
    assert stats["rerank_bytes_fp32"] == n * d * 4
    assert stats["bytes_saved"] == \
        stats["rerank_bytes_fp32"] - stats["rerank_bytes"]
    assert stats["bytes_saved"] > 0
    # pio-tpu index surfaces the mode + savings
    from incubator_predictionio_tpu.tools.cli import format_index_stats

    text = "\n".join(format_index_stats([model]))
    assert "int8 member rows" in text and "int8 coarse" in text
    # fp32 index reports no savings line
    fp32 = _exact_oracle()
    assert "int8" not in "\n".join(format_index_stats([fp32]))


def test_int8_search_unknown_user_vector_paths(int8_env):
    """IVFIndex.search under int8 with query vectors that did NOT come from
    the user table (the unknown-user/cold-start serving shape): the scores
    agree with the fp32 rerank formula within the quantization bound."""
    model = _clustered_model()
    model.prepare_for_serving()
    ivf = model._ivf
    rng = np.random.default_rng(11)
    q = rng.standard_normal((4, model.config.rank)).astype(np.float32)
    ub = np.zeros(4, np.float32)
    idx, scores = ivf.search(q, ub, model.mean, 10)
    assert idx.shape == (4, 10) and np.isfinite(scores).all()
    item_emb = np.asarray(model.item_emb, np.float32)
    item_bias = np.asarray(model.item_bias, np.float32)
    want = np.take_along_axis(
        q @ item_emb.T + item_bias[None, :], idx, axis=1) + model.mean
    np.testing.assert_allclose(scores, want, rtol=0.05, atol=0.05)


def test_int8_is_the_serving_default(two_stage_env, monkeypatch):
    """The tentpole contract: with NO quantize knob set, a built index
    stores and scores int8; PIO_RETRIEVAL_QUANTIZE=0 is the opt-OUT."""
    from incubator_predictionio_tpu.serving import ann

    monkeypatch.delenv("PIO_RETRIEVAL_QUANTIZE", raising=False)
    assert ann.quantize_enabled()
    model = _clustered_model()
    model.prepare_for_serving()
    assert model._ivf is not None and model._ivf.quantized
    assert model._ivf.stats()["bytes_saved"] > 0
    monkeypatch.setenv("PIO_RETRIEVAL_QUANTIZE", "0")
    assert not ann.quantize_enabled()


# -- the device leg (ISSUE 27) ----------------------------------------------
#
# IVFIndex.search is the semantic reference; IVFIndex.search_device runs the
# same two stages as three executables with one device_get. Here the kernels
# run under the Pallas interpreter on the CPU backend.

def _uneven_index(seed=5, n_items=3000, rank=16, partitions=24):
    """A seeded quantized index with uneven partitions, one of them empty
    (a twin of a live centroid that owns no members, so it IS probed)."""
    model = _clustered_model(seed=seed, n_users=96, n_items=n_items,
                             rank=rank)
    key = dict(ann.build_key(n_items), n_partitions=partitions,
               quantize=True)
    ivf = ann.build_ivf(model.item_emb, model.item_bias, key=key)
    at = int(np.argmax(np.diff(ivf.offsets))) + 1
    ivf = ann.IVFIndex(
        centroids=np.insert(ivf.centroids, at, ivf.centroids[at - 1], axis=0),
        member_ids=ivf.member_ids,
        offsets=np.insert(ivf.offsets, at, ivf.offsets[at]),
        bias_m=ivf.bias_m, key=ivf.key, emb_q=ivf.emb_q,
        scales_m=ivf.scales_m)
    sizes = np.diff(ivf.offsets)
    assert sizes[at] == 0 and sizes.max() > 2 * sizes[sizes > 0].min()
    assert ivf.prepare_device()
    return model, ivf


@pytest.fixture(scope="module")
def uneven():
    import jax.numpy as jnp

    model, ivf = _uneven_index()
    tables = (jnp.asarray(model.user_emb), jnp.asarray(model.user_bias))
    return model, ivf, tables


def _mask_forms(model, ivf, users, nprobe, num):
    """exclude / row_mask that hit what the plain search returns, so a
    mask that is not applied shows as a wrong answer."""
    q = model.user_emb[users]
    plain = ivf.search(q, model.user_bias[users], model.mean, num,
                       nprobe=nprobe, observe=False)
    exclude = np.unique(plain[0][:, :2])
    row_mask = np.zeros((len(users), model.n_items), np.float32)
    row_mask[np.arange(len(users))[:, None], plain[0][:, 2:5]] = -np.inf
    return {"exclude": (exclude, None), "row_mask": (None, row_mask),
            "both": (exclude, row_mask)}


_DEVICE_CASES = [
    (f"b{b}-{form}", b, form, 6, 10)
    for b in (1, 3, 8, 9, 33)
    for form in ("plain", "exclude", "row_mask", "both")
] + [
    # nprobe ≥ partitions: every partition probed, the empty one too
    ("nprobe_all", 5, "both", 99, 10),
    # one probe holds fewer than num candidates
    ("probe_short", 4, "plain", 1, 0),
    # the probe holds enough, the rule filters leave fewer than num
    ("survivors_short", 4, "whitelist", 6, 10),
]


@pytest.mark.parametrize(
    "b, form, nprobe, num", [c[1:] for c in _DEVICE_CASES],
    ids=[c[0] for c in _DEVICE_CASES])
def test_device_leg_matches_host_search(uneven, b, form, nprobe, num):
    model, ivf, tables = uneven
    users = (np.arange(b, dtype=np.int32) * 7 + 3) % model.n_users
    q, ub = model.user_emb[users], model.user_bias[users]
    if not num:  # probe_short: more than the largest partition holds
        num = int(np.diff(ivf.offsets).max()) + 1
    if form == "whitelist":
        inside = ivf.candidate_ids(q[1], nprobe)
        white = np.full((b, model.n_items), -np.inf, np.float32)
        white[:, inside[:num]] = 0.0
        white[1, inside[num - 1]] = -np.inf  # row 1 keeps num - 1
        exclude, row_mask = None, white
    elif form == "plain":
        exclude, row_mask = None, None
    else:
        exclude, row_mask = _mask_forms(model, ivf, users, nprobe, num)[form]
    fallbacks = ann.FALLBACKS._default().value
    engaged = ann.DEVICE_RERANK._default().value
    host = ivf.search(q, ub, model.mean, num, nprobe=nprobe,
                      exclude=exclude, row_mask=row_mask, observe=False)
    got = ivf.search_device(users, tables, model.mean, num, k=16,
                            nprobe=nprobe, exclude=exclude,
                            row_mask=row_mask, interpret=True)
    if form == "whitelist" or nprobe == 1:
        assert host is None and got is None
        assert ann.FALLBACKS._default().value == fallbacks + 1
        assert ann.DEVICE_RERANK._default().value == engaged
        return
    assert ann.FALLBACKS._default().value == fallbacks
    assert ann.DEVICE_RERANK._default().value == engaged + 1
    assert got[0].shape == host[0].shape == (b, num)
    assert got[0].dtype == np.int64 and got[1].dtype == np.float32
    np.testing.assert_allclose(got[1], host[1], rtol=0, atol=1e-5)
    # ids: equal wherever the host's own order is decided by the scores
    # (its top num + 1 all distinct: no tie inside, none at the boundary)
    wider = ivf.search(q, ub, model.mean, num + 1, nprobe=nprobe,
                       exclude=exclude, row_mask=row_mask, observe=False)
    decided = (np.diff(wider[1], axis=1) != 0).all(axis=1) \
        if wider is not None else np.ones(b, bool)
    assert decided.sum() >= b - 1
    np.testing.assert_array_equal(got[0][decided], host[0][decided])
    if exclude is not None:
        assert not np.isin(got[0], exclude).any()
    if row_mask is not None:
        assert (np.take_along_axis(row_mask, got[0], axis=1) == 0).all()


def _device_model(monkeypatch, quantize_index="1", interpret=True,
                  mode="two_stage"):
    """A model whose towers and kernels are 'on a device': the kernels under
    the Pallas interpreter, the int8 catalog + bf16 users resident."""
    monkeypatch.setenv("PIO_RETRIEVAL_MODE", mode)
    monkeypatch.setenv("PIO_RETRIEVAL_NPROBE", "16")
    monkeypatch.setenv("PIO_RETRIEVAL_QUANTIZE", quantize_index)
    if interpret:
        monkeypatch.setenv("PIO_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PIO_PALLAS_INTERPRET", raising=False)
    model = _clustered_model(n_items=2048)
    model.prepare_for_serving(quantize=True, serve_k=16, host_max_elements=0)
    return model


@pytest.mark.parametrize(
    "case", ["resident", "stale_overlay", "float32_index",
             "no_kernel_backend", "no_room", "exclude", "row_mask"])
def test_device_leg_runs_iff_resident(monkeypatch, case):
    """Which routine answers follows what prepare found resident (the serve
    plan's ``pruned``) and what the batch carries (no setting): the device
    leg iff kernels, towers and a quantized index without an overlay are on
    a device and the batch has no rule filter (a dense mask a batch costs
    more to send than the host rerank it would save); IVFIndex.search
    otherwise."""
    if case == "no_room":  # the padded layout wants over half of what is free
        monkeypatch.setattr(ann, "_device_free_bytes", lambda: 1 << 16)
    model = _device_model(
        monkeypatch, quantize_index="0" if case == "float32_index" else "1",
        interpret=case != "no_kernel_backend")
    assert model._ivf.device_ready == (
        case not in ("float32_index", "no_room", "no_kernel_backend"))
    if case == "stale_overlay":
        rows = {7: np.ones(model.config.rank + 1, np.float32)}
        moved = model.with_row_updates(item_rows=rows)
        moved.prepare_for_serving(quantize=True, serve_k=16,
                                  host_max_elements=0)
        assert moved._ivf.stale_count == 1 and not moved._ivf.device_ready
        assert model._ivf.device_ready  # the live model's view is its own
        model = moved
    on_device = case in ("resident", "exclude", "row_mask")
    assert model._plan.pruned == (
        "device-leg" if on_device else "host-routine")
    assert model.serving_info()["pruned"] == model._plan.pruned
    engaged = ann.DEVICE_RERANK._default().value
    batches = ann.TWO_STAGE_BATCHES._default().value
    users = np.arange(5, dtype=np.int32)
    filters = {"exclude": dict(exclude=np.arange(3)),
               "row_mask": dict(row_mask=np.zeros((5, model.n_items),
                                                  np.float32))}
    idx, scores = TwoTowerMF.recommend_batch(
        model, users, 10, **filters.get(case, {}))
    assert idx.shape == (5, 10) and np.isfinite(scores).all()
    assert ann.TWO_STAGE_BATCHES._default().value == batches + 1
    assert ann.DEVICE_RERANK._default().value == engaged + (case == "resident")


def test_device_leg_leaves_a_restored_tower_on_the_device(monkeypatch):
    """A restored deployment keeps its towers on the device: the leg reads
    the bfloat16 serving copy there and pulls nothing to the host; against
    the host routine over the float32 rows the answers differ by the
    bfloat16 rounding of the queries alone."""
    import jax.numpy as jnp

    host = _device_model(monkeypatch)
    fused = TwoTowerModel(mean=host.mean, config=host.config)
    fused._tables = {
        "ue": jnp.asarray(np.c_[host.user_emb, host.user_bias]),
        "ie": jnp.asarray(np.c_[host.item_emb, host.item_bias])}
    fused._n_users, fused._n_items = host.n_users, host.n_items
    fused.prepare_for_serving(quantize=True, serve_k=16, host_max_elements=0)
    assert fused._ivf.device_ready and fused.user_emb is None
    users = np.arange(12, dtype=np.int32)
    engaged = ann.DEVICE_RERANK._default().value
    idx, scores = TwoTowerMF.recommend_batch(fused, users, 10)
    assert ann.DEVICE_RERANK._default().value == engaged + 1
    assert fused.user_emb is None  # nothing was pulled to the host
    want = fused._ivf.search(host.user_emb[users], host.user_bias[users],
                             host.mean, 10, nprobe=16, observe=False)
    np.testing.assert_allclose(scores, want[1], rtol=5e-3, atol=0)
    assert (idx == want[0]).mean() > 0.8


def test_device_leg_spans_and_fallback_to_exact(monkeypatch):
    """The stage spans say where they ran; a probe too narrow for num is
    counted and answered by the exact path, as on the host."""
    from incubator_predictionio_tpu.obs import trace

    model = _device_model(monkeypatch)
    trace.TRACES.clear()
    users = np.arange(3, dtype=np.int32)
    TwoTowerMF.recommend_batch(model, users, 10)
    stages = {s["name"]: s["attrs"] for s in trace.TRACES.spans()
              if s["name"].startswith("retrieval.batch.")}
    assert stages["retrieval.batch.coarse"]["where"] == "device"
    assert stages["retrieval.batch.rerank"]["where"] == "device"
    # the plan is fixed at prepare: a narrower probe is a new prepare
    monkeypatch.setenv("PIO_RETRIEVAL_NPROBE", "1")
    model.prepare_for_serving(quantize=True, serve_k=16, host_max_elements=0)
    assert model._plan.nprobe == 1 and model._plan.pruned == "device-leg"
    num = int(np.diff(model._ivf.offsets).max()) + 1
    fallbacks = ann.FALLBACKS._default().value
    idx, _ = TwoTowerMF.recommend_batch(model, users, num)
    assert ann.FALLBACKS._default().value == fallbacks + 1
    exact, _ = TwoTowerMF.recommend_batch(model, users, num, exact=True)
    np.testing.assert_array_equal(idx, exact)
    assert ann.FALLBACKS._default().value == fallbacks + 1


@pytest.mark.parametrize("kind", ["device-leg", "host-routine", "exact"])
def test_device_leg_warmup_leaves_nothing_to_compile(monkeypatch, kind):
    """Warm-up walks the plan's warm list, and after it a dispatch at every
    serve bucket builds no executable, plain, rule-filtered or sent to the
    full-catalog scorer (the pruned path's fallback): jitstats' keys and
    the jit caches themselves stay flat."""
    from incubator_predictionio_tpu.models import two_tower
    from incubator_predictionio_tpu.ops import retrieval
    from incubator_predictionio_tpu.serving.plan import (
        ROW_MASK_MAX_ELEMENTS,
        SERVE_BUCKETS,
        WarmShape,
    )
    from incubator_predictionio_tpu.utils import jitstats

    if kind == "host-routine":  # an int8 index the device has no room for
        monkeypatch.setattr(ann, "_device_free_bytes", lambda: 1 << 16)
    model = _device_model(
        monkeypatch, mode="exact" if kind == "exact" else "two_stage")
    plan = model._plan
    assert plan.pruned == (None if kind == "exact" else kind)
    max_batch = 16
    buckets = [b for b in SERVE_BUCKETS if b <= max_batch]
    # the coarse kernel's buckets past the prime's, then every exact bucket
    coarse = [b for b in buckets if b > 8] if plan.pruned else []
    assert plan.warm_shapes(max_batch) == (
        [WarmShape(1, "two_stage")] if plan.pruned else []) + [
        WarmShape(b, "two_stage") for b in coarse] + [
        WarmShape(b, "exact", True) for b in buckets]
    assert buckets[-1] * model.n_items <= ROW_MASK_MAX_ELEMENTS
    assert model.warmup(max_batch) == len(coarse) + len(buckets)
    fns = (retrieval.quantize_user_rows, retrieval.score_centroids_quantized,
           retrieval.two_stage_rerank, two_tower._topk_quantized)
    keys, sizes = jitstats.count(), [f._cache_size() for f in fns]
    engaged = ann.DEVICE_RERANK._default().value
    for b in buckets:
        users = np.arange(b, dtype=np.int32)
        mask = np.zeros((b, model.n_items), np.float32)
        for kw in ({}, {"row_mask": mask}, {"exact": True},
                   {"exact": True, "row_mask": mask}):
            idx, _ = TwoTowerMF.recommend_batch(model, users, 10, **kw)
            assert idx.shape == (b, 10)
    assert ann.DEVICE_RERANK._default().value == engaged + (
        len(buckets) if kind == "device-leg" else 0)
    assert jitstats.count() == keys
    assert [f._cache_size() for f in fns] == sizes
