"""Benchmark suite: all five BASELINE.md configs + serving latency on one chip.

Prints ONE JSON line whose headline is the north-star metric
(BASELINE.md:21-23): recommendation-template training throughput in
events/sec/chip, plus ``mfu``, ``predict_p50_ms`` / ``predict_p95_ms``
(measured through the deployed query server under concurrent load), and a
``configs`` matrix covering classification / recommendation / similarproduct /
ecommerce retrieval / sequential transformer and event-server ingestion.

Device lanes need a chip: with none they fail, and a failed lane fails the
run. The JSON line records ``platform``/``device`` as JAX reports them.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
baseline is measured in-process — the identical adam epoch in pure numpy on
the host. MFU is the honest hardware-utilization figure: analytic FLOPs of
each schedule ÷ chip peak (embedding workloads are HBM-bound, so their
``hbm_util`` is reported as well).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import numpy as np

SMALL = bool(os.environ.get("PIO_BENCH_SMALL"))
ONLY = set(filter(None, os.environ.get("PIO_BENCH_CONFIGS", "").split(",")))

# -- chip peak tables: bf16 FLOPs/s comes from the profiler's single source
#    of truth (obs/profile.py TPU_PEAK_FLOPS — the table behind the
#    pio_training_mfu gauge, so bench MFU and live MFU can never disagree);
#    the HBM bytes/s column is bench-only
_HBM_PEAKS = [
    ("v6", 1640e9), ("trillium", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9), ("v5 lite", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def chip_peaks(device) -> tuple[float, float]:
    from incubator_predictionio_tpu.obs.profile import peak_flops_for

    kind = device.device_kind.lower()
    flops = peak_flops_for(device.platform, kind)
    bw = next((b for key, b in _HBM_PEAKS if key in kind), None)
    if flops is None or bw is None:
        raise RuntimeError(
            f"no peaks known for {device.platform} device {device.device_kind!r}"
            ": device lanes need a listed TPU")
    return flops, bw


def _mfu(total_flops: float, dt: float, peak: float | None) -> float | None:
    return None if peak is None else round(total_flops / dt / peak, 4)


def _bw(total_bytes: float, dt: float, peak: float | None) -> float | None:
    return None if peak is None else round(total_bytes / dt / peak, 4)


# ---------------------------------------------------------------------------
# 1+2+3. two-tower family: recommendation (explicit), similarproduct
#        (implicit, sampled negatives), and the numpy host baseline
# ---------------------------------------------------------------------------

REC_USERS, REC_ITEMS = 6040, 3706           # MovieLens-1M shape
REC_EVENTS = 120_000 if SMALL else 1_000_000
REC_RANK, REC_BATCH, REC_EPOCHS = 64, 65536, 20


def _two_tower_flops_bytes(n_events, rank, batch, epochs, n_users, n_items,
                           moment_bytes=4):
    """Analytic per-schedule FLOPs and HBM bytes of the fused train loop.
    ``moment_bytes`` reflects the adam moment STORAGE dtype (4 = fp32,
    2 = bf16 via ``adam_moments_dtype``) so hbm_util stays honest when the
    traffic really shrinks."""
    n_batches = max(1, (n_events + batch - 1) // batch)
    steps = epochs * n_batches
    n_params = (n_users + n_items) * (rank + 1)
    flops_step = 12 * rank * batch + 12 * n_params  # fwd+bwd dots + dense adam
    # adam state r/w (params fp32 + m + v at their storage width, read+write)
    # + batch embedding gathers
    bytes_step = (n_params * (4 * 2 + moment_bytes * 4)
                  + batch * rank * 4 * 4)
    return steps * flops_step, steps * bytes_step


def _bench_two_tower(
    ctx, peaks, n_users, n_items, rank, n_events, batch,
    epochs, data_seed, moments_dtype="float32",
) -> "tuple[dict, np.ndarray, np.ndarray, np.ndarray, object]":
    """Shared warmup+timed two-tower run. Distinct model seeds per run: a
    timed run identical to the warmup could be served from an execution
    cache. Utilization is computed over the train phase — the one-time model
    pull (timings["gather_sec"]) says nothing about the chip."""
    from incubator_predictionio_tpu.models.two_tower import TwoTowerConfig, TwoTowerMF

    rng = np.random.default_rng(data_seed)
    users = rng.integers(0, n_users, n_events).astype(np.int32)
    items = rng.integers(0, n_items, n_events).astype(np.int32)
    ratings = (1.0 + 4.0 * rng.random(n_events)).astype(np.float32)

    def run(seed):
        return TwoTowerMF(TwoTowerConfig(
            rank=rank, batch_size=batch, epochs=epochs, seed=seed,
            adam_moments_dtype=moments_dtype,
        )).fit(ctx, users, items, ratings, n_users, n_items)

    run(0)  # warmup: pays every compile
    t0 = time.perf_counter()
    model = run(1)
    dt = time.perf_counter() - t0
    flops, bts = _two_tower_flops_bytes(
        n_events, rank, batch, epochs, n_users, n_items,
        moment_bytes=2 if moments_dtype == "bfloat16" else 4)
    t_train = model.timings["train_sec"]
    return ({
        "events_per_sec": round(epochs * n_events / dt, 1),
        "train_events_per_sec": round(epochs * n_events / t_train, 1),
        "mfu": _mfu(flops, t_train, peaks[0]),
        "hbm_util": _bw(bts, t_train, peaks[1]),
        "timings": model.timings,
    }, users, items, ratings, model)


def bench_recommendation(ctx, peaks) -> dict:
    out, users, items, ratings, _ = _bench_two_tower(
        ctx, peaks, REC_USERS, REC_ITEMS, REC_RANK, REC_EVENTS,
        REC_BATCH, REC_EPOCHS, data_seed=42)
    host_eps = bench_numpy_baseline(users, items, ratings)
    out["vs_host_numpy"] = round(out["events_per_sec"] / host_eps, 2)
    return out


def bench_recommendation_scaled(ctx, peaks, device) -> dict:
    """Production-representative two-tower shapes (VERDICT r2: ≥1M users,
    ≥100k items, rank 128): the dominant HBM traffic is the dense adam
    streaming over the 142M-parameter fused tables — the config whose
    ``hbm_util`` tells whether the schedule saturates the chip's bandwidth.

    The tables exceed HOST_SERVE_MAX_ELEMENTS so TwoTowerConfig's
    gather="auto" keeps them DEVICE-RESIDENT (round-4: no full-table host
    pull — round 3 lost 80% of end-to-end throughput to a 21.7s gather).
    persist/load time the orbax sharded-checkpoint save and the device-
    resident restore — the full train→persist→deploy cycle without the
    tables ever visiting host numpy."""
    import shutil
    import tempfile

    import jax

    small = SMALL
    n_users, n_items, rank = (
        (100_000, 20_000, 64) if small else (1_000_000, 100_000, 128))
    # bf16 moment storage: 6 → 4 fp32-equivalent table passes per step on
    # the dense-adam traffic that dominates this config (parity:
    # tests/test_optim_parity.py). PIO_BENCH_ADAM_MOMENTS=float32 ablates.
    moments = os.environ.get("PIO_BENCH_ADAM_MOMENTS", "bfloat16")
    out, _u, _i, _r, model = _bench_two_tower(
        ctx, peaks, n_users, n_items, rank,
        n_events=200_000 if small else 4_000_000,
        batch=65536, epochs=2 if small else 4, data_seed=9,
        moments_dtype=moments)
    out["adam_moments_dtype"] = moments
    # the headline ratio must compare THIS config against its own numpy
    # baseline (same table shapes/rank), not the MovieLens-shaped one
    host_eps = bench_numpy_baseline(
        _u, _i, _r, n_users=n_users, n_items=n_items, rank=rank)
    out["vs_host_numpy"] = round(out["events_per_sec"] / host_eps, 2)
    if model is not None and model.device_resident:
        from incubator_predictionio_tpu.data.bimap import BiMap
        from incubator_predictionio_tpu.templates.recommendation import RecModel

        d = tempfile.mkdtemp(prefix="bench_devmodel_")
        prev_basedir = os.environ.get("PIO_FS_BASEDIR")
        os.environ["PIO_FS_BASEDIR"] = d
        try:
            rec = RecModel(model, BiMap({}), BiMap({}))
            t0 = time.perf_counter()
            saved = rec.save("bench_0", None, ctx)
            t_persist = time.perf_counter() - t0
            t0 = time.perf_counter()
            loaded = RecModel.load("bench_0", None, ctx)
            jax.block_until_ready(loaded.mf._tables)
            t_load = time.perf_counter() - t0
            out["device_resident"] = bool(saved)
            out["persist_sec"] = round(t_persist, 4)
            out["deploy_load_sec"] = round(t_load, 4)
        finally:
            if prev_basedir is None:
                os.environ.pop("PIO_FS_BASEDIR", None)
            else:
                os.environ["PIO_FS_BASEDIR"] = prev_basedir
            shutil.rmtree(d, ignore_errors=True)
    return out


def bench_similarproduct(ctx, peaks) -> dict:
    """Implicit MF: positives + sampled negatives through the same towers
    (reference ALS.trainImplicit, similarproduct ALSAlgorithm.scala:61-135)."""
    from incubator_predictionio_tpu.models.negative_sampling import sample_negatives
    from incubator_predictionio_tpu.models.two_tower import TwoTowerConfig, TwoTowerMF

    n_users, n_items = 10_000, 10_000
    n_pos = 40_000 if SMALL else 250_000
    negs = 3
    rng = np.random.default_rng(7)
    pos_u = rng.integers(0, n_users, n_pos).astype(np.int32)
    pos_i = rng.integers(0, n_items, n_pos).astype(np.int32)
    neg_u, neg_i = sample_negatives(pos_u, pos_i, n_items, negs, rng)
    users = np.concatenate([pos_u, neg_u])
    items = np.concatenate([pos_i, neg_i])
    ratings = np.concatenate(
        [np.ones(n_pos, np.float32), np.zeros(len(neg_u), np.float32)])
    epochs, batch, rank = 10, 65536, 64

    def run(seed):
        return TwoTowerMF(TwoTowerConfig(
            rank=rank, batch_size=batch, epochs=epochs, seed=seed,
        )).fit(ctx, users, items, ratings, n_users, n_items)

    run(0)
    t0 = time.perf_counter()
    model = run(1)
    dt = time.perf_counter() - t0
    flops, bts = _two_tower_flops_bytes(
        len(users), rank, batch, epochs, n_users, n_items)
    t_train = model.timings["train_sec"]
    return {
        "events_per_sec": round(epochs * len(users) / dt, 1),
        "mfu": _mfu(flops, t_train, peaks[0]),
        "hbm_util": _bw(bts, t_train, peaks[1]),
    }


def bench_numpy_baseline(users, items, ratings, n_events: int = 100_000,
                         n_users: int = REC_USERS, n_items: int = REC_ITEMS,
                         rank: int = REC_RANK) -> float:
    """Identical per-event math (adam over embedding gathers), pure numpy."""
    n_events = min(n_events, len(users))
    rng = np.random.default_rng(0)
    ue = (rng.standard_normal((n_users, rank)) / np.sqrt(rank)).astype(np.float32)
    ie = (rng.standard_normal((n_items, rank)) / np.sqrt(rank)).astype(np.float32)
    ub = np.zeros(n_users, np.float32)
    ib = np.zeros(n_items, np.float32)
    m = {k: np.zeros_like(v) for k, v in (("ue", ue), ("ie", ie), ("ub", ub), ("ib", ib))}
    v = {k: np.zeros_like(val) for k, val in (("ue", ue), ("ie", ie), ("ub", ub), ("ib", ib))}
    lr, b1, b2, eps = 3e-2, 0.9, 0.999, 1e-8
    mean = ratings[:n_events].mean()
    t0 = time.perf_counter()
    step = 0
    for start in range(0, n_events, REC_BATCH):
        step += 1
        bu = users[start:start + REC_BATCH]
        bi = items[start:start + REC_BATCH]
        br = ratings[start:start + REC_BATCH] - mean
        e_u, e_i = ue[bu], ie[bi]
        pred = np.sum(e_u * e_i, axis=1) + ub[bu] + ib[bi]
        err = pred - br
        gu = 2 * err[:, None] * e_i / len(bu)
        gi = 2 * err[:, None] * e_u / len(bu)
        gb = 2 * err / len(bu)
        grads = {
            "ue": np.zeros_like(ue), "ie": np.zeros_like(ie),
            "ub": np.zeros_like(ub), "ib": np.zeros_like(ib),
        }
        np.add.at(grads["ue"], bu, gu)
        np.add.at(grads["ie"], bi, gi)
        np.add.at(grads["ub"], bu, gb)
        np.add.at(grads["ib"], bi, gb)
        for k, p in (("ue", ue), ("ie", ie), ("ub", ub), ("ib", ib)):
            m[k] = b1 * m[k] + (1 - b1) * grads[k]
            v[k] = b2 * v[k] + (1 - b2) * grads[k] ** 2
            mh = m[k] / (1 - b1 ** step)
            vh = v[k] / (1 - b2 ** step)
            p -= lr * mh / (np.sqrt(vh) + eps)
    return n_events / (time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# 4. classification MLP
# ---------------------------------------------------------------------------

def bench_classification(ctx, peaks) -> dict:
    from incubator_predictionio_tpu.models.mlp import MLPClassifier, MLPConfig

    n, d, hidden, epochs, batch = (
        20_000 if SMALL else 100_000), 3, (128, 128), 40, 4096
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.int32)
    cfg = MLPConfig(hidden_dims=hidden, epochs=epochs, batch_size=batch)

    MLPClassifier(cfg).fit(ctx, x, y)
    t0 = time.perf_counter()
    MLPClassifier(cfg).fit(ctx, x, y)
    dt = time.perf_counter() - t0
    dims = [d, *hidden, 2]
    flops_per_example = 6 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return {
        "events_per_sec": round(epochs * n / dt, 1),
        "mfu": _mfu(epochs * n * flops_per_example, dt, peaks[0]),
    }


# ---------------------------------------------------------------------------
# 5. ecommerce retrieval (serving-side scoring over a large catalog)
# ---------------------------------------------------------------------------

def bench_ecommerce_retrieval(ctx, peaks, device) -> dict:
    """Rule-filtered template serving at scale: the ECommAlgorithm predict
    path with live business rules (categories, white/black lists, the
    unavailable-items constraint read, unseen-only history) — serial
    per-query with reference read-per-query semantics (TTL=0) vs the
    vectorized ``batch_predict`` (mask compilation + cached/batched store
    reads + axis-wise top-k). Both paths are parity-checked query-for-query
    before timing; store-read counts and the coalesced batch-size
    distribution are recorded so the speedup is attributable. On TPU this
    also asserts the Pallas int8 kernel (plain + row-masked) against the
    jnp oracle."""
    import datetime as _dt

    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.bimap import BiMap
    from incubator_predictionio_tpu.data.storage import App, Storage, use_storage
    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerModel,
    )
    from incubator_predictionio_tpu.serving import TTLCache
    from incubator_predictionio_tpu.templates.ecommerce import (
        ECommAlgorithm,
        ECommAlgorithmParams,
        ECommModel,
        Query,
    )

    # SMALL trims the catalog and query volume to keep wall time down, but
    # keeps a production-depth view history — the serial lane's cost IS the
    # per-query store reads, so shallow histories would understate the gap
    n_users, n_items, rank = (200, 1_500, 32) if SMALL else (500, 4_000, 32)
    views_per_user = 80 if SMALL else 40
    rng = np.random.default_rng(3)
    utc = _dt.timezone.utc
    t0_ev = _dt.datetime(2020, 1, 1, tzinfo=utc)
    storage = Storage({"PIO_STORAGE_SOURCES_BENCHMEM_TYPE": "memory"})
    app_id = storage.get_meta_data_apps().insert(App(0, "bench-ecomm"))
    events = storage.get_events()
    events.init(app_id)
    cats = {f"i{i}": (f"c{i % 8}", f"g{i % 3}") for i in range(n_items)}
    for i in range(n_items):
        events.insert(Event(
            event="$set", entity_type="item", entity_id=f"i{i}",
            properties=DataMap({"categories": list(cats[f"i{i}"])}),
            event_time=t0_ev), app_id)
    for u in range(n_users):
        for i in map(int, rng.integers(0, n_items, views_per_user)):
            events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                event_time=t0_ev), app_id)
    events.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [f"i{i}" for i in range(0, 40)]}),
        event_time=t0_ev), app_id)
    norm = rng.standard_normal((n_items, rank)).astype(np.float32)
    norm /= np.linalg.norm(norm, axis=1, keepdims=True) + 1e-9
    model = ECommModel(
        mf=TwoTowerModel(
            user_emb=rng.standard_normal((n_users, rank)).astype(np.float32),
            item_emb=rng.standard_normal((n_items, rank)).astype(np.float32),
            user_bias=np.zeros(n_users, np.float32),
            item_bias=np.zeros(n_items, np.float32),
            mean=3.0, config=TwoTowerConfig(rank=rank)),
        user_map=BiMap.string_int(f"u{u}" for u in range(n_users)),
        item_map=BiMap.string_int(f"i{i}" for i in range(n_items)),
        categories=cats,
        popularity=rng.integers(0, 100, n_items).astype(np.float32),
        item_vecs_norm=norm,
    ).prepare_for_serving()
    parity = None
    if device.platform == "tpu":
        parity = _pallas_parity_check(model.mf)
    # the query mix: all four filter kinds + unknown users, like live traffic
    def make_query(j: int) -> Query:
        u = f"u{int(rng.integers(0, n_users))}" if j % 16 else "coldstart"
        kind = j % 4
        if kind == 0:
            return Query(user=u, num=10)
        if kind == 1:
            return Query(user=u, num=10, categories=(f"c{j % 8}",))
        if kind == 2:
            return Query(user=u, num=10,
                         black_list=tuple(f"i{i}" for i in range(j % 7)))
        return Query(user=u, num=10, categories=(f"g{j % 3}",),
                     white_list=tuple(f"i{i}" for i in range(100, 1100)))

    # throughput-oriented coalesce depth: the store-read + scan cost is per
    # BATCH, so deeper batches amortize further (the server's max_batch knob;
    # the recorded batch_size_distribution keeps the artifact honest). The
    # query count is deliberately NOT a batch multiple — the tail batch is
    # the partial coalesce a draining queue produces
    batch = 128
    n_serial = 128 if SMALL else 256
    n_batched = 2016 if SMALL else 4064
    queries = [make_query(j) for j in range(max(n_serial, n_batched))]
    from tests.fixtures.counting_events import CountingEvents

    counting = CountingEvents(events)
    storage.get_events = lambda: counting
    prev = use_storage(storage)
    try:
        serial_algo = ECommAlgorithm(
            ECommAlgorithmParams(app_name="bench-ecomm"))
        serial_algo._constraint_cache = TTLCache(0)  # reference semantics
        batch_algo = ECommAlgorithm(
            ECommAlgorithmParams(app_name="bench-ecomm"))
        # parity first: the serial path is the oracle
        want = [serial_algo.predict(model, q) for q in queries[:batch]]
        got = dict(batch_algo.batch_predict(
            model, list(enumerate(queries[:batch]))))
        parity_ok = all(
            [(s.item, s.score) for s in want[i].item_scores]
            == [(s.item, s.score) for s in got[i].item_scores]
            for i in range(batch))
        if not parity_ok:
            # the headline number is only meaningful for a path that
            # answers identically — fail the config, don't publish a
            # speedup for divergent results
            raise RuntimeError(
                "batched-vs-serial parity failure in ecommerce_retrieval")
        # serial timing (reference read-per-query semantics)
        reads0 = counting.total_reads
        t0 = time.perf_counter()
        for q in queries[:n_serial]:
            serial_algo.predict(model, q)
        dt_serial = time.perf_counter() - t0
        serial_reads = (counting.total_reads - reads0) / n_serial
        serial_qps = n_serial / dt_serial
        # batched timing through coalesced micro-batches
        batch_sizes: dict[str, int] = {}
        reads0 = counting.total_reads
        t0 = time.perf_counter()
        for off in range(0, n_batched, batch):
            chunk = queries[off:off + batch]
            batch_algo.batch_predict(model, list(enumerate(chunk)))
            batch_sizes[str(len(chunk))] = batch_sizes.get(str(len(chunk)), 0) + 1
        dt_batched = time.perf_counter() - t0
        n_dispatched = sum(int(k) * v for k, v in batch_sizes.items())
        batched_reads = (counting.total_reads - reads0) / max(1, sum(batch_sizes.values()))
        batched_qps = n_dispatched / dt_batched
    finally:
        use_storage(prev)
        storage.close()
    flops = 2 * rank * n_items * n_dispatched  # the scoring matmuls
    out = {
        "queries_per_sec": round(batched_qps, 1),
        "serial_queries_per_sec": round(serial_qps, 1),
        "speedup_vs_serial": round(batched_qps / serial_qps, 1),
        "batched_parity": parity_ok,
        "batch_size_distribution": batch_sizes,
        "store_reads": {
            "serial_per_query": round(serial_reads, 2),
            "batched_per_batch": round(batched_reads, 2),
        },
        "mfu": _mfu(flops, dt_batched, peaks[0]),
    }
    if parity is not None:
        out["pallas_kernel_parity"] = parity
    return out


def _pallas_parity_check(model) -> bool:
    """Quantized Pallas scorer (plain + per-row rule mask) vs the jnp
    oracle on identical inputs."""
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.retrieval import (
        pad_catalog,
        quantize_rows,
        score_catalog_quantized,
        score_catalog_reference,
    )

    n = min(2048, model.item_emb.shape[0])
    items_q, scales = quantize_rows(np.asarray(model.item_emb[:n]))
    items_q, scales, bias, mask = pad_catalog(
        items_q, scales,
        np.asarray(model.item_bias[:n], np.float32),
        np.zeros(n, np.float32))
    b = min(64, model.user_emb.shape[0])
    ue = jnp.asarray(np.asarray(model.user_emb)[:b], jnp.float32)
    rng = np.random.default_rng(0)
    row_mask = np.zeros((b, items_q.shape[0]), np.float32)
    row_mask[np.arange(b), rng.integers(0, n, b)] = -np.inf
    row_mask = jnp.asarray(row_mask)
    ok = True
    for rm in (None, row_mask):
        got = np.asarray(score_catalog_quantized(
            ue, items_q, scales, bias, mask, rm))
        want = np.asarray(score_catalog_reference(
            ue, items_q, scales, bias, mask, rm))
        good = bool(np.allclose(got, want, rtol=2e-2, atol=2e-2,
                                equal_nan=True))
        if not good:
            _log(f"PALLAS PARITY FAILURE (row_mask={rm is not None}): "
                 f"max abs diff {np.max(np.abs(got - want)):.4f}")
        ok = ok and good
    return ok


# ---------------------------------------------------------------------------
# 5b. two-stage retrieval at catalog scale (docs/serving.md)
# ---------------------------------------------------------------------------

def bench_retrieval_scale(ctx, peaks, device) -> dict:
    """Exact full-catalog top-k vs the two-stage (IVF coarse prune + exact
    rerank) path across catalog sizes × ``nprobe`` — the qps-vs-recall@10
    curve that justifies PIO_RETRIEVAL_MODE=two_stage for big catalogs.

    Catalogs are mixture-of-concepts synthetic towers (√N concepts,
    σ=0.5) — the clustered geometry trained MF factors actually have, and
    the regime the recall floor is specified over (an iid-gaussian catalog
    has no structure to prune by; see tests/test_two_stage_retrieval.py).
    The exact lane is the oracle: recall@10 is measured against ITS answers
    on a held-out query set, and the headline speedup is only quoted at
    operating points with recall ≥ 0.95."""
    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerModel,
        TwoTowerMF,
    )

    rank = 32
    n_users = 10_000
    # coalesced serving batches (the server's max_batch regime — cf. the
    # ecommerce serving bench above): the int8 rerank amortizes each probed
    # partition's upcast+GEMM across every query in the batch that probes
    # it, so the quantized lane's speedup is measured at serve batch depth
    batch, num = 128, 10
    n_eval = 256            # oracle/recall query users
    sizes = (100_000, 250_000) if SMALL else (100_000, 1_000_000)
    # the int8 amortization win compounds with probes per query (more
    # probers share each partition's upcast+GEMM), so the bigger-catalog
    # operating points sit at the deep end of the grid
    nprobes = (8, 16, 32, 64, 128)
    prev_env = {k: os.environ.get(k) for k in
                ("PIO_RETRIEVAL_MODE", "PIO_RETRIEVAL_NPROBE",
                 "PIO_RETRIEVAL_QUANTIZE")}
    points = []
    headline = {}
    try:
        for n_items in sizes:
            rng = np.random.default_rng(11)
            n_concepts = max(64, int(round(np.sqrt(n_items))))
            concepts = rng.standard_normal((n_concepts, rank)).astype(np.float32)
            item = concepts[rng.integers(0, n_concepts, n_items)] \
                + 0.5 * rng.standard_normal((n_items, rank)).astype(np.float32)
            user = concepts[rng.integers(0, n_concepts, n_users)] \
                + 0.5 * rng.standard_normal((n_users, rank)).astype(np.float32)
            model = TwoTowerModel(
                user_emb=user, item_emb=item,
                user_bias=(rng.standard_normal(n_users) * 0.1).astype(np.float32),
                item_bias=(rng.standard_normal(n_items) * 0.1).astype(np.float32),
                mean=3.0, config=TwoTowerConfig(rank=rank))
            qusers = rng.integers(0, n_users, (64, batch)).astype(np.int32)
            eusers = rng.integers(0, n_users, (n_eval // batch, batch)).astype(np.int32)

            def lane_qps(min_sec=2.0):
                # warm one batch, then timed closed-loop batches
                TwoTowerMF.recommend_batch(model, qusers[0], num)
                done = 0
                t0 = time.perf_counter()
                while True:
                    TwoTowerMF.recommend_batch(
                        model, qusers[done % len(qusers)], num)
                    done += 1
                    dt = time.perf_counter() - t0
                    if dt >= min_sec and done >= 8:
                        return done * batch / dt

            os.environ["PIO_RETRIEVAL_MODE"] = "exact"
            model.prepare_for_serving(serve_k=num)
            exact_qps = lane_qps()
            oracle = [TwoTowerMF.recommend_batch(model, row, num)[0]
                      for row in eusers]
            os.environ["PIO_RETRIEVAL_MODE"] = "two_stage"
            # fp32 lane first: int8 is the serving default, so the
            # comparison lane opts out explicitly
            os.environ["PIO_RETRIEVAL_QUANTIZE"] = "0"
            model.prepare_for_serving(serve_k=num)  # builds the IVF index
            build_sec = model._ivf.build_seconds
            assert not model._ivf.quantized
            for nprobe in nprobes:
                os.environ["PIO_RETRIEVAL_NPROBE"] = str(nprobe)
                got = [TwoTowerMF.recommend_batch(model, row, num)[0]
                       for row in eusers]
                recall = float(np.mean([
                    len(set(o[r]) & set(g[r])) / num
                    for o, g in zip(oracle, got) for r in range(batch)]))
                qps = lane_qps()
                points.append({
                    "n_items": n_items, "nprobe": nprobe,
                    "n_partitions": model._ivf.n_partitions,
                    "qps": round(qps, 1), "recall_at_10": round(recall, 4),
                    "exact_qps": round(exact_qps, 1),
                    "speedup_vs_exact": round(qps / exact_qps, 1),
                })
                _log(f"retrieval_scale n={n_items} nprobe={nprobe}: "
                     f"{qps:.0f} qps vs exact {exact_qps:.0f} "
                     f"(recall@10 {recall:.3f})")
            # int8 lane: both stages quantized (int8 coarse probe + int8
            # rerank, one fp32 rescale each) at the SAME nprobe grid —
            # the acceptance gate is ≥1.5× qps over the fp32 two-stage
            # lane at an operating point holding recall@10 ≥ 0.95
            fp32_qps = {p["nprobe"]: p["qps"] for p in points
                        if p["n_items"] == n_items and "lane" not in p}
            os.environ["PIO_RETRIEVAL_QUANTIZE"] = "1"
            model.prepare_for_serving(serve_k=num)  # int8 index rebuild
            int8_build_sec = model._ivf.build_seconds
            assert model._ivf.quantized
            for nprobe in nprobes:
                os.environ["PIO_RETRIEVAL_NPROBE"] = str(nprobe)
                got = [TwoTowerMF.recommend_batch(model, row, num)[0]
                       for row in eusers]
                recall = float(np.mean([
                    len(set(o[r]) & set(g[r])) / num
                    for o, g in zip(oracle, got) for r in range(batch)]))
                qps = lane_qps()
                points.append({
                    "lane": "int8", "n_items": n_items, "nprobe": nprobe,
                    "n_partitions": model._ivf.n_partitions,
                    "qps": round(qps, 1), "recall_at_10": round(recall, 4),
                    "exact_qps": round(exact_qps, 1),
                    "speedup_vs_exact": round(qps / exact_qps, 1),
                    "speedup_vs_fp32_two_stage":
                        round(qps / fp32_qps[nprobe], 2),
                })
                _log(f"retrieval_scale[int8] n={n_items} nprobe={nprobe}: "
                     f"{qps:.0f} qps ({qps / fp32_qps[nprobe]:.2f}x fp32 "
                     f"two-stage, recall@10 {recall:.3f})")
            os.environ["PIO_RETRIEVAL_QUANTIZE"] = "0"
            os.environ.pop("PIO_RETRIEVAL_NPROBE", None)
            model.prepare_for_serving(serve_k=num)  # back to the fp32 index
            good = [p for p in points
                    if p["n_items"] == n_items and "lane" not in p
                    and p["recall_at_10"] >= 0.95]
            good_int8 = [p for p in points
                         if p["n_items"] == n_items
                         and p.get("lane") == "int8"
                         and p["recall_at_10"] >= 0.95]
            # the int8 gate, asserted IN the lane: some nprobe holds the
            # recall floor AND clears 1.5x over fp32 two-stage
            assert good_int8, \
                f"int8 lane lost the 0.95 recall floor at n={n_items}"
            best_int8 = max(
                p["speedup_vs_fp32_two_stage"] for p in good_int8)
            assert best_int8 >= 1.5, \
                (f"int8 lane gate: best speedup over fp32 two-stage at the "
                 f"recall floor is {best_int8:.2f}x < 1.5x (n={n_items})")
            headline[str(n_items)] = {
                "exact_qps": round(exact_qps, 1),
                "index_build_sec": round(build_sec, 1),
                **({"best_qps": max(p["qps"] for p in good),
                    "best_speedup": max(p["speedup_vs_exact"] for p in good),
                    "recall_floor": 0.95} if good else
                   {"best_speedup": None}),
                "int8_build_sec": round(int8_build_sec, 1),
                "int8_best_qps": max(p["qps"] for p in good_int8),
                "int8_best_speedup_vs_fp32": best_int8,
                "int8_recall_floor": 0.95,
            }
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {"points": points, "headline": headline,
            "batch": batch, "num": num, "rank": rank}


def bench_sharded_serving(ctx, peaks, device) -> dict:
    """Sharded serving (docs/sharding.md) next to the exact and two-stage
    lanes: the same catalog served (a) exact single-host, (b) two-stage
    single-host IVF, (c) per-shard exact top-k + cross-shard merge from
    model-axis-sharded device tables, (d) the composed per-shard-IVF +
    merge-rerank path. Archives qps per lane, recall@10 vs the exact
    oracle for the pruned lanes, and the per-lane ``pio_shard_*`` metric
    deltas (merge fan-in, per-shard top-k/merge time, fallbacks).

    Runs on 8 virtual CPU devices (run_one_config sets the XLA flag for
    this config) — like the fleet scenario it measures the ARCHITECTURE
    (merge overhead and layout), not chip throughput. The sharded_exact
    lane's recall is vs the f32 HOST oracle, so slightly under 1.0 purely
    from bf16 device scoring re-ordering near-ties — the sharded-vs-
    single-DEVICE parity is bitwise and pinned in tests/test_sharding.py."""
    import jax

    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerModel,
        TwoTowerMF,
    )
    from incubator_predictionio_tpu.obs.metrics import REGISTRY
    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    rank = 32
    n_users = 10_000
    n_items = 60_000 if SMALL else 150_000
    batch, num = 16, 10
    n_shards = min(8, len(jax.devices()))
    rng = np.random.default_rng(13)
    n_concepts = max(64, int(round(np.sqrt(n_items))))
    concepts = rng.standard_normal((n_concepts, rank)).astype(np.float32)
    item = concepts[rng.integers(0, n_concepts, n_items)] \
        + 0.5 * rng.standard_normal((n_items, rank)).astype(np.float32)
    user = concepts[rng.integers(0, n_concepts, n_users)] \
        + 0.5 * rng.standard_normal((n_users, rank)).astype(np.float32)
    user_bias = (rng.standard_normal(n_users) * 0.1).astype(np.float32)
    item_bias = (rng.standard_normal(n_items) * 0.1).astype(np.float32)

    def host_model():
        return TwoTowerModel(
            user_emb=user, item_emb=item, user_bias=user_bias,
            item_bias=item_bias, mean=3.0,
            config=TwoTowerConfig(rank=rank))

    def device_sharded_model():
        """The same towers resident as model-axis-sharded device tables —
        what a sharded fit/restore produces (fused bias column, rows
        padded to the shard multiple)."""
        mctx = MeshContext.create(axes={"data": 1, "model": n_shards})
        m = TwoTowerModel(mean=3.0, config=TwoTowerConfig(rank=rank))

        def fused(emb, bias):
            t = np.concatenate([emb, bias[:, None]], axis=1)
            pad = -(-t.shape[0] // n_shards) * n_shards - t.shape[0]
            return np.pad(t, ((0, pad), (0, 0)))

        m._tables = {
            "ue": mctx.put(fused(user, user_bias), "model", None),
            "ie": mctx.put(fused(item, item_bias), "model", None),
        }
        m._n_users, m._n_items = n_users, n_items
        return m

    qusers = rng.integers(0, n_users, (64, batch)).astype(np.int32)
    eusers = rng.integers(0, n_users, (256 // batch, batch)).astype(np.int32)

    def lane_qps(model, min_sec=2.0):
        TwoTowerMF.recommend_batch(model, qusers[0], num)
        done = 0
        t0 = time.perf_counter()
        while True:
            TwoTowerMF.recommend_batch(model, qusers[done % len(qusers)], num)
            done += 1
            dt = time.perf_counter() - t0
            if dt >= min_sec and done >= 8:
                return done * batch / dt

    def shard_delta(before):
        after = _metrics_snapshot(REGISTRY.expose())
        return {k: v for k, v in _snapshot_delta(before, after).items()
                if k.startswith("pio_shard_")}

    prev_env = {k: os.environ.get(k) for k in
                ("PIO_SHARD_SERVE", "PIO_SHARD_SERVE_SHARDS",
                 "PIO_RETRIEVAL_MODE", "PIO_RETRIEVAL_NPROBE")}
    lanes: dict[str, dict] = {}
    try:
        os.environ["PIO_RETRIEVAL_NPROBE"] = "16"
        # (a) exact single-host oracle lane
        os.environ["PIO_SHARD_SERVE"] = "0"
        os.environ["PIO_RETRIEVAL_MODE"] = "exact"
        m = host_model()
        m.prepare_for_serving(serve_k=num)
        m.warmup(max_batch=batch)
        lanes["exact"] = {"qps": round(lane_qps(m), 1)}
        oracle = [TwoTowerMF.recommend_batch(m, row, num)[0]
                  for row in eusers]

        def recall(model):
            got = [TwoTowerMF.recommend_batch(model, row, num)[0]
                   for row in eusers]
            return round(float(np.mean([
                len(set(o[r]) & set(g[r])) / num
                for o, g in zip(oracle, got) for r in range(batch)])), 4)

        # (b) two-stage single-host lane
        os.environ["PIO_RETRIEVAL_MODE"] = "two_stage"
        m = host_model()
        m.prepare_for_serving(serve_k=num)
        m.warmup(max_batch=batch)
        lanes["two_stage"] = {"qps": round(lane_qps(m), 1),
                              "recall_at_10": recall(m)}
        # (c) sharded exact from device tables
        os.environ["PIO_SHARD_SERVE"] = "1"
        os.environ["PIO_RETRIEVAL_MODE"] = "exact"
        md = device_sharded_model()
        md.prepare_for_serving(serve_k=num)
        md.warmup(max_batch=batch)
        before = _metrics_snapshot(REGISTRY.expose())
        lanes["sharded_exact"] = {
            "qps": round(lane_qps(md), 1), "n_shards": n_shards,
            "recall_at_10": recall(md),  # exact: must be 1.0
        }
        lanes["sharded_exact"]["pio_shard"] = shard_delta(before)
        # (d) composed per-shard IVF + merge rerank
        os.environ["PIO_RETRIEVAL_MODE"] = "two_stage"
        md = device_sharded_model()
        md.prepare_for_serving(serve_k=num)
        md.warmup(max_batch=batch)
        before = _metrics_snapshot(REGISTRY.expose())
        lanes["sharded_two_stage"] = {
            "qps": round(lane_qps(md), 1), "n_shards": n_shards,
            "recall_at_10": recall(md),
        }
        lanes["sharded_two_stage"]["pio_shard"] = shard_delta(before)
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    for name, lane in lanes.items():
        _log(f"sharded_serving {name}: {lane['qps']} qps"
             + (f" recall@10 {lane['recall_at_10']}"
                if "recall_at_10" in lane else ""))
    return {"lanes": lanes, "n_items": n_items, "batch": batch, "num": num,
            "rank": rank, "n_shards": n_shards,
            "n_devices": len(jax.devices())}


# ---------------------------------------------------------------------------
# 6. sequential transformer (the long-context flagship)
# ---------------------------------------------------------------------------

def bench_sequential(ctx, peaks, device) -> dict:
    from incubator_predictionio_tpu.models.transformer import (
        TransformerConfig,
        TransformerRecommender,
    )

    # production-representative shapes (VERDICT r2: d_model ≥512, seq ≥512)
    small = SMALL
    if small:
        vocab, max_len, d, layers, heads = 10_000, 128, 256, 4, 4
        n, epochs, batch = 256, 1, 128
    else:
        vocab, max_len, d, layers, heads = 10_000, 512, 512, 6, 8
        n, epochs, batch = 2048, 2, 64
    import dataclasses as _dc

    rng = np.random.default_rng(11)
    seqs = rng.integers(1, vocab, (n, max_len + 1)).astype(np.int32)
    cfg = TransformerConfig(
        vocab_size=vocab, max_len=max_len, d_model=d, n_heads=heads,
        n_layers=layers, batch_size=batch, epochs=epochs, attention="local")

    TransformerRecommender(cfg).fit(ctx, seqs, None)
    t0 = time.perf_counter()
    # distinct seed: an identical re-run could be served from an execution
    # cache (no recompile — seed is data, not static)
    model = TransformerRecommender(_dc.replace(cfg, seed=1)).fit(ctx, seqs, None)
    dt = time.perf_counter() - t0
    tokens = epochs * n * max_len
    n_nonemb = 12 * layers * d * d  # attn(4d²) + mlp(8d²) per layer
    flops_per_token = 6 * n_nonemb + 12 * layers * d * max_len
    t_train = model.timings["train_sec"]
    return {
        "tokens_per_sec": round(tokens / dt, 1),
        "train_tokens_per_sec": round(tokens / t_train, 1),
        "mfu": _mfu(tokens * flops_per_token, t_train, peaks[0]),
        "timings": model.timings,
    }


# ---------------------------------------------------------------------------
# 7. serving latency through the deployed query server (north-star p50)
# ---------------------------------------------------------------------------

#: Standalone load client (argv: base_url, duration_s, n_users). Runs in its
#: own process — no jax, no shared event loop with the server — over raw
#: keep-alive sockets, and prints one JSON line of client-observed stats.
_SERVING_CLIENT_SCRIPT = """
# Raw-socket HTTP/1.1 keep-alive load generator: the client shares the
# host's core(s) with the server under test, and an aiohttp client costs
# more per request than the server handler — measuring through it reports
# the client, not the server (same rationale as the ingestion driver).
import asyncio, json, sys, time, urllib.parse

import numpy as np

base, duration, n_users = sys.argv[1], float(sys.argv[2]), int(sys.argv[3])
host = urllib.parse.urlsplit(base).hostname
port = urllib.parse.urlsplit(base).port
lat_ms = []


def req_bytes(user):
    body = json.dumps({"user": user, "num": 10}).encode()
    return (f"POST /queries.json HTTP/1.1\\r\\nHost: {host}:{port}\\r\\n"
            f"Content-Type: application/json\\r\\n"
            f"Content-Length: {len(body)}\\r\\n\\r\\n").encode() + body


async def post(r, w, user):
    w.write(req_bytes(user))
    await w.drain()
    status = await r.readline()
    assert b" 200 " in status, status
    length = None
    while True:
        line = await r.readline()
        if line in (b"\\r\\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":")[1])
    assert length is not None
    await r.readexactly(length)


async def main():
    conns = [await asyncio.open_connection(host, port) for _ in range(16)]
    await post(*conns[0], "u1")  # warmup round trip
    stop_at = time.perf_counter() + duration

    async def worker(conn, wid):
        rng = np.random.default_rng(wid)
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            await post(*conn, f"u{rng.integers(0, n_users)}")
            lat_ms.append((time.perf_counter() - t0) * 1e3)

    await asyncio.gather(*(worker(c, i) for i, c in enumerate(conns)))
    for _, w in conns:
        w.close()

asyncio.run(main())
a = np.sort(np.asarray(lat_ms))
pct = lambda q: float(a[min(len(a) - 1, int(q * (len(a) - 1)))])
print(json.dumps({
    "p50_ms": round(pct(0.50), 2), "p95_ms": round(pct(0.95), 2),
    "p99_ms": round(pct(0.99), 2), "qps": round(len(a) / duration, 1),
    "count": len(a),
}))
"""

def _metrics_snapshot(text: str) -> dict:
    """Trim a /metrics page into a JSON-friendly snapshot: counter/gauge
    samples plus histogram _count/_sum (bucket rows add noise, not signal,
    to a bench artifact)."""
    from incubator_predictionio_tpu.obs.metrics import parse_prometheus_text

    out: dict[str, float] = {}
    for name, fam in parse_prometheus_text(text).items():
        for sname, labels, value in fam["samples"]:
            if sname.endswith("_bucket"):
                continue
            label = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
            out[f"{sname}{{{label}}}" if label else sname] = value
    return out


def _snapshot_delta(before: dict, after: dict) -> dict:
    """Per-run view of a /metrics snapshot from a process that outlives
    the run (the fleet bench's replicas and the bench-process router
    registry serve several topologies in a row): monotonic samples
    (counters, histogram _sum/_count) are differenced against the
    ``before`` snapshot so the artifact records what THIS run did, not
    the cumulative history; gauges keep their end-of-run value."""
    out: dict = {}
    for key, value in after.items():
        base = before.get(key)
        if (isinstance(value, (int, float))
                and isinstance(base, (int, float))
                and ("_total" in key or "_sum" in key or "_count" in key)):
            out[key] = round(value - base, 6)
        else:
            out[key] = value
    return out


def _train_recommendation(ctx, storage, tmp: str, n_users: int,
                          n_items: int, n_events: int,
                          factory_path: str = (
                              "incubator_predictionio_tpu.templates."
                              "recommendation.RecommendationEngine")) -> str:
    """Seed rating events and train the recommendation template through
    the real workflow; returns the engine-variant path. Shared by the
    serving, overload, and fleet scenarios (one training recipe, several
    load shapes); ``factory_path`` lets a scenario deploy a wrapped engine
    (the fleet scenario's service-floor fixture) around the same model."""
    import datetime as dt_mod

    from incubator_predictionio_tpu.core.controller import (
        resolve_engine_factory,
    )
    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import App
    from incubator_predictionio_tpu.data.storage.base import EngineInstance

    app_id = storage.get_meta_data_apps().insert(App(0, "bench-app"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(5)
    utc = dt_mod.timezone.utc
    batch = [
        Event(event="rate", entity_type="user",
              entity_id=f"u{rng.integers(0, n_users)}",
              target_entity_type="item",
              target_entity_id=f"i{rng.integers(0, n_items)}",
              properties=DataMap({"rating": float(1 + 4 * rng.random())}),
              event_time=dt_mod.datetime(2022, 1, 1, tzinfo=utc))
        for _ in range(n_events)
    ]
    events.insert_batch(batch, app_id)

    variant_path = os.path.join(tmp, "engine.json")
    variant = {
        "id": "bench", "version": "1",
        "engineFactory": factory_path,
        "datasource": {"params": {"appName": "bench-app"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 32, "numIterations": 3, "batchSize": 8192}}],
    }
    with open(variant_path, "w") as f:
        json.dump(variant, f)
    engine = resolve_engine_factory(factory_path)()
    engine_params = engine.engine_params_from_variant(variant)
    instance = EngineInstance(
        id="", status="INIT",
        start_time=dt_mod.datetime.now(utc), end_time=None,
        engine_id="bench", engine_version="1",
        engine_variant=os.path.abspath(variant_path),
        engine_factory=variant["engineFactory"])
    run_train(engine, engine_params, instance, storage=storage, ctx=ctx)
    return variant_path


def bench_serving(ctx) -> dict:
    """Train the recommendation template through the real workflow, deploy it
    in the real query server, and measure client-observed latency under
    concurrent load (16 closed-loop clients) — exercising bind → supplement →
    MicroBatcher → batch_predict → serve, the full CreateServer.scala:464-494
    path."""
    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.server.query_server import QueryServer, ServerConfig
    from incubator_predictionio_tpu.templates.recommendation import RecommendationEngine

    import tempfile

    n_users, n_items, n_events = 2000, 1000, (5_000 if SMALL else 50_000)
    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    prev = use_storage(storage)
    tmp = tempfile.mkdtemp(prefix="pio-bench-")
    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events)

        # The server runs IN the bench process (it owns the accelerator); the
        # LOAD CLIENT is a separate OS process driving a real TCP socket —
        # client-observed latency includes the wire, not a shared event loop.
        import subprocess
        import sys as _sys

        from incubator_predictionio_tpu.parallel.launcher import free_port

        duration = 2.0 if SMALL else 6.0
        port = free_port()
        client_script = _SERVING_CLIENT_SCRIPT

        # gauge serving-only compiles: earlier configs in this process (e.g.
        # the retrieval bench) already registered jit keys
        from incubator_predictionio_tpu.utils import jitstats

        jitstats.reset()

        async def drive() -> tuple[dict, dict]:
            server = QueryServer(
                ServerConfig(engine_variant=variant_path, ip="127.0.0.1",
                             port=port),
                storage=storage, ctx=ctx)
            await server.start()
            try:
                proc = await asyncio.create_subprocess_exec(
                    _sys.executable, "-c", client_script,
                    f"http://127.0.0.1:{port}", str(duration), str(n_users),
                    stdout=subprocess.PIPE,
                )
                try:
                    stdout, _ = await asyncio.wait_for(
                        proc.communicate(), timeout=duration + 120)
                except asyncio.TimeoutError:
                    proc.kill()  # a wedged load generator must not outlive us
                    await proc.wait()
                    raise
                assert proc.returncode == 0, proc.returncode
                client_stats = json.loads(stdout.decode().strip().splitlines()[-1])
                import aiohttp

                async with aiohttp.ClientSession() as s:
                    status = await (await s.get(
                        f"http://127.0.0.1:{port}/")).json()
                    metrics_text = await (await s.get(
                        f"http://127.0.0.1:{port}/metrics")).text()
                return client_stats, status, metrics_text
            finally:
                await server.shutdown()

        client_stats, status, metrics_text = asyncio.run(drive())
        metrics_snapshot = _metrics_snapshot(metrics_text)
        out = {
            "predict_p50_ms": client_stats["p50_ms"],
            "predict_p95_ms": client_stats["p95_ms"],
            "predict_p99_ms": client_stats["p99_ms"],
            "queries_per_sec": client_stats["qps"],
            "max_batch_seen": status.get("maxBatchSeen"),
            "jit_compile_keys": status.get("jitCompileKeys"),
            "server_p50_ms": round(
                status["servingSecPercentiles"]["p50"] * 1e3, 2),
            # the /metrics fold (ISSUE 2): the same counters/gauges a
            # Prometheus scrape would see during the run, archived with the
            # bench so telemetry regressions show up in artifact diffs
            "metrics": metrics_snapshot,
        }
        # Pallas/oracle parity on the DEPLOYED model's factors. The bench
        # catalog itself serves from the host fast path (small catalog); this
        # asserts that had it been large enough for the device path, the
        # quantized scorer agrees — on the trained weights, not synthetic ones
        import jax

        if jax.devices()[0].platform == "tpu":
            instances = storage.get_meta_data_engine_instances()
            inst = instances.get_latest_completed(
                "bench", "1", os.path.abspath(variant_path))
            blob = storage.get_model_data_models().get(inst.id)
            from incubator_predictionio_tpu.utils.serialization import (
                deserialize_model,
            )

            with open(variant_path) as f:
                variant = json.load(f)
            engine = RecommendationEngine().apply()
            engine_params = engine.engine_params_from_variant(variant)
            persisted = deserialize_model(blob.models)
            models = engine.prepare_deploy(
                ctx, engine_params, persisted, inst.id)
            # read-only check on the trained factor tables
            out["pallas_kernel_parity"] = _pallas_parity_check(models[0].mf)
        return out
    finally:
        use_storage(prev)
        storage.close()


# ---------------------------------------------------------------------------
# 7a½. trace-plane overhead (docs/observability.md "The trace plane"):
#      serving qps with the durable span spool at 0% / 1% / 100% head
#      sampling vs tracing-off — the measurement plane must not tax the
#      thing it measures (≤5% at 1% sampling asserted)
# ---------------------------------------------------------------------------


def bench_trace_overhead(ctx) -> dict:
    """Deploy the recommendation template in the real query server and
    drive the same 16-connection closed loop under four trace-plane
    configurations: export off, spool at PIO_TRACE_SAMPLE 0 / 0.01 / 1.0.
    Two passes per lane, best qps kept (the lanes share one noisy host
    with the load client). Archives the assembled slowest-trace waterfall
    from the 100% lane — the artifact `pio-tpu trace slowest` would show."""
    import subprocess
    import sys as _sys
    import tempfile

    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.obs import collect
    from incubator_predictionio_tpu.obs import spool as trace_spool
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from incubator_predictionio_tpu.server.query_server import (
        QueryServer,
        ServerConfig,
    )

    n_users, n_items, n_events = 2000, 1000, (5_000 if SMALL else 20_000)
    duration = 2.0 if SMALL else 4.0
    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    prev = use_storage(storage)
    tmp = tempfile.mkdtemp(prefix="pio-traceov-")
    # one spool dir PER LANE: the archived artifact and byte figure must
    # describe a single configuration, not the union of all four lanes
    spool_100 = os.path.join(tmp, "spool-100pct")
    trace_envs = {
        "off": {},
        "sample_0": {"PIO_TRACE_SPOOL_DIR": os.path.join(tmp, "spool-0"),
                     "PIO_TRACE_SAMPLE": "0"},
        "sample_1pct": {"PIO_TRACE_SPOOL_DIR": os.path.join(tmp, "spool-1"),
                        "PIO_TRACE_SAMPLE": "0.01"},
        "sample_100pct": {"PIO_TRACE_SPOOL_DIR": spool_100,
                          "PIO_TRACE_SAMPLE": "1"},
    }
    touched = sorted({k for env in trace_envs.values() for k in env})
    saved_env = {k: os.environ.get(k) for k in touched}

    def _apply_env(env: dict) -> None:
        for k in touched:
            os.environ.pop(k, None)
        os.environ.update(env)

    async def drive(variant_path: str, port: int) -> dict:
        server = QueryServer(
            ServerConfig(engine_variant=variant_path, ip="127.0.0.1",
                         port=port),
            storage=storage, ctx=ctx)
        await server.start()
        try:
            proc = await asyncio.create_subprocess_exec(
                _sys.executable, "-c", _SERVING_CLIENT_SCRIPT,
                f"http://127.0.0.1:{port}", str(duration), str(n_users),
                stdout=subprocess.PIPE)
            try:
                stdout, _ = await asyncio.wait_for(
                    proc.communicate(), timeout=duration + 120)
            except asyncio.TimeoutError:
                proc.kill()
                await proc.wait()
                raise
            assert proc.returncode == 0, proc.returncode
            return json.loads(stdout.decode().strip().splitlines()[-1])
        finally:
            await server.shutdown()

    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events)
        lanes: dict[str, dict] = {}
        for _pass in range(2):
            for lane, env in trace_envs.items():
                _apply_env(env)
                if not env:
                    # an earlier lane configured the module-wide exporter;
                    # "off" must really mean export disabled
                    trace_spool.close_export()
                stats = asyncio.run(drive(variant_path, free_port()))
                prev_best = lanes.get(lane)
                if prev_best is None or stats["qps"] > prev_best["qps"]:
                    lanes[lane] = stats
        trace_spool.close_export()

        # assemble the 100% lane's spool: the slowest trace's waterfall is
        # the bench artifact an operator would pull via `pio-tpu trace`
        spans, problems = collect.read_spool_dir(spool_100)
        trees = collect.slowest(collect.assemble(spans), 1)
        slowest_artifact = None
        if trees:
            t = trees[0]
            slowest_artifact = {
                "traceId": t["traceId"],
                "durationMs": round(t["durationSec"] * 1e3, 2),
                "spanCount": t["spanCount"],
                "services": t["services"],
                "complete": t["complete"],
                "waterfall": collect.waterfall(t),
            }
        spool_bytes = sum(
            os.path.getsize(p) for p in trace_spool.spool_files(spool_100))
        qps_off = lanes["off"]["qps"]
        qps_1pct = lanes["sample_1pct"]["qps"]
        regression_1pct = (1.0 - qps_1pct / qps_off) if qps_off else 0.0
        out = {
            "lanes": lanes,
            "qps_off": qps_off,
            "qps_sample_0": lanes["sample_0"]["qps"],
            "qps_sample_1pct": qps_1pct,
            "qps_sample_100pct": lanes["sample_100pct"]["qps"],
            "regression_1pct_vs_off": round(regression_1pct, 4),
            "spool_bytes_after_100pct": spool_bytes,
            "spool_problems": problems,
            "slowest_trace": slowest_artifact,
            "spooled_spans": len(spans),
        }
        # acceptance: 1% sampling with the spool on costs ≤5% qps vs off
        assert regression_1pct <= 0.05, (
            f"trace plane at 1% sampling cost {regression_1pct:.1%} qps "
            f"({qps_1pct:.0f} vs {qps_off:.0f})")
        return out
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        trace_spool.close_export()
        use_storage(prev)
        storage.close()


# ---------------------------------------------------------------------------
# 7a2. performance-plane overhead (docs/observability.md "Metrics history &
#      SLOs"): the continuous plane must be cheap enough to leave on
# ---------------------------------------------------------------------------


def bench_obs_overhead(ctx) -> dict:
    """Deploy the recommendation template in the real query server and
    drive the same 16-connection closed loop under three performance-plane
    configurations: plane off; history + SLO engine on (the always-on
    default, with the self-scrape interval cranked 20× faster than the
    5000 ms default so its cost is actually exercised inside a short
    lane); and the full plane with the wall-stack sampler at 97 Hz on
    top. Two passes per lane, best qps kept. Archives the durable
    history's record count and on-disk bytes from the full lane — the
    artifact ``pio-tpu history <dir>`` would summarize."""
    import subprocess
    import sys as _sys
    import tempfile

    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.obs import history as hist
    from incubator_predictionio_tpu.obs.plane import close_perf_plane
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from incubator_predictionio_tpu.server.query_server import (
        QueryServer,
        ServerConfig,
    )

    n_users, n_items, n_events = 2000, 1000, (5_000 if SMALL else 20_000)
    duration = 2.0 if SMALL else 4.0
    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    prev = use_storage(storage)
    tmp = tempfile.mkdtemp(prefix="pio-obsov-")
    slo_conf = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "conf", "slo.json")
    hist_full = os.path.join(tmp, "hist-full")
    # one history dir PER LANE: the archived byte/record figures must
    # describe a single configuration, not the union of both on-lanes
    plane_envs = {
        "off": {},
        "history_slo": {
            "PIO_HISTORY_DIR": os.path.join(tmp, "hist-default"),
            "PIO_HISTORY_INTERVAL_MS": "250",
            "PIO_SLO_CONFIG": slo_conf,
        },
        "full_profiler": {
            "PIO_HISTORY_DIR": hist_full,
            "PIO_HISTORY_INTERVAL_MS": "250",
            "PIO_SLO_CONFIG": slo_conf,
            "PIO_PROFILE_HZ": "97",
        },
    }
    touched = sorted({k for env in plane_envs.values() for k in env})
    saved_env = {k: os.environ.get(k) for k in touched}

    def _apply_env(env: dict) -> None:
        for k in touched:
            os.environ.pop(k, None)
        os.environ.update(env)

    async def drive(variant_path: str, port: int) -> dict:
        server = QueryServer(
            ServerConfig(engine_variant=variant_path, ip="127.0.0.1",
                         port=port),
            storage=storage, ctx=ctx)
        await server.start()
        try:
            proc = await asyncio.create_subprocess_exec(
                _sys.executable, "-c", _SERVING_CLIENT_SCRIPT,
                f"http://127.0.0.1:{port}", str(duration), str(n_users),
                stdout=subprocess.PIPE)
            try:
                stdout, _ = await asyncio.wait_for(
                    proc.communicate(), timeout=duration + 120)
            except asyncio.TimeoutError:
                proc.kill()
                await proc.wait()
                raise
            assert proc.returncode == 0, proc.returncode
            return json.loads(stdout.decode().strip().splitlines()[-1])
        finally:
            await server.shutdown()

    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events)
        lanes: dict[str, dict] = {}
        for _pass in range(2):
            for lane, env in plane_envs.items():
                _apply_env(env)
                if not env:
                    # an earlier lane configured the module-wide recorder /
                    # sampler; "off" must really mean the plane is down
                    close_perf_plane()
                stats = asyncio.run(drive(variant_path, free_port()))
                prev_best = lanes.get(lane)
                if prev_best is None or stats["qps"] > prev_best["qps"]:
                    lanes[lane] = stats
        close_perf_plane()

        records = hist.read_history(hist_full)
        hist_bytes = sum(
            os.path.getsize(os.path.join(hist_full, f))
            for f in os.listdir(hist_full)) if os.path.isdir(hist_full) else 0
        qps_off = lanes["off"]["qps"]
        qps_on = lanes["history_slo"]["qps"]
        regression_on = (1.0 - qps_on / qps_off) if qps_off else 0.0
        out = {
            "lanes": lanes,
            "qps_off": qps_off,
            "qps_history_slo": qps_on,
            "qps_full_profiler": lanes["full_profiler"]["qps"],
            "regression_history_slo_vs_off": round(regression_on, 4),
            "history_records_full_lane": len(records),
            "history_bytes_full_lane": hist_bytes,
        }
        # acceptance: history + SLO engine (scraping 20× faster than the
        # default interval) costs ≤3% qps vs plane-off
        assert regression_on <= 0.03, (
            f"performance plane cost {regression_on:.1%} qps "
            f"({qps_on:.0f} vs {qps_off:.0f})")
        return out
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        close_perf_plane()
        use_storage(prev)
        storage.close()


# ---------------------------------------------------------------------------
# 7b. goodput under overload (docs/resilience.md "Overload & admission
#     control"): offered load at ~3× measured capacity through the real
#     admission layer — goodput and admitted-p99, not peak qps, are what a
#     production stack is judged on
# ---------------------------------------------------------------------------

#: Three-phase load client (argv after the repo root: base_url, warm_s,
#: cap_s, over_s, n_users). The protocol and the raw-socket driver live in
#: ONE place — ``tests/fixtures/loadgen.py`` — shared with the chaos storm
#: test; this subprocess shim only puts the repo on the path and runs it.
#: Phase 1 (warm): single closed-loop connection — strictly below capacity,
#: where zero requests may be shed. Phase 2 (capacity): 16 closed-loop
#: connections — the measured ceiling. Phase 3 (overload): open-loop at 3×
#: the phase-2 qps across 48 connections; 429/504 are counted, not errors.
_OVERLOAD_CLIENT_SCRIPT = """
import sys

sys.path.insert(0, sys.argv[1])
from tests.fixtures.loadgen import bench_main

bench_main(sys.argv[2:])
"""


def bench_overload(ctx) -> dict:
    """Offered load at ~3× measured capacity through the deployed query
    server's admission layer (resilience/admission.py): records goodput
    (qps of valid 200s, degraded included — brownout's whole point) and
    the p99 of *admitted* requests, plus the 429/504 shed tallies. The
    acceptance bars (goodput ≥ 70% of capacity, admitted p99 bounded,
    zero sheds below capacity) are asserted by the slow storm test
    (tests/test_chaos_procs.py); this scenario archives the numbers."""
    import subprocess
    import sys as _sys
    import tempfile

    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from incubator_predictionio_tpu.server.query_server import (
        QueryServer,
        ServerConfig,
    )

    n_users, n_items, n_events = 2000, 1000, (5_000 if SMALL else 20_000)
    warm_s, cap_s, over_s = (1.0, 1.5, 3.0) if SMALL else (2.0, 4.0, 8.0)
    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    prev = use_storage(storage)
    tmp = tempfile.mkdtemp(prefix="pio-bench-overload-")
    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events)
        port = free_port()

        async def drive() -> tuple[dict, dict, str]:
            server = QueryServer(
                ServerConfig(
                    engine_variant=variant_path, ip="127.0.0.1", port=port,
                    # the overload posture under test: a real per-query
                    # budget (the shed/deadline yardstick), a bounded
                    # queue, and a quick-reacting brownout
                    query_timeout_sec=0.5, admission_max_queue=128,
                    brownout_enter_sec=0.3, brownout_exit_sec=1.0),
                storage=storage, ctx=ctx)
            await server.start()
            try:
                proc = await asyncio.create_subprocess_exec(
                    _sys.executable, "-c", _OVERLOAD_CLIENT_SCRIPT,
                    os.path.dirname(os.path.abspath(__file__)),
                    f"http://127.0.0.1:{port}", str(warm_s), str(cap_s),
                    str(over_s), str(n_users), stdout=subprocess.PIPE)
                total_s = warm_s + cap_s + over_s
                try:
                    stdout, _ = await asyncio.wait_for(
                        proc.communicate(), timeout=total_s + 120)
                except asyncio.TimeoutError:
                    proc.kill()
                    await proc.wait()
                    raise
                assert proc.returncode == 0, proc.returncode
                client = json.loads(stdout.decode().strip().splitlines()[-1])
                import aiohttp

                async with aiohttp.ClientSession() as s:
                    health = await (await s.get(
                        f"http://127.0.0.1:{port}/health")).json()
                    metrics_text = await (await s.get(
                        f"http://127.0.0.1:{port}/metrics")).text()
                return client, health, metrics_text
            finally:
                await server.shutdown()

        client, health, metrics_text = asyncio.run(drive())
        cap = client["capacity"]
        over = client["overload"]
        warm = client["warm"]
        warm_shed = sum(v for k, v in warm["counts"].items()
                        if k in ("429", "504"))
        out = {
            "capacity_qps": cap["qps"],
            "capacity_p50_ms": cap["p50_ms"],
            "capacity_p99_ms": cap["p99_ms"],
            "offered_qps": over["offered_qps"],
            "goodput_qps": over["goodput_qps"],
            "goodput_ratio": round(
                over["goodput_qps"] / max(cap["qps"], 1e-9), 3),
            "admitted_p50_ms": over["p50_ms"],
            "admitted_p99_ms": over["p99_ms"],
            "p99_ratio": round(
                over["p99_ms"] / max(cap["p99_ms"], 1e-9), 3),
            "rejected_429": over["counts"].get("429", 0),
            "shed_504": over["counts"].get("504", 0),
            "degraded_200": over["counts"].get("degraded", 0),
            # the below-capacity invariant, recorded (the storm test
            # asserts it): nothing sheds on an unloaded server
            "below_capacity_sheds": warm_shed,
            "admission_health": health.get("admission"),
            "metrics": _metrics_snapshot(metrics_text),
        }
        return out
    finally:
        use_storage(prev)
        storage.close()


# ---------------------------------------------------------------------------
# 7c. fleet serving (docs/serving.md "Fleet serving"): 1 vs 3 query-server
#     replicas behind the fleet router at a FIXED offered load — the
#     horizontal-scaling story the router exists for
# ---------------------------------------------------------------------------

#: Load-client shim for the fleet scenario (argv after the repo root:
#: base_url, warm_s, cap_s, over_s, n_users, offered_qps). Same raw-socket
#: driver as overload (tests/fixtures/loadgen.py); offered_qps <= 0 runs
#: the capacity-measuring three-phase protocol, > 0 drives a fixed rate.
_FLEET_CLIENT_SCRIPT = """
import sys

sys.path.insert(0, sys.argv[1])
from tests.fixtures.loadgen import fleet_main

fleet_main(sys.argv[2:])
"""


def bench_fleet(ctx) -> dict:
    """Train once, deploy the SAME model in 1 and then 3 real query-server
    subprocesses, and drive the fleet router over each topology: the
    three-phase protocol sizes the 1-replica fleet, then the 3-replica
    fleet takes the same saturating offered load. Replicas deploy the
    service-floor fixture engine (tests/fixtures/floor_engine.py): each
    query pays a fixed service cost on top of the real ALS compute, so
    per-replica capacity is a known constant and goodput scaling measures
    the ROUTER's spreading/retry behaviour — on a 2-core box CPU-bound
    replicas would only contend with each other and the scaling number
    would describe the box, not the fleet. Per-replica /metrics snapshots
    ride along in the artifact."""
    import subprocess
    import sys as _sys
    import tempfile
    import urllib.request

    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.fleet.router import (
        RouterConfig,
        RouterServer,
    )
    from incubator_predictionio_tpu.parallel.launcher import free_port

    n_users, n_items, n_events = 2000, 1000, (5_000 if SMALL else 20_000)
    warm_s, cap_s, over_s = (1.0, 1.5, 3.0) if SMALL else (2.0, 4.0, 8.0)
    tmp = tempfile.mkdtemp(prefix="pio-bench-fleet-")
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "store.db"),
    }
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events,
            factory_path="tests.fixtures.floor_engine."
                         "FloorRecommendationEngine")
    finally:
        use_storage(prev)
        storage.close()

    def spawn_replica(port: int) -> subprocess.Popen:
        # real subprocesses (not in-process servers): replica parallelism
        # must come from the OS scheduler, not one GIL. --query-timeout 2.0
        # leaves room for a full micro-batch at the service floor
        # (64 x 25ms = 1.6s) inside the per-query budget. The 25ms floor
        # pins per-replica capacity near 40 qps so the 3-replica ideal
        # (~120 qps aggregate) stays inside this box's CPU headroom for
        # client + router + replicas — at a higher aggregate rate the 2
        # cores, not the router, become the measured constraint.
        return subprocess.Popen(
            [_sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "deploy", "-v", variant_path, "--ip", "127.0.0.1",
             "--port", str(port), "--query-timeout", "2.0"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PIO_NATIVE_HTTP": "0", **store_cfg,
                 "PIO_BENCH_SERVICE_FLOOR_MS": "25",
                 "PIO_ADMISSION_MAX_QUEUE": "128",
                 "PIO_BROWNOUT_ENTER_SEC": "0.3",
                 "PIO_BROWNOUT_EXIT_SEC": "1.0"},
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            start_new_session=True)

    def wait_ready(port: int, timeout_s: float = 240.0) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/", timeout=1.0) as resp:
                    if resp.status == 200:
                        return
            except Exception:  # noqa: BLE001 - still booting
                time.sleep(0.1)
        raise TimeoutError(f"replica on :{port} not ready")

    ports = [free_port() for _ in range(3)]
    replicas = [spawn_replica(p) for p in ports]

    async def drive_topology(
            replica_ports: list,
            offered_qps: float) -> tuple[dict, dict, dict]:
        """Router over the given replicas; offered_qps <= 0 measures.
        Returns (client results, router metrics, per-replica metrics) —
        both metric dicts are THIS run's deltas: the bench-process
        registry and the replica subprocesses outlive the run, so raw
        snapshots would accumulate every earlier topology's counts."""
        rport = free_port()
        router = RouterServer(RouterConfig(
            replicas=tuple(f"http://127.0.0.1:{p}" for p in replica_ports),
            ip="127.0.0.1", port=rport, deadline_sec=3.0,
            health_interval_sec=0.5))
        await router.start()
        try:
            import aiohttp

            async with aiohttp.ClientSession() as s:
                async def snap() -> tuple[dict, dict]:
                    router_m = _metrics_snapshot(await (await s.get(
                        f"http://127.0.0.1:{rport}/metrics")).text())
                    reps: dict = {}
                    for p in replica_ports:
                        try:
                            reps[f":{p}"] = _metrics_snapshot(
                                await (await s.get(
                                    f"http://127.0.0.1:{p}/metrics",
                                    timeout=aiohttp.ClientTimeout(
                                        total=5.0))).text())
                        except Exception as e:  # noqa: BLE001
                            reps[f":{p}"] = {"error": repr(e)}
                    return router_m, reps

                base_router, base_reps = await snap()
                proc = await asyncio.create_subprocess_exec(
                    _sys.executable, "-c", _FLEET_CLIENT_SCRIPT,
                    os.path.dirname(os.path.abspath(__file__)),
                    f"http://127.0.0.1:{rport}", str(warm_s), str(cap_s),
                    str(over_s), str(n_users), str(offered_qps),
                    stdout=subprocess.PIPE)
                total_s = warm_s + cap_s + over_s
                try:
                    stdout, _ = await asyncio.wait_for(
                        proc.communicate(), timeout=total_s + 120)
                except asyncio.TimeoutError:
                    proc.kill()
                    await proc.wait()
                    raise
                assert proc.returncode == 0, proc.returncode
                client = json.loads(
                    stdout.decode().strip().splitlines()[-1])
                final_router, final_reps = await snap()
            return (client,
                    _snapshot_delta(base_router, final_router),
                    {k: _snapshot_delta(base_reps.get(k, {}), v)
                     for k, v in final_reps.items()})
        finally:
            await router.shutdown()

    try:
        for p in ports:
            wait_ready(p)
        # topology 1: ONE replica behind the router — the three-phase
        # protocol measures its closed-loop capacity and offers 3×; the
        # micro-batcher often absorbs that outright (queue depth grows the
        # batches — the PR 3 effect), so ESCALATE the offered rate until
        # the single replica genuinely saturates (goodput < 85% of
        # offered): only a load one replica cannot serve can show what
        # three are worth
        single, router_m1, replica_m1 = asyncio.run(
            drive_topology(ports[:1], 0.0))
        over1 = single["overload"]
        offered = over1["offered_qps"]
        g1 = over1["goodput_qps"]
        for _ in range(3):
            if g1 < 0.85 * offered:
                break
            offered = round(3.0 * g1, 1)
            esc, router_m1, replica_m1 = asyncio.run(
                drive_topology(ports[:1], offered))
            over1 = esc["overload"]
            g1 = over1["goodput_qps"]
        single["overload"] = over1
        # topology 2: THREE replicas take the SAME saturating offered
        # load — goodput should scale with the fleet
        fleet3, router_m3, replica_m3 = asyncio.run(
            drive_topology(ports, offered))
        g3 = fleet3["overload"]["goodput_qps"]
        return {
            "offered_qps": offered,
            "single_capacity_qps": single["capacity"]["qps"],
            "single_goodput_qps": g1,
            "single_p99_ms": single["overload"]["p99_ms"],
            "fleet3_goodput_qps": g3,
            "fleet3_p99_ms": fleet3["overload"]["p99_ms"],
            # the acceptance headline: ≥ 2× single-replica goodput with 3
            # replicas at saturating load (ISSUE 6)
            "goodput_scaling": round(g3 / max(g1, 1e-9), 3),
            "p99_ratio": round(
                fleet3["overload"]["p99_ms"]
                / max(single["overload"]["p99_ms"], 1e-9), 3),
            "single_counts": single["overload"]["counts"],
            "fleet3_counts": fleet3["overload"]["counts"],
            "router_metrics_single": router_m1,
            "router_metrics_fleet3": router_m3,
            "replica_metrics_single": replica_m1,
            "replica_metrics_fleet3": replica_m3,
        }
    finally:
        import signal as _signal

        for proc in replicas:
            try:
                os.killpg(proc.pid, _signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


# ---------------------------------------------------------------------------
# 7d. multi-tenant serving (docs/tenancy.md): four tenants in ONE
#     query-server process under a shared byte budget, one tenant offering
#     3× its quota — the noisy-neighbor containment + packing numbers whose
#     acceptance bars the chaos test asserts
#     (tests/test_chaos_procs.py::test_multi_tenant_noisy_neighbor_contained)
# ---------------------------------------------------------------------------

#: Per-tenant load driver (argv after the repo root: host, port, path,
#: duration_s, target_qps, n_conns, body). Each tenant's driver is its OWN
#: subprocess: on a small host, concurrent drivers sharing one client event
#: loop pollute each other's latency tails through GIL/scheduler contention
#: — the victim's p99 would measure the CLIENT, not the platform.
_TENANT_CLIENT_SCRIPT = """
import sys

sys.path.insert(0, sys.argv[1])
from tests.fixtures.loadgen import tenant_main

tenant_main(sys.argv[2:])
"""


def bench_multi_tenant(ctx) -> dict:
    """Deploy FOUR tenants of the same recommendation model in one
    multi-tenant query server (server/tenancy.py) under a byte budget that
    fits only three, then measure the victim tenant at its steady rate
    twice: with the noisy neighbor offering exactly its quota (baseline —
    within-quota admitted load shares the host legitimately) and offering
    3× (storm). The headline ratios compare storm to baseline: containment
    means 3× offered looks like 1× to the victim, with the excess shed as
    orderly 429s. A final first-touch of the cold fourth tenant archives
    the packing motion (LRU eviction + cold load, both counted) and the
    per-tenant ledger. Identical engines per tenant on purpose: every
    cross-tenant difference is then the PLATFORM's doing (quota, packing),
    never the model's."""
    import subprocess
    import sys as _sys
    import tempfile
    import urllib.request

    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from tests.fixtures.loadgen import closed_loop, request_bytes

    n_users, n_items, n_events = 2000, 1000, (5_000 if SMALL else 20_000)
    window_s = 3.0 if SMALL else 6.0
    quota_qps = 30.0
    repo_root = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="pio-bench-tenants-")
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "store.db"),
    }
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events)
    finally:
        use_storage(prev)
        storage.close()

    # 1000-byte resident hints under a 3000-byte budget: three tenants fit,
    # the fourth provably cannot without evicting someone
    tenants = [
        {"tenant": "noisy", "engineVariant": variant_path,
         "quotaQps": quota_qps, "quotaBurst": quota_qps,
         "residentBytes": 1000},
        {"tenant": "victim", "engineVariant": variant_path,
         "residentBytes": 1000},
        {"tenant": "steady", "engineVariant": variant_path,
         "residentBytes": 1000},
        {"tenant": "latecomer", "engineVariant": variant_path,
         "residentBytes": 1000},
    ]
    tenants_file = os.path.join(tmp, "tenants.json")
    with open(tenants_file, "w") as f:
        json.dump(tenants, f)

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    body = json.dumps({"user": "u7", "num": 10})
    server = subprocess.Popen(
        [_sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
         "deploy", "-v", variant_path, "--tenants", tenants_file,
         "--ip", "127.0.0.1", "--port", str(port),
         "--query-timeout", "0.5"],
        cwd=repo_root,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **store_cfg,
             "PIO_TENANT_HBM_BUDGET": "3000"},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True)

    def http(method: str, path: str, payload=None, timeout=60.0):
        data = None if payload is None else json.dumps(payload).encode()
        req = urllib.request.Request(
            f"{base}{path}", data=data, method=method,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read() or b"null")

    def scrape() -> dict:
        with urllib.request.urlopen(f"{base}/metrics", timeout=10.0) as r:
            text = r.read().decode()
        return {k: v for k, v in _metrics_snapshot(text).items()
                if k.startswith("pio_tenant_")}

    def driver(tenant: str, qps: float) -> subprocess.Popen:
        return subprocess.Popen(
            [_sys.executable, "-c", _TENANT_CLIENT_SCRIPT, repo_root,
             "127.0.0.1", str(port), f"/engines/{tenant}/queries.json",
             str(window_s), str(qps), "16", body],
            cwd=repo_root, stdout=subprocess.PIPE, text=True)

    def measure(noisy_qps: float) -> tuple[dict, dict, dict]:
        """One concurrent (noisy, victim) window; returns their driver
        results plus the window's pio_tenant_* metric delta."""
        before = scrape()
        noisy = driver("noisy", noisy_qps)
        victim = driver("victim", victim_rate)
        n_out, _ = noisy.communicate(timeout=window_s + 60)
        v_out, _ = victim.communicate(timeout=60)
        assert noisy.returncode == 0 and victim.returncode == 0
        return (json.loads(n_out), json.loads(v_out),
                _snapshot_delta(before, scrape()))

    try:
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(f"{base}/", timeout=1.0) as r:
                    if r.status == 200:
                        break
            except Exception:  # noqa: BLE001 - still booting
                time.sleep(0.1)
        else:
            raise TimeoutError("multi-tenant server not ready")

        # cold loads are off the hot path by design: pay them up front for
        # every tenant but the latecomer — it must stay cold so its first
        # touch under the now-full budget IS the packing motion. "steady"
        # loads and then idles: the true LRU resident the eviction takes.
        for t in ("noisy", "victim", "steady"):
            http("POST", f"/engines/{t}/queries.json",
                 json.loads(body), timeout=120.0)
        # warm both hot tenants' batch buckets at real concurrency: a
        # mid-window first-compile would masquerade as neighbor
        # interference
        req_noisy = request_bytes("127.0.0.1", port, body.encode(),
                                  path="/engines/noisy/queries.json")
        req_victim = request_bytes("127.0.0.1", port, body.encode(),
                                   path="/engines/victim/queries.json")
        asyncio.run(closed_loop(
            "127.0.0.1", port, 8, 1.0, lambda: req_noisy))
        cap_counts, _ = asyncio.run(closed_loop(
            "127.0.0.1", port, 8, 2.0, lambda: req_victim))
        # victim's steady rate: well inside its solo capacity — headroom
        # the neighbor is NOT entitled to eat
        victim_rate = max(10.0, 0.35 * cap_counts.get(200, 0) / 2.0)

        base_noisy, base_victim, base_delta = measure(quota_qps)
        storm_noisy, storm_victim, storm_delta = measure(3.0 * quota_qps)

        # packing coda: the latecomer's first query under the full budget
        http("POST", "/engines/latecomer/queries.json",
             json.loads(body), timeout=120.0)
        snap = http("GET", "/tenants.json")

        vg_base = base_victim["goodput_qps"]
        p99_base = base_victim["p99_ms"]
        return {
            "tenants": len(tenants),
            "budget_bytes": 3000,
            "quota_qps": quota_qps,
            "victim_offered_qps": round(victim_rate, 1),
            "noisy_offered_qps": round(3.0 * quota_qps, 1),
            # acceptance bars (asserted by the chaos test, archived here):
            # victim goodput ratio ≥ 0.95 and p99 ratio ≤ 1.5 vs the
            # 1×-quota baseline
            "victim_goodput_ratio": round(
                storm_victim["goodput_qps"] / max(vg_base, 1e-9), 3),
            "victim_p99_ratio": round(
                storm_victim["p99_ms"] / max(p99_base, 1e-9), 3),
            "noisy_goodput_vs_quota": round(
                storm_noisy["goodput_qps"] / quota_qps, 3),
            "noisy_rejected_429": storm_noisy["counts"].get("429", 0),
            "noisy_shed_503": storm_noisy["counts"].get("503", 0),
            "baseline": {"noisy": base_noisy, "victim": base_victim},
            "storm": {"noisy": storm_noisy, "victim": storm_victim},
            "tenant_metrics_baseline": base_delta,
            "tenant_metrics_storm": storm_delta,
            "packing": {
                "resident_count": snap["residentCount"],
                "latecomer_cold_loads":
                    snap["tenants"]["latecomer"]["coldLoads"],
                "evicted": sorted(t for t, row in snap["tenants"].items()
                                  if not row["resident"]),
            },
            "tenants_snapshot": snap,
        }
    finally:
        import signal as _signal

        try:
            os.killpg(server.pid, _signal.SIGKILL)
        except ProcessLookupError:
            pass
        server.wait()


# ---------------------------------------------------------------------------
# 7c'. sharded fleet (docs/sharding.md "Multi-host shard owners"): the
#      catalog split ACROSS processes — scatter/gather parity cost vs one
#      process holding everything, plus failover MTTR when an owner takes
#      a SIGKILL
# ---------------------------------------------------------------------------


def bench_sharded_fleet(ctx) -> dict:
    """Train once, deploy the catalog two ways — ONE process holding every
    item row, and THREE shard-owner subprocesses behind the scatter/gather
    router — and measure what the split costs and what it buys:

    - **budget proof** (ShardSpec byte accounting): the whole catalog's
      training residency exceeds the per-process ``PIO_SHARD_HBM_BUDGET``
      the owners boot under; each owner's slice fits. The split is the
      only deploy shape that serves this catalog at that budget.
    - **latency**: client-observed p50/p95 through the router's fan-out +
      merge vs the single process, same queries — the bounded cost of
      going multi-host. Every sharded answer is checked against the
      single-process oracle (``wrong_answers`` must stay 0).
    - **failover MTTR**: SIGKILL one owner mid-traffic and restart it from
      its state dir; clock from the kill to the first degraded-but-flagged
      answer and to the first full oracle-exact answer. Partial-policy
      metric deltas from the router ride along."""
    import tempfile
    import urllib.error
    import urllib.request

    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from incubator_predictionio_tpu.sharding.table import ShardSpec
    from tests.fixtures.procs import ServerProc, ShardOwnerProc

    n_users, n_items = 1200, 900
    n_events = 4_000 if SMALL else 16_000
    n_lat = 40 if SMALL else 120
    n_shards = 3
    rank = 32
    tmp = tempfile.mkdtemp(prefix="pio-bench-shardfleet-")
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "store.db"),
    }
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events)
    finally:
        use_storage(prev)
        storage.close()

    # -- budget proof: byte accounting from the authoritative layout ----
    # items shard across owners; the user table replicates to every owner
    # (deltas for user rows ship everywhere — docs/sharding.md)
    item_spec = ShardSpec("item", n_items, rank + 1, n_shards)
    one_proc = ShardSpec("item", n_items, rank + 1, 1)
    user_bytes = ShardSpec("user", n_users, rank + 1, 1).train_bytes_per_shard()
    whole_catalog = one_proc.train_bytes_per_shard() + user_bytes
    per_owner = item_spec.train_bytes_per_shard() + user_bytes
    # a budget one owner fits under but the whole catalog does not
    budget = (whole_catalog + per_owner) // 2
    assert per_owner <= budget < whole_catalog

    def post(url: str, body: dict, timeout: float = 15.0):
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return (resp.status,
                        {k.lower(): v for k, v in resp.headers.items()},
                        json.loads(resp.read()))
        except urllib.error.HTTPError as e:
            try:
                body_out = json.loads(e.read())
            except Exception:  # noqa: BLE001 - non-JSON error body
                body_out = None
            return e.code, {k.lower(): v for k, v in e.headers.items()}, \
                body_out

    oport = free_port()
    owner_ports = [free_port() for _ in range(n_shards)]
    rport = free_port()
    oracle_url = f"http://127.0.0.1:{oport}"
    owner_urls = [f"http://127.0.0.1:{p}" for p in owner_ports]
    router_q = f"http://127.0.0.1:{rport}/queries.json"
    owner_env = {**store_cfg, "PIO_SHARD_HBM_BUDGET": str(budget)}

    def _owner(s: int) -> ShardOwnerProc:
        return ShardOwnerProc(
            s, n_shards, os.path.join(tmp, f"owner{s}"),
            ["-v", variant_path, "--ip", "127.0.0.1",
             "--port", str(owner_ports[s]), "--server-access-key", "sk"],
            env=owner_env)

    def _router_health() -> dict:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rport}/health", timeout=5.0) as resp:
            return json.loads(resp.read())

    def _router_metrics() -> dict:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rport}/metrics", timeout=5.0) as resp:
            return _metrics_snapshot(resp.read().decode())

    def lane_lat(url: str, queries: list) -> dict:
        lat = []
        for q in queries:
            t0 = time.perf_counter()
            st, _h, _b = post(url, q)
            lat.append((time.perf_counter() - t0) * 1e3)
            assert st == 200, st
        lat.sort()
        return {"p50_ms": round(lat[len(lat) // 2], 2),
                "p95_ms": round(lat[int(len(lat) * 0.95)], 2)}

    oracle = ServerProc(["deploy", "-v", variant_path, "--ip", "127.0.0.1",
                         "--port", str(oport)], env=store_cfg)
    owners = [_owner(s) for s in range(n_shards)]
    router = ServerProc(
        ["fleet", "route", "--ip", "127.0.0.1", "--port", str(rport),
         "--health-interval", "0.3", "--probe-timeout", "1.0",
         "--deadline", "3.0", "--server-access-key", "sk",
         *[a for u in owner_urls for a in ("--replica", u)]],
        env=dict(store_cfg))
    try:
        oracle.wait_ready(f"{oracle_url}/", timeout=240.0)
        for url, o in zip(owner_urls, owners):
            o.wait_ready(f"{url}/", timeout=240.0)
        router.wait_ready(f"http://127.0.0.1:{rport}/")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            h = _router_health()
            sh = h.get("sharding") or {}
            if sh.get("nRanges") == n_shards and not sh.get("downRanges"):
                break
            time.sleep(0.2)
        else:
            raise TimeoutError("router never adopted the shard topology")

        queries = [{"user": f"u{u}", "num": 10}
                   for u in range(min(n_lat, n_users))]
        oracle_ans = {}
        for q in queries:
            st, _h, body = post(f"{oracle_url}/queries.json", q)
            assert st == 200, st
            oracle_ans[q["user"]] = body["itemScores"]

        # -- latency lanes (and bitwise parity along the way) -----------
        single = lane_lat(f"{oracle_url}/queries.json", queries)
        wrong = 0
        for q in queries:
            st, hdrs, body = post(router_q, q)
            assert st == 200 and hdrs.get("x-pio-fleet-sharded") == \
                str(n_shards), (st, hdrs)
            if body["itemScores"] != oracle_ans[q["user"]]:
                wrong += 1
        sharded = lane_lat(router_q, queries)

        # -- failover MTTR: SIGKILL owner 1, restart from its state dir --
        m_before = _router_metrics()
        victim = 1
        owners[victim].kill9()
        t_kill = time.monotonic()
        owners[victim] = _owner(victim)
        t_degraded = t_full = None
        probe_i = 0
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline and t_full is None:
            q = queries[probe_i % len(queries)]
            probe_i += 1
            try:
                st, hdrs, body = post(router_q, q, timeout=10.0)
            except Exception:  # noqa: BLE001 - connection reset mid-kill
                continue
            now = time.monotonic()
            if st == 200 and "x-pio-partial" in hdrs:
                if t_degraded is None:
                    t_degraded = now - t_kill
            elif st == 200:
                if body["itemScores"] == oracle_ans[q["user"]]:
                    t_full = now - t_kill
            time.sleep(0.02)
        assert t_full is not None, "fleet never recovered a full answer"
        m_after = _router_metrics()

        return {
            "n_shards": n_shards,
            "hbm_budget_bytes": int(budget),
            "whole_catalog_bytes": int(whole_catalog),
            "per_owner_bytes": int(per_owner),
            "catalog_fits_one_process": bool(whole_catalog <= budget),
            "owner_fits_budget": bool(per_owner <= budget),
            "single_p50_ms": single["p50_ms"],
            "single_p95_ms": single["p95_ms"],
            "sharded_p50_ms": sharded["p50_ms"],
            "sharded_p95_ms": sharded["p95_ms"],
            "fanout_p50_cost": round(
                sharded["p50_ms"] / max(single["p50_ms"], 1e-9), 3),
            "wrong_answers": wrong,
            "parity_queries": len(queries),
            "failover_first_degraded_s": (
                round(t_degraded, 3) if t_degraded is not None else None),
            "failover_mttr_s": round(t_full, 3),
            "router_metrics_delta": _snapshot_delta(m_before, m_after),
        }
    finally:
        router.stop()
        oracle.stop()
        for o in owners:
            o.stop()


# ---------------------------------------------------------------------------
# 7d. storage failover (docs/replication.md): sustained ingest, SIGKILL the
#     primary storage server, promote the follower — MTTR and zero acked
#     loss through the quorum-replicated eventlog
# ---------------------------------------------------------------------------


def bench_storage_failover() -> dict:
    """Replicated storage pair (quorum ack) behind a real event-server
    subprocess whose EVENTDATA source lists BOTH endpoints
    (PIO_STORAGE_SOURCES_R_URLS): ingest at a steady rate, SIGKILL the
    primary mid-stream, promote the follower, and measure MTTR — kill →
    first write verifiably landed on the promoted follower — plus the
    recovery invariants (zero acked loss, zero duplicates, bumped epoch).
    Replication + fencing metric deltas from the survivor ride along."""
    import tempfile
    import threading
    import urllib.request

    from incubator_predictionio_tpu.parallel.launcher import free_port
    from tests.fixtures.procs import ServerProc, http_json

    tmp = tempfile.mkdtemp(prefix="pio-bench-failover-")
    pre_s = 2.0 if SMALL else 4.0
    from incubator_predictionio_tpu.data.storage import (
        AccessKey,
        App,
        Storage,
    )

    meta = Storage({
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "es-meta.db"),
    })
    app_id = meta.get_meta_data_apps().insert(App(0, "failover-bench"))
    key = meta.get_meta_data_access_keys().insert(AccessKey("", app_id, ()))
    meta.close()

    pport, fport, eport = free_port(), free_port(), free_port()
    purl, furl = f"http://127.0.0.1:{pport}", f"http://127.0.0.1:{fport}"

    def store_env(name):
        return {
            "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
            "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(tmp, f"{name}-log"),
            "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, f"{name}.db"),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
        }

    follower = ServerProc(
        ["storageserver", "--ip", "127.0.0.1", "--port", str(fport),
         "--repl-role", "follower", "--repl-sync", "quorum",
         "--repl-peer", purl], env=store_env("f"))
    primary = ServerProc(
        ["storageserver", "--ip", "127.0.0.1", "--port", str(pport),
         "--repl-role", "primary", "--repl-sync", "quorum",
         "--repl-peer", furl], env=store_env("p"))
    es = ServerProc(
        ["eventserver", "--ip", "127.0.0.1", "--port", str(eport)],
        env={
            "PIO_STORAGE_SOURCES_R_TYPE": "remote",
            "PIO_STORAGE_SOURCES_R_URLS": f"{purl},{furl}",
            "PIO_STORAGE_SOURCES_R_TIMEOUT": "3",
            "PIO_STORAGE_SOURCES_R_RETRY_MAX_ATTEMPTS": "1",
            "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "es-meta.db"),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
            "PIO_EVENT_WAL_DIR": os.path.join(tmp, "wal"),
            "PIO_EVENTSERVER_AUTH_TTL": "600",
            "PIO_EVENTSERVER_BREAKER_THRESHOLD": "2",
            "PIO_EVENTSERVER_BREAKER_RESET": "0.3",
            "PIO_RESILIENCE_BREAKER_RESET": "0.3",
        })

    acked: list = []
    stop = threading.Event()
    base = f"http://127.0.0.1:{eport}"
    event_body = {"event": "view", "entityType": "user",
                  "eventTime": "2024-01-01T00:00:00Z"}

    def ingest_loop():
        i = 0
        while not stop.is_set():
            try:
                status, body = http_json(
                    "POST", f"{base}/events.json?accessKey={key}",
                    dict(event_body, entityId=f"u{i}"), timeout=10.0)
                if status == 201:
                    acked.append(body["eventId"])
            except Exception:  # noqa: BLE001 - ambiguous, not acked
                pass
            i += 1
            time.sleep(0.01)

    def snap_metrics(url):
        try:
            with urllib.request.urlopen(f"{url}/metrics", timeout=5) as r:
                return _metrics_snapshot(r.read().decode())
        except Exception as e:  # noqa: BLE001
            return {"error": repr(e)}

    loader = threading.Thread(target=ingest_loop, daemon=True)
    try:
        follower.wait_ready(f"{furl}/")
        primary.wait_ready(f"{purl}/")
        es.wait_ready(f"{base}/")
        base_metrics = snap_metrics(furl)
        t0 = time.monotonic()
        loader.start()
        time.sleep(pre_s)
        pre_acked = len(acked)
        pre_qps = pre_acked / (time.monotonic() - t0)

        # SIGKILL the primary, promote the survivor (solo replica set —
        # the dead primary rejoins via `pio-tpu store scrub`)
        t_kill = time.monotonic()
        primary.kill9()
        t_reaped = time.monotonic()
        st, body = http_json("POST", f"{furl}/repl/promote",
                             {"peers": []}, timeout=10.0)
        assert st == 200, (st, body)
        t_promoted = time.monotonic()

        # MTTR: first write verifiably ON the promoted follower (write a
        # probe event through the event server, read it back from the
        # follower's RPC surface)
        mttr = None
        deadline = time.monotonic() + 60.0
        probe_n = 0
        while time.monotonic() < deadline:
            status, body = http_json(
                "POST", f"{base}/events.json?accessKey={key}",
                dict(event_body, entityId=f"probe-{probe_n}"),
                timeout=10.0)
            probe_n += 1
            if status == 201:
                acked.append(body["eventId"])
                st2, got = http_json(
                    "POST", f"{furl}/rpc/events/get",
                    {"event_id": body["eventId"], "app_id": app_id},
                    timeout=5.0)
                if st2 == 200 and got.get("result") is not None:
                    mttr = time.monotonic() - t_kill
                    break
            time.sleep(0.05)
        stop.set()
        loader.join(timeout=10.0)

        # drain the spill, then verify the invariants
        drain_deadline = time.monotonic() + 60.0
        spill_depth = None
        while time.monotonic() < drain_deadline:
            st, h = http_json("GET", f"{base}/health", timeout=5.0)
            spill_depth = h.get("spillQueueDepth")
            if st == 200 and spill_depth == 0:
                break
            time.sleep(0.1)
        _, fh = http_json("GET", f"{furl}/health")
        after_metrics = snap_metrics(furl)

        from incubator_predictionio_tpu.data.storage.remote import (
            RemoteStorageClient,
        )

        reader = RemoteStorageClient({"URL": furl, "TIMEOUT": "10"})
        ids = [e.event_id for e in reader.events().find(app_id)]
        lost = sorted(set(acked) - set(ids))
        dup = len(ids) - len(set(ids))
        if lost:
            # forensics BEFORE failing: where did each lost ack's bytes
            # end up? (p-log = unreplicated primary suffix, wal = event
            # server's spill, deadLettered = drain diverted it)
            from incubator_predictionio_tpu.resilience.wal import (
                inspect_dir,
            )

            def grep(path, needle):
                try:
                    with open(path, "rb") as fh:
                        return needle.encode() in fh.read()
                except OSError:
                    return None

            st_h, es_h = http_json("GET", f"{base}/health", timeout=5.0)
            forensics = {
                "deadLettered": es_h.get("deadLettered"),
                "wal": inspect_dir(os.path.join(tmp, "wal")),
                "lost": {
                    lid: {
                        "in_primary_log": grep(os.path.join(
                            tmp, "p-log", "app_1.piolog"), lid),
                        "in_follower_log": grep(os.path.join(
                            tmp, "f-log", "app_1.piolog"), lid),
                    } for lid in lost[:8]},
            }
            raise AssertionError(
                f"acked events lost across failover: {lost[:8]} — "
                f"{json.dumps(forensics, default=str)}")
        assert dup == 0, f"{dup} duplicate ids served"
        repl_delta = {
            k: v for k, v in _snapshot_delta(base_metrics,
                                             after_metrics).items()
            if k.startswith(("pio_repl_", "pio_scrub_"))}
        return {
            "pre_failover_ack_qps": round(pre_qps, 1),
            "acked_total": len(acked),
            "stored_total": len(ids),
            "acked_lost": len(lost),
            "duplicate_ids": dup,
            "mttr_s": round(mttr, 3) if mttr is not None else None,
            "kill_reap_s": round(t_reaped - t_kill, 3),
            "promote_rpc_s": round(t_promoted - t_reaped, 3),
            "final_spill_depth": spill_depth,
            "epoch_after": (fh.get("replication") or {}).get("epoch"),
            "role_after": (fh.get("replication") or {}).get("role"),
            # lag/fencing/repair counters across the whole run, survivor's
            # point of view (applied bytes = everything quorum shipped)
            "survivor_repl_metrics_delta": repl_delta,
        }
    finally:
        stop.set()
        es.stop()
        primary.stop()
        follower.stop()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def bench_disaster_recovery() -> dict:
    """The DR drill (docs/dr.md): sustained ingest against a real event
    server on the eventlog backend, a backup taken IN FLIGHT, ``rm -rf``
    of the whole live data surface (eventlog + WAL + metadata), a
    verified restore, restart, and the recovery invariants: zero
    acked-event loss up to the cut + replayed WAL tail (RPO =
    post-backup window only, asserted by id set, forensics on any
    discrepancy) with the restore wall time reported as RTO. A second
    phase backs up a replication FOLLOWER's data dir mid-ingest and
    measures the primary's ack goodput during the copy — read-only views,
    primary serving untouched."""
    import shutil
    import tempfile
    import threading

    from incubator_predictionio_tpu.backup import (
        BackupSource,
        RestoreTargets,
        create_backup,
        restore_backup,
    )
    from incubator_predictionio_tpu.data.storage import (
        AccessKey,
        App,
        Storage,
    )
    from incubator_predictionio_tpu.native import format as fmt
    from incubator_predictionio_tpu.obs.metrics import REGISTRY
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from tests.fixtures.procs import ServerProc, http_json

    tmp = tempfile.mkdtemp(prefix="pio-bench-dr-")
    pre_s = 1.5 if SMALL else 3.0
    event_body = {"event": "view", "entityType": "user",
                  "eventTime": "2024-01-01T00:00:00Z"}
    m_before = _metrics_snapshot(REGISTRY.expose())

    def seed_meta(db_path):
        meta = Storage({
            "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQ_PATH": db_path,
        })
        app_id = meta.get_meta_data_apps().insert(App(0, "dr-bench"))
        key = meta.get_meta_data_access_keys().insert(
            AccessKey("", app_id, ()))
        meta.close()
        return app_id, key

    def ingest_loop(base, key, acked, stop, lock):
        i = 0
        while not stop.is_set():
            try:
                status, body = http_json(
                    "POST", f"{base}/events.json?accessKey={key}",
                    dict(event_body, entityId=f"u{i}"), timeout=10.0)
                if status == 201:
                    with lock:
                        acked.append(body["eventId"])
            except Exception:  # noqa: BLE001 - ambiguous, not acked
                pass
            i += 1
            time.sleep(0.005)

    # ---- phase A: full-host-loss drill ---------------------------------
    elog_dir = os.path.join(tmp, "live-elog")
    wal_dir = os.path.join(tmp, "wal")
    meta_db = os.path.join(tmp, "meta.db")
    bdir = os.path.join(tmp, "backups")
    env = {
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": elog_dir,
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": meta_db,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
        "PIO_EVENT_WAL_DIR": wal_dir,
        "PIO_EVENTSERVER_AUTH_TTL": "600",
    }
    app_id, key = seed_meta(meta_db)
    eport = free_port()
    base = f"http://127.0.0.1:{eport}"
    acked: list = []
    lock = threading.Lock()
    stop = threading.Event()
    es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                     "--port", str(eport)], env=env)
    es2 = None
    loader = threading.Thread(
        target=ingest_loop, args=(base, key, acked, stop, lock),
        daemon=True)
    try:
        es.wait_ready(f"{base}/")
        # warm synchronously before the measured window: the server's
        # first insert pays one-time lazy init (native-lib probe) that
        # would otherwise eat the whole SMALL ingest window
        status, body = http_json(
            "POST", f"{base}/events.json?accessKey={key}",
            dict(event_body, entityId="warm"), timeout=30.0)
        assert status == 201, (status, body)
        with lock:
            acked.append(body["eventId"])
        loader.start()
        time.sleep(pre_s)
        with lock:
            n_before_backup = len(acked)
        t_bk = time.monotonic()
        meta_storage = Storage({
            "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQ_PATH": meta_db,
        })
        # ingest keeps flowing while the copy runs — the cut freezes the
        # point in time, not the writers
        rep = create_backup(bdir, BackupSource(
            eventlog_dir=elog_dir, wal_dir=wal_dir, storage=meta_storage))
        meta_storage.close()
        backup_s = time.monotonic() - t_bk
        assert rep["verify"]["clean"], rep["verify"]["errors"]
        with lock:
            n_after_backup = len(acked)
        time.sleep(pre_s / 2)
        es.kill9()
        stop.set()
        loader.join(timeout=10.0)
        acked_all = list(acked)

        # the disaster: the entire live data surface goes away
        shutil.rmtree(elog_dir)
        shutil.rmtree(wal_dir, ignore_errors=True)
        os.remove(meta_db)

        # RTO clock: restore start → first post-restore ack verifiably in
        # the restored store (restore wall time reported separately)
        t_restore = time.monotonic()
        # full repository config: the WAL tail must replay into the
        # restored EVENTLOG, not a defaulted sqlite EVENTDATA
        restore_storage = Storage(env)
        rr = restore_backup(bdir, RestoreTargets(
            eventlog_dir=elog_dir, wal_dir=wal_dir),
            storage=restore_storage, replay_wal=True)
        restore_storage.close()
        restore_wall_s = time.monotonic() - t_restore
        es2 = ServerProc(["eventserver", "--ip", "127.0.0.1",
                          "--port", str(eport)], env=env)
        es2.wait_ready(f"{base}/")
        status, body = http_json(
            "POST", f"{base}/events.json?accessKey={key}",
            dict(event_body, entityId="probe-after-restore"), timeout=30.0)
        assert status == 201, (status, body)
        probe = body["eventId"]
        rto_s = time.monotonic() - t_restore
        es2.sigterm()
        es2.wait_exit()
    finally:
        stop.set()
        es.stop()
        if es2 is not None:
            es2.stop()

    # forensic parity by id set on the restored log itself
    with open(os.path.join(elog_dir, "app_1.piolog"), "rb") as f:
        buf = f.read()
    strings, _live, _ = fmt.read_log(buf)
    counts: dict = {}
    for _off, kind, payload in fmt.iter_records(buf):
        if kind == fmt.KIND_EVENT:
            eid, _ = fmt.decode_event_payload(payload, strings)
            counts[eid] = counts.get(eid, 0) + 1
    stored = set(counts)
    dup = {k: v for k, v in counts.items() if v > 1}
    pre_backup = set(acked_all[:n_before_backup])
    post_backup = set(acked_all[n_before_backup:])
    lost = (pre_backup | post_backup) - stored
    if (pre_backup - stored) or dup or not (lost <= post_backup):
        forensics = {
            "lost_pre_backup": sorted(pre_backup - stored)[:8],
            "lost_outside_window": sorted(lost - post_backup)[:8],
            "duplicates": dict(list(dup.items())[:8]),
            "cuts": rep["cuts"],
            "restore": rr,
        }
        raise AssertionError(
            f"DR invariants violated: {json.dumps(forensics, default=str)}")
    assert probe in stored

    # ---- phase B: backup-from-follower, primary goodput untouched ------
    follower_phase = _dr_follower_backup_phase(tmp, pre_s, event_body,
                                               ingest_loop)

    m_after = _metrics_snapshot(REGISTRY.expose())
    backup_delta = {k: v for k, v in
                    _snapshot_delta(m_before, m_after).items()
                    if k.startswith("pio_backup_")}
    result = {
        "acked_total": len(acked_all),
        "acked_before_backup": n_before_backup,
        "acked_after_backup": len(acked_all) - n_after_backup,
        "stored_total": len(stored),
        "acked_lost_pre_cut": len(pre_backup - stored),
        "rpo_lost_post_backup": len(lost),
        "duplicate_ids": len(dup),
        "backup_create_s": round(backup_s, 3),
        "backup_bytes_stored": rep["bytesStored"],
        "restore_wall_s_rto": round(restore_wall_s, 3),
        "recovery_total_s": round(rto_s, 3),
        "wal_tail_replayed": rr.get("walReplayed"),
        "backup_metrics_delta": backup_delta,
        "follower_backup": follower_phase,
    }
    shutil.rmtree(tmp, ignore_errors=True)
    return result


def _dr_follower_backup_phase(tmp, pre_s, event_body, ingest_loop) -> dict:
    """Replicated pair (quorum), event server in front: measure the
    primary's ack goodput in a clean window, then again WHILE a backup
    reads the FOLLOWER's data dir — the copy must not dent primary
    ingest (acceptance: no goodput regression; asserted at ≥0.6 to ride
    host noise, reported exactly)."""
    import shutil
    import threading

    from incubator_predictionio_tpu.backup import (
        BackupSource,
        create_backup,
    )
    from incubator_predictionio_tpu.data.storage import (
        AccessKey,
        App,
        Storage,
    )
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from tests.fixtures.procs import ServerProc, http_json

    meta_db = os.path.join(tmp, "f-es-meta.db")
    meta = Storage({
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": meta_db,
    })
    app_id = meta.get_meta_data_apps().insert(App(0, "dr-follower"))
    key = meta.get_meta_data_access_keys().insert(AccessKey("", app_id, ()))
    meta.close()

    pport, fport, eport = free_port(), free_port(), free_port()
    purl, furl = f"http://127.0.0.1:{pport}", f"http://127.0.0.1:{fport}"
    f_log = os.path.join(tmp, "f-follower-log")

    def store_env(name, log_dir):
        return {
            "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
            "PIO_STORAGE_SOURCES_EL_PATH": log_dir,
            "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(
                tmp, f"{name}.db"),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
        }

    follower = ServerProc(
        ["storageserver", "--ip", "127.0.0.1", "--port", str(fport),
         "--repl-role", "follower", "--repl-sync", "quorum",
         "--repl-peer", purl],
        env=store_env("f-follower", f_log))
    primary = ServerProc(
        ["storageserver", "--ip", "127.0.0.1", "--port", str(pport),
         "--repl-role", "primary", "--repl-sync", "quorum",
         "--repl-peer", furl],
        env=store_env("f-primary", os.path.join(tmp, "f-primary-log")))
    es = ServerProc(
        ["eventserver", "--ip", "127.0.0.1", "--port", str(eport)],
        env={
            "PIO_STORAGE_SOURCES_R_TYPE": "remote",
            "PIO_STORAGE_SOURCES_R_URLS": f"{purl},{furl}",
            "PIO_STORAGE_SOURCES_R_TIMEOUT": "3",
            "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQ_PATH": meta_db,
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
            "PIO_EVENT_WAL_DIR": os.path.join(tmp, "f-wal"),
            "PIO_EVENTSERVER_AUTH_TTL": "600",
        })
    base = f"http://127.0.0.1:{eport}"
    acked: list = []
    lock = threading.Lock()
    stop = threading.Event()
    loader = threading.Thread(
        target=ingest_loop, args=(base, key, acked, stop, lock),
        daemon=True)
    try:
        follower.wait_ready(f"{furl}/")
        primary.wait_ready(f"{purl}/")
        es.wait_ready(f"{base}/")
        status, _body = http_json(
            "POST", f"{base}/events.json?accessKey={key}",
            dict(event_body, entityId="warm"), timeout=30.0)
        assert status == 201, (status, _body)
        loader.start()
        time.sleep(pre_s / 2)  # warm
        with lock:
            n0 = len(acked)
        time.sleep(pre_s)
        with lock:
            n1 = len(acked)
        clean_qps = (n1 - n0) / pre_s

        # backup the FOLLOWER's dir while ingest continues; keep copying
        # (full, no incremental dedupe) for the whole measured window so
        # the window is copy-saturated
        bdir = os.path.join(tmp, "f-backups")
        copies = 0
        copy_stop = time.monotonic() + pre_s
        with lock:
            n2 = len(acked)
        while time.monotonic() < copy_stop:
            create_backup(bdir, BackupSource(eventlog_dir=f_log),
                          incremental=False, self_verify=False)
            copies += 1
        copy_window = time.monotonic() - (copy_stop - pre_s)
        with lock:
            n3 = len(acked)
        during_qps = (n3 - n2) / copy_window
        stop.set()
        loader.join(timeout=10.0)
    finally:
        stop.set()
        es.stop()
        primary.stop()
        follower.stop()

    ratio = during_qps / clean_qps if clean_qps else None
    assert ratio is None or ratio >= 0.6, (
        f"follower-dir backup dented primary ingest: {during_qps:.1f} "
        f"vs {clean_qps:.1f} ack/s (ratio {ratio:.2f})")
    return {
        "clean_ack_qps": round(clean_qps, 1),
        "during_copy_ack_qps": round(during_qps, 1),
        "goodput_ratio": round(ratio, 3) if ratio is not None else None,
        "backup_copies_in_window": copies,
    }


# ---------------------------------------------------------------------------
# 8. event-server ingestion throughput (EventServer.scala:261-462 hot path)
# ---------------------------------------------------------------------------

#: Standalone event-server process (argv: port, backend, path). Seeds the
#: app + access key in ITS OWN storage (built from PIO_STORAGE_* style
#: config), then serves — the bench client reaches it only over the socket,
#: exactly like a production deployment.
_INGEST_SERVER_SCRIPT = """
import os, sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
port, backend, path = int(sys.argv[1]), sys.argv[2], sys.argv[3]
# EVENTDATA on the benched backend; METADATA in-memory (eventlog is an
# EVENTDATA-only backend, like the reference's HBase)
cfg = {
    "PIO_STORAGE_SOURCES_META_TYPE": "memory",
    "PIO_STORAGE_SOURCES_EV_TYPE": backend,
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "META",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "META",
}
if path:
    cfg["PIO_STORAGE_SOURCES_EV_PATH"] = path
from incubator_predictionio_tpu.data.storage import AccessKey, App, Storage
from incubator_predictionio_tpu.server.event_server import (
    EventServerConfig, serve_forever)

storage = Storage(cfg)
app_id = storage.get_meta_data_apps().insert(App(0, "ingest-app"))
storage.get_meta_data_access_keys().insert(
    AccessKey(key="bench-key", app_id=app_id, events=()))
storage.get_events().init(app_id)
serve_forever(EventServerConfig(ip="127.0.0.1", port=port, stats=False),
              storage)
"""


def bench_ingestion() -> dict:
    """Batch-ingest throughput per EVENTDATA backend, out-of-process: the
    event server runs as its own OS process on each durable backend (sqlite
    WAL/fsync, eventlog append+CRC) plus memory as the no-durability ceiling;
    the client drives a real socket (EventServer.scala:261-462 hot path)."""
    import subprocess
    import sys as _sys
    import tempfile

    from incubator_predictionio_tpu.parallel.launcher import free_port

    out: dict[str, float] = {}
    n_batches = 40 if SMALL else 400  # longer run: 1-core noise averages out
    payload = [
        {"event": "view", "entityType": "user", "entityId": f"u{i}",
         "targetEntityType": "item", "targetEntityId": f"i{i % 97}"}
        for i in range(50)  # the reference's 50-event batch cap
    ]

    async def drive(port: int) -> float:
        # Raw-socket HTTP/1.1 keep-alive client with a PRECOMPUTED request:
        # the client shares the single core with the server under test, and
        # an aiohttp client costs more per request than the server's whole
        # handler — measuring through it reports the client, not the server.
        body = json.dumps(payload).encode()
        req = (
            f"POST /batch/events.json?accessKey=bench-key HTTP/1.1\r\n"
            f"Host: 127.0.0.1:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode() + body

        async def ready() -> None:
            for _ in range(120):
                if proc.poll() is not None:  # died at startup: fail fast
                    raise RuntimeError(
                        f"event server exited rc={proc.returncode}")
                try:
                    r, w = await asyncio.open_connection("127.0.0.1", port)
                    w.close()
                    await w.wait_closed()
                    return
                except OSError:
                    await asyncio.sleep(0.25)
            raise RuntimeError("event server did not come up")

        async def post(r, w) -> None:
            w.write(req)
            await w.drain()
            status = await r.readline()
            assert b" 200 " in status, status
            length = None
            while True:
                line = await r.readline()
                if line in (b"\r\n", b""):
                    break
                if line.lower().startswith(b"content-length:"):
                    length = int(line.split(b":")[1])
            assert length is not None
            await r.readexactly(length)

        await ready()
        conns = [await asyncio.open_connection("127.0.0.1", port)
                 for _ in range(8)]
        try:
            await post(*conns[0])  # warmup
            t0 = time.perf_counter()

            async def worker(conn, n: int) -> None:
                for _ in range(n):
                    await post(*conn)

            per = n_batches // 8
            await asyncio.gather(*(worker(c, per) for c in conns))
            return 8 * per * 50 / (time.perf_counter() - t0)
        finally:
            for _, w in conns:
                w.close()

    for backend in ("memory", "sqlite", "eventlog"):
        tmp = tempfile.mkdtemp(prefix=f"pio-ingest-{backend}-")
        path = "" if backend == "memory" else os.path.join(tmp, "store")
        port = free_port()
        proc = subprocess.Popen(
            [_sys.executable, "-c", _INGEST_SERVER_SCRIPT,
             str(port), backend, path],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        try:
            eps = asyncio.run(drive(port))
            out[f"ingest_events_per_sec_{backend}"] = round(eps, 1)
        except Exception as e:  # noqa: BLE001 - one backend must not zero the rest
            _log(f"ingestion[{backend}] FAILED: {e!r}")
            out[f"ingest_events_per_sec_{backend}"] = 0.0
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
    # headline key: the default deployment backend (sqlite)
    out["ingest_events_per_sec"] = out.get("ingest_events_per_sec_sqlite", 0.0)
    return out


# ---------------------------------------------------------------------------

def bench_ingest_durability() -> dict:
    """The durability tax, isolated (ISSUE 4): spill-ack throughput with
    the in-memory deque (PR 1's crash-lossy baseline) vs the WAL with and
    without fsync. Batches of 50 mirror the event server's group-commit
    (one append+fsync per /batch request), so the fsync lane measures what
    a spilled batch ack actually pays on this host's storage."""
    import collections
    import tempfile

    from incubator_predictionio_tpu.resilience.wal import SpillWal

    N_BATCHES, BATCH = 40, 50

    def mk_batch(b: int) -> list[dict]:
        return [{"event": {"event": "rate", "entityType": "user",
                           "entityId": f"u{b}-{i}", "eventId": f"{b:04d}{i:04d}",
                           "eventTime": "2024-01-01T00:00:00Z",
                           "properties": {"rating": 5}},
                 "app_id": 1, "channel_id": None} for i in range(BATCH)]

    batches = [mk_batch(b) for b in range(N_BATCHES)]
    out: dict[str, float] = {}

    t0 = time.perf_counter()
    dq: collections.deque = collections.deque()
    for batch in batches:
        dq.extend(batch)
    out["memory_events_per_sec"] = N_BATCHES * BATCH / max(
        time.perf_counter() - t0, 1e-9)

    for label, fsync in (("wal_nofsync", False), ("wal_fsync", True)):
        with tempfile.TemporaryDirectory() as d:
            wal = SpillWal(d, fsync=fsync)
            t0 = time.perf_counter()
            for batch in batches:
                wal.append([dict(r) for r in batch])
            dt = time.perf_counter() - t0
            wal.close()
        out[f"{label}_events_per_sec"] = N_BATCHES * BATCH / dt
        out[f"{label}_batch_ms"] = dt / N_BATCHES * 1e3
    # the headline ratio BENCH_*.json tracks from this PR on: how much of
    # the in-memory ack rate survives the fsync-on-ack contract
    out["fsync_tax_vs_memory"] = (
        out["wal_fsync_events_per_sec"] / out["memory_events_per_sec"])
    out["fsync_tax_vs_nofsync"] = (
        out["wal_fsync_events_per_sec"] / out["wal_nofsync_events_per_sec"])
    return out


def build_result_line(configs: dict, device_info: dict,
                      wedged: str | None = None) -> str:
    """The single JSON artifact line."""
    rec = configs.get("recommendation", {})
    rec_scaled = configs.get("recommendation_scaled", {})
    serving = configs.get("serving", {})
    line = {
        "metric": "recommendation_scaled_train_throughput",
        "value": rec_scaled.get("events_per_sec", 0.0),
        "unit": "events/sec/chip",
        "vs_baseline": rec_scaled.get(
            "vs_host_numpy", rec.get("vs_host_numpy", 0.0)),
        "platform": device_info.get("platform"),
        "device": device_info.get("device"),
        "mfu": rec_scaled.get("mfu"),
        "hbm_util": rec_scaled.get("hbm_util", rec.get("hbm_util")),
        "predict_p50_ms": serving.get("predict_p50_ms"),
        "predict_p95_ms": serving.get("predict_p95_ms"),
        "configs": configs,
    }
    if wedged:
        line["wedged"] = wedged
    return json.dumps(line)


# suite order; "ingestion" and "ingest_durability" never touch the device
# (they bench the event servers' durable write paths)
CONFIG_NAMES = ["recommendation", "recommendation_scaled", "classification",
                "similarproduct", "ecommerce_retrieval", "retrieval_scale",
                "sharded_serving", "sequential", "serving", "trace_overhead",
                "obs_overhead", "overload", "fleet", "multi_tenant",
                "sharded_fleet",
                "ingestion", "ingest_durability",
                "streaming_freshness", "storage_failover",
                "continuous_training", "disaster_recovery",
                "distributed_training"]
# "fleet" and "sharded_fleet" are device-free too: their replicas are CPU
# subprocesses (a fleet on one host) — the scenarios measure the ROUTER's
# horizontal scaling and scatter/gather cost, not chip throughput; "sharded_serving" likewise runs on 8 virtual CPU
# devices (merge/layout architecture, not chip throughput);
# "continuous_training" measures the control plane's recovery clock, not
# the chip
DEVICE_FREE = {"ingestion", "ingest_durability", "fleet", "multi_tenant",
               "sharded_fleet",
               "streaming_freshness", "storage_failover",
               "sharded_serving", "continuous_training",
               "disaster_recovery", "distributed_training"}


def _build_suite(ctx, peaks, device) -> dict:
    return {
        "recommendation": lambda: bench_recommendation(ctx, peaks),
        "recommendation_scaled": lambda: bench_recommendation_scaled(
            ctx, peaks, device),
        "classification": lambda: bench_classification(ctx, peaks),
        "similarproduct": lambda: bench_similarproduct(ctx, peaks),
        "ecommerce_retrieval": lambda: bench_ecommerce_retrieval(ctx, peaks, device),
        "retrieval_scale": lambda: bench_retrieval_scale(ctx, peaks, device),
        "sharded_serving": lambda: bench_sharded_serving(ctx, peaks, device),
        "sequential": lambda: bench_sequential(ctx, peaks, device),
        "serving": lambda: bench_serving(ctx),
        "trace_overhead": lambda: bench_trace_overhead(ctx),
        "obs_overhead": lambda: bench_obs_overhead(ctx),
        "overload": lambda: bench_overload(ctx),
        "fleet": lambda: bench_fleet(ctx),
        "multi_tenant": lambda: bench_multi_tenant(ctx),
        "sharded_fleet": lambda: bench_sharded_fleet(ctx),
        "ingestion": lambda: bench_ingestion(),
        "ingest_durability": lambda: bench_ingest_durability(),
        "streaming_freshness": lambda: bench_streaming_freshness(),
        "storage_failover": lambda: bench_storage_failover(),
        "continuous_training": lambda: bench_continuous_training(),
        "disaster_recovery": lambda: bench_disaster_recovery(),
        "distributed_training": lambda: bench_distributed_training(),
    }


# ---------------------------------------------------------------------------
# 10. streaming freshness (docs/streaming.md): event→recommendation-visible
#     latency through the incremental delta pipeline vs the full
#     retrain+redeploy cycle, plus the updater's sustained fold throughput
# ---------------------------------------------------------------------------


def bench_streaming_freshness() -> dict:
    """Train the recommendation template on the eventlog backend, deploy it
    in a real in-process query server, then stream live events through the
    updater (tail → fold → delta → POST /delta with smoke-gate + probation)
    and measure how long an event takes to become serving-visible — against
    the only alternative the repo had before: a full retrain + /reload."""
    import datetime as dt_mod
    import tempfile

    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import Storage, use_storage
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.server.query_server import (
        QueryServer,
        ServerConfig,
    )
    from incubator_predictionio_tpu.streaming.updater import (
        StreamUpdater,
        UpdaterConfig,
        load_base_model,
    )

    ctx = MeshContext.create()
    tmp = tempfile.mkdtemp(prefix="pio-stream-bench-")
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "store.db"),
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(tmp, "eventlog"),
        **{f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE": src
           for repo, src in (("METADATA", "SQ"), ("EVENTDATA", "EL"),
                             ("MODELDATA", "SQ"))},
    }
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    n_users, n_items = 2000, 1000
    n_events = 5_000 if SMALL else 20_000
    rounds = 4 if SMALL else 8
    events_per_round = 25
    sustained_n = 2_000 if SMALL else 8_000
    utc = dt_mod.timezone.utc
    rng = np.random.default_rng(5)

    def live_events(n):
        now = dt_mod.datetime.now(utc)
        return [
            Event(event="rate", entity_type="user",
                  entity_id=f"u{rng.integers(0, n_users)}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, n_items)}",
                  properties=DataMap({"rating": float(1 + 4 * rng.random())}),
                  event_time=now)
            for _ in range(n)
        ]

    try:
        variant_path = _train_recommendation(
            ctx, storage, tmp, n_users, n_items, n_events)
        app = storage.get_meta_data_apps().get_by_name("bench-app")
        events_store = storage.get_events()
        port = free_port()
        base = f"http://127.0.0.1:{port}"

        async def drive() -> dict:
            import aiohttp

            loop = asyncio.get_running_loop()
            server = QueryServer(
                ServerConfig(engine_variant=variant_path, ip="127.0.0.1",
                             port=port),
                storage=storage, ctx=ctx)
            await server.start()
            try:
                model, instance_id, event_names, defaults = \
                    await loop.run_in_executor(
                        None, lambda: load_base_model(variant_path, storage))
                updater = StreamUpdater(
                    UpdaterConfig(
                        state_dir=os.path.join(tmp, "stream-state"),
                        feed_path=events_store.log_path(app.id),
                        replicas=(base,), batch_events=16_384),
                    model, instance_id, event_names=event_names,
                    default_values=defaults)
                async with aiohttp.ClientSession() as s:
                    m_before = _metrics_snapshot(
                        await (await s.get(f"{base}/metrics")).text())
                    # -- freshness rounds -----------------------------
                    freshness_ms = []
                    for _ in range(rounds):
                        batch = live_events(events_per_round)
                        t0 = time.perf_counter()
                        await loop.run_in_executor(
                            None, events_store.insert_batch, batch, app.id)
                        out = await loop.run_in_executor(
                            None, updater.run_once)
                        assert out["status"] == "applied", out
                        health = await (await s.get(
                            f"{base}/health")).json()
                        stream = health["deployment"]["streaming"]
                        assert stream["lastDeltaSeq"] == out["toSeq"]
                        freshness_ms.append(
                            (time.perf_counter() - t0) * 1e3)
                    # -- sustained fold throughput --------------------
                    await loop.run_in_executor(
                        None, events_store.insert_batch,
                        live_events(sustained_n), app.id)
                    t0 = time.perf_counter()
                    folded = 0
                    while folded < sustained_n:
                        out = await loop.run_in_executor(
                            None, updater.run_once)
                        if out["status"] != "applied":
                            break
                        folded += out["events"]
                    sustained_sec = time.perf_counter() - t0
                    # freshness AT HEAD: probe health NOW, after the
                    # catch-up fold — not a snapshot from the rounds loop
                    health = await (await s.get(f"{base}/health")).json()
                    staleness = (health["deployment"]["streaming"]
                                 or {}).get("stalenessSeconds")
                    m_after = _metrics_snapshot(
                        await (await s.get(f"{base}/metrics")).text())
                    # -- full retrain + redeploy baseline -------------
                    t0 = time.perf_counter()
                    await loop.run_in_executor(
                        None, lambda: _train_recommendation(
                            ctx, storage, tmp, n_users, n_items, 0))
                    retrain_sec = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    resp = await s.post(f"{base}/reload")
                    assert resp.status == 200, await resp.text()
                    reload_sec = time.perf_counter() - t0
                freshness_ms.sort()
                full_cycle_ms = (retrain_sec + reload_sec) * 1e3
                p50 = freshness_ms[len(freshness_ms) // 2]
                p99 = freshness_ms[-1]
                return {
                    "event_visible_p50_ms": round(p50, 1),
                    "event_visible_p99_ms": round(p99, 1),
                    "updater_events_per_sec": round(
                        folded / sustained_sec, 1) if folded else 0.0,
                    "sustained_events": folded,
                    "full_retrain_redeploy_ms": round(full_cycle_ms, 1),
                    "freshness_speedup": round(full_cycle_ms / p50, 1),
                    "staleness_seconds_at_head": staleness,
                    # which touched-row engine folded (docs/streaming.md
                    # "Fused fold updates"); default auto = fused stack
                    "fold_engine": os.environ.get(
                        "PIO_STREAM_FUSED", "auto"),
                    "metrics_delta": {
                        k: round(m_after.get(k, 0) - m_before.get(k, 0), 3)
                        for k in ("pio_stream_applied_total",
                                  "pio_stream_deduped_total",
                                  "pio_deploy_rollbacks_total")
                        if k in m_after or k in m_before},
                }
            finally:
                await server.shutdown()

        return asyncio.run(drive())
    finally:
        use_storage(prev)
        storage.close()


# ---------------------------------------------------------------------------
# 11. continuous training (docs/jobs.md): SIGKILL the training worker
#     mid-epoch and measure retrain MTTR (kill → new instance serving),
#     then trip the streaming quarantine and measure the auto-retrain loop's
#     quarantine → fresh-recommendations end-to-end time
# ---------------------------------------------------------------------------


def bench_continuous_training() -> dict:
    """Two clocks on the control plane (incubator_predictionio_tpu/jobs/):

    - **retrain MTTR**: a train job is mid-epoch in a real worker
      subprocess when it takes a SIGKILL; the job is reclaimed under a new
      fence, RESUMES from the epoch checkpoint, and the clock stops when
      the gated deploy lands on the serving process — with exactly one
      /reload observed.
    - **quarantine → fresh**: the stream's divergence quarantine marker is
      planted; the trigger loop auto-submits the full retrain, an
      in-process worker executes + promotes it, and the clock stops when a
      restarted updater (marker cleared by the new instance id) has folded
      live events into an applied delta again.
    """
    import datetime as dt_mod
    import shutil
    import tempfile

    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import (
        App,
        Storage,
        use_storage,
    )
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.jobs import (
        JobWorker,
        Orchestrator,
        TriggerConfig,
        TriggerLoop,
        WorkerConfig,
    )
    from incubator_predictionio_tpu.obs.metrics import REGISTRY
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.streaming import guard as guards
    from tests.fixtures.procs import ServerProc, free_port as _fp, http_json

    ctx = MeshContext.create()
    tmp = tempfile.mkdtemp(prefix="pio-ct-bench-")
    iterations = 8 if SMALL else 16
    n_events = 4_000 if SMALL else 10_000
    n_users, n_items = 400, 300
    utc = dt_mod.timezone.utc
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "store.db"),
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(tmp, "eventlog"),
        **{f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE": src
           for repo, src in (("METADATA", "SQ"), ("EVENTDATA", "EL"),
                             ("MODELDATA", "SQ"))},
    }
    ckpt_dir = os.path.join(tmp, "ckpt")
    variant_path = os.path.join(tmp, "engine.json")
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    rng = np.random.default_rng(9)

    def live_events(n, rating=None):
        now = dt_mod.datetime.now(utc)
        return [
            Event(event="rate", entity_type="user",
                  entity_id=f"u{rng.integers(0, n_users)}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, n_items)}",
                  properties=DataMap({"rating": float(
                      rating if rating is not None
                      else 1 + 4 * rng.random())}),
                  event_time=now)
            for _ in range(n)
        ]

    def train_base() -> str:
        from incubator_predictionio_tpu.core.controller import (
            resolve_engine_factory,
        )
        from incubator_predictionio_tpu.core.workflow import run_train

        with open(variant_path) as f:
            variant = json.load(f)
        engine = resolve_engine_factory(variant["engineFactory"])()
        engine_params = engine.engine_params_from_variant(variant)
        instance = EngineInstance(
            id="", status="INIT", start_time=dt_mod.datetime.now(utc),
            end_time=None, engine_id="ct", engine_version="1",
            engine_variant=os.path.abspath(variant_path),
            engine_factory=variant["engineFactory"])
        return run_train(engine, engine_params, instance, storage=storage,
                         ctx=ctx)

    def jobs_delta(before):
        after = _metrics_snapshot(REGISTRY.expose())
        return {k: round(after.get(k, 0) - before.get(k, 0), 3)
                for k in after
                if k.startswith("pio_jobs_")
                and after.get(k, 0) != before.get(k, 0)}

    qs = w1 = w2 = None
    try:
        with open(variant_path, "w") as f:
            json.dump({
                "id": "ct", "version": "1",
                "engineFactory": "incubator_predictionio_tpu.templates."
                                 "recommendation.RecommendationEngine",
                "datasource": {"params": {"appName": "ct-app"}},
                "algorithms": [{"name": "als", "params": {
                    "rank": 32, "numIterations": iterations,
                    "batchSize": 1024,
                    "checkpointDir": ckpt_dir, "checkpointEvery": 1}}],
            }, f)
        app_id = storage.get_meta_data_apps().insert(App(0, "ct-app"))
        events_store = storage.get_events()
        events_store.init(app_id)
        events_store.insert_batch(live_events(n_events), app_id)
        t0 = time.perf_counter()
        base_instance = train_base()
        base_train_s = time.perf_counter() - t0
        shutil.rmtree(ckpt_dir, ignore_errors=True)

        qport = _fp()
        base_url = f"http://127.0.0.1:{qport}"
        qs = ServerProc(["deploy", "-v", variant_path, "--ip", "127.0.0.1",
                         "--port", str(qport)], env=dict(store_cfg))
        qs.wait_ready(f"{base_url}/", timeout=300.0)

        m_before = _metrics_snapshot(REGISTRY.expose())
        orch = Orchestrator(storage.get_meta_data_jobs())
        jobs_store = storage.get_meta_data_jobs()

        # -- phase A: retrain MTTR under a mid-epoch SIGKILL --------------
        job = orch.submit("train", {
            "engine_variant": os.path.abspath(variant_path),
            "server_url": base_url})
        w1 = ServerProc(["jobs", "worker", "--poll", "0.2"],
                        env={**store_cfg, "PIO_JOBS_LEASE_SEC": "2"})
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            j = jobs_store.get(job.id)
            steps = [d for d in (os.listdir(ckpt_dir)
                                 if os.path.isdir(ckpt_dir) else [])
                     if d.isdigit()]
            if j.status == "RUNNING" and steps \
                    and max(int(s) for s in steps) >= 2:
                break
            if not j.active:
                raise RuntimeError(f"train finished early: {j.status}")
            time.sleep(0.05)
        else:
            raise RuntimeError("no mid-epoch checkpoint window")
        t_kill = time.perf_counter()
        w1.kill9()
        w2 = ServerProc(["jobs", "worker", "--poll", "0.2"],
                        env={**store_cfg, "PIO_JOBS_LEASE_SEC": "30"})
        while True:
            j = jobs_store.get(job.id)
            if not j.active:
                break
            if time.perf_counter() - t_kill > 600.0:
                raise RuntimeError(f"reclaimed job never finished: {j}\n"
                                   + w2.output()[-2000:])
            time.sleep(0.1)
        retrain_mttr_s = time.perf_counter() - t_kill
        assert j.status == "COMPLETED", (j.status, j.failure)
        out2 = w2.output()
        resumed_epoch = (int(out2.split("resuming from epoch",
                                        1)[1].split()[0])
                         if "resuming from epoch" in out2 else 0)
        _, health = http_json("GET", f"{base_url}/health")
        served = health["deployment"]["instanceId"]
        assert served == j.result["instanceId"] != base_instance

        # -- phase B: quarantine → fresh recommendations ------------------
        from incubator_predictionio_tpu.streaming.updater import (
            StreamUpdater,
            UpdaterConfig,
            load_base_model,
        )

        state_dir = os.path.join(tmp, "stream-state")
        os.makedirs(state_dir, exist_ok=True)
        guards.quarantine(state_dir, "bench divergence trip", at_seq=0,
                          base_instance=served)
        worker = JobWorker(orch, storage,
                           WorkerConfig(worker_id="bench-inproc",
                                        lease_sec=120), ctx=ctx)
        loop = TriggerLoop(orch, storage, TriggerConfig(
            engine_variant=variant_path, server_url=base_url,
            stream_state_dir=state_dir))
        t_q = time.perf_counter()
        submitted = loop.run_once()
        assert submitted and submitted[0].trigger == "quarantine"
        out = worker.run_once()
        assert out["status"] == "COMPLETED", out
        model, instance_id, event_names, defaults = load_base_model(
            variant_path, storage)
        updater = StreamUpdater(
            UpdaterConfig(state_dir=state_dir,
                          feed_path=events_store.log_path(app_id),
                          replicas=(base_url,), batch_events=4096),
            model, instance_id, event_names=event_names,
            default_values=defaults)
        assert updater.quarantined is None   # marker cleared by new id
        events_store.insert_batch(live_events(50), app_id)
        fold = updater.run_once()
        assert fold["status"] == "applied", fold
        quarantine_to_fresh_s = time.perf_counter() - t_q
        _, h2 = http_json("GET", f"{base_url}/health")
        stream = h2["deployment"]["streaming"]
        assert stream["lastDeltaSeq"] == fold["toSeq"]

        return {
            "base_train_s": round(base_train_s, 2),
            "retrain_mttr_s": round(retrain_mttr_s, 2),
            "resumed_from_epoch": resumed_epoch,
            "epochs_total": iterations,
            "epochs_saved_by_resume": resumed_epoch,
            "job_fence_at_completion": j.fence,
            "job_attempts": j.attempt,
            "quarantine_to_fresh_s": round(quarantine_to_fresh_s, 2),
            "gate_verdicts": {
                "killed_job": (j.result.get("gate") or {}).get("verdict"),
                "quarantine_job": (out["result"].get("gate")
                                   or {}).get("verdict"),
            },
            "pio_jobs_delta": jobs_delta(m_before),
        }
    finally:
        for p in (w1, w2, qs):
            if p is not None:
                p.stop()
        use_storage(prev)
        storage.close()


# ---------------------------------------------------------------------------
# 12. distributed training (docs/sharding.md "Multi-host training"): 1 vs N
#     supervised member processes training the recommendation template with
#     row-sharded tables, then SIGKILL one member mid-epoch — MTTR, the
#     pinned resume epoch, and zero divergence vs the uninterrupted N-member
#     run, plus the supervisor plane's pio_dist_* metric deltas
# ---------------------------------------------------------------------------


def bench_distributed_training() -> dict:
    """Three supervised runs of ``pio-tpu train --distributed`` members:

    - **1 member** (degenerate mesh) and **2 members** uninterrupted —
      the multi-process overhead column;
    - **2 members + SIGKILL** of one member after the second slice-
      checkpoint commit: the supervisor fences generation 1, re-forms the
      mesh, and the new generation resumes from the last commit. The lane
      archives the recovery MTTR, the log-pinned resume epoch, and proves
      the recovered run's final committed state is BIT-IDENTICAL to the
      uninterrupted 2-member run (zero divergence).
    """
    import datetime as dt_mod
    import glob as glob_mod
    import tempfile
    import threading

    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import App, Storage, use_storage
    from incubator_predictionio_tpu.distributed.supervisor import Supervisor
    from incubator_predictionio_tpu.obs.metrics import REGISTRY
    from incubator_predictionio_tpu.utils import checkpoint as ckpt_fs

    tmp = tempfile.mkdtemp(prefix="pio-dist-bench-")
    iterations = 8 if SMALL else 12
    n_events = 3_000 if SMALL else 8_000
    utc = dt_mod.timezone.utc
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(tmp, "store.db"),
    }
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        app_id = storage.get_meta_data_apps().insert(App(0, "dist-app"))
        events = storage.get_events()
        events.init(app_id)
        rng = np.random.default_rng(13)
        events.insert_batch([
            Event(event="rate", entity_type="user",
                  entity_id=f"u{rng.integers(0, 400)}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, 300)}",
                  properties=DataMap({"rating": float(1 + 4 * rng.random())}),
                  event_time=dt_mod.datetime(2022, 1, 1, tzinfo=utc))
            for _ in range(n_events)
        ], app_id)
    finally:
        use_storage(prev)
        storage.close()

    def phase(tag: str, members: int):
        ckpt_dir = os.path.join(tmp, f"ckpt-{tag}")
        variant_path = os.path.join(tmp, f"engine-{tag}.json")
        with open(variant_path, "w") as f:
            json.dump({
                "id": f"dist-{tag}", "version": "1",
                "engineFactory": "incubator_predictionio_tpu.templates."
                                 "recommendation.RecommendationEngine",
                "datasource": {"params": {"appName": "dist-app"}},
                "algorithms": [{"name": "als", "params": {
                    "rank": 32, "numIterations": iterations,
                    "batchSize": 1024,
                    "checkpointDir": ckpt_dir, "checkpointEvery": 1}}],
            }, f)
        sup = Supervisor(
            ["train", "-v", variant_path, "--distributed",
             "--mesh-axes", json.dumps({"model": members})],
            num_processes=members,
            state_dir=os.path.join(tmp, f"mesh-{tag}"),
            heartbeat_ms=2000,
            max_recoveries=2,
            cpu_devices_per_process=1,
            env={**store_cfg, "PIO_FS_BASEDIR": os.path.join(tmp, f"fs-{tag}")},
            timeout=900.0,
        )
        return sup, ckpt_dir

    # -- 1 member (degenerate mesh) then 2 members, uninterrupted ----------
    sup1, _ = phase("1p", 1)
    t0 = time.perf_counter()
    res1 = sup1.run()
    train_1p_s = time.perf_counter() - t0
    assert res1.ok, res1.logs_text()[-3000:]

    sup2, ckpt_2p = phase("2p", 2)
    t0 = time.perf_counter()
    res2 = sup2.run()
    train_2p_s = time.perf_counter() - t0
    assert res2.ok and res2.recoveries == 0, res2.logs_text()[-3000:]

    # -- 2 members, SIGKILL one mid-epoch ----------------------------------
    m_before = _metrics_snapshot(REGISTRY.expose())
    supc, ckpt_ch = phase("chaos", 2)
    box: dict = {}
    t0 = time.perf_counter()
    runner = threading.Thread(target=lambda: box.update(res=supc.run()))
    runner.start()
    deadline = time.monotonic() + 600.0
    while time.monotonic() < deadline:
        steps = ckpt_fs.committed_steps(ckpt_ch)
        alive = supc.alive_pids()
        if steps and steps[-1] >= 2 and alive:
            os.kill(sorted(alive.items())[-1][1], 9)
            break
        if not runner.is_alive():
            raise AssertionError("chaos run finished before the kill window")
        time.sleep(0.05)
    runner.join(timeout=900.0)
    chaos_total_s = time.perf_counter() - t0
    resc = box["res"]
    assert resc.ok and resc.recoveries == 1, resc.logs_text()[-3000:]
    logs = resc.logs_text()
    assert "resuming from epoch" in logs, logs[-3000:]
    resumed_epoch = int(logs.split("resuming from epoch", 1)[1].split()[0])

    # zero divergence: recovered == uninterrupted, bit for bit
    leaves_2p = ckpt_fs.assemble_committed_step(ckpt_2p, iterations)
    leaves_ch = ckpt_fs.assemble_committed_step(ckpt_ch, iterations)
    div = max(
        (float(np.max(np.abs(np.asarray(a, np.float64)
                             - np.asarray(b, np.float64))))
         if np.asarray(a).size else 0.0)
        for a, b in zip(leaves_2p, leaves_ch))
    assert div == 0.0, f"recovered run diverged by {div}"

    after = _metrics_snapshot(REGISTRY.expose())
    dist_delta = {k: round(after.get(k, 0) - m_before.get(k, 0), 3)
                  for k in after
                  if k.startswith("pio_dist_")
                  and after.get(k, 0) != m_before.get(k, 0)}
    slices = len(glob_mod.glob(os.path.join(
        ckpt_ch, "slices", f"step-{iterations}", "member-*.json")))
    return {
        "members": 2,
        "epochs": iterations,
        "train_1p_s": round(train_1p_s, 2),
        "train_2p_s": round(train_2p_s, 2),
        "chaos_total_s": round(chaos_total_s, 2),
        "recovery_mttr_s": [round(t, 3) for t in resc.mttr_s],
        "recoveries": resc.recoveries,
        "final_generation": resc.generation,
        "resumed_from_epoch": resumed_epoch,
        "member_slices_at_final_commit": slices,
        "divergence_max_abs": div,
        "pio_dist_delta": dist_delta,
    }


def run_one_config(name: str) -> None:
    """Child mode: run exactly one config and print ``CONFIG_RESULT=<json>``.

    A device lane needs a chip: with none, ``chip_peaks`` raises, the child
    exits non-zero without a result and the parent fails the run."""
    import jax

    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    ctx = MeshContext.create()
    device = jax.devices()[0]
    peaks = (None, None) if name in DEVICE_FREE else chip_peaks(device)
    t0 = time.perf_counter()
    result = _build_suite(ctx, peaks, device)[name]()
    _log(f"{name}: {result} ({time.perf_counter() - t0:.1f}s)")
    result.setdefault("platform", device.platform)
    result.setdefault("device", device.device_kind)
    print("CONFIG_RESULT=" + json.dumps(result), flush=True)


def _run_config_subprocess(name: str, timeout_s: float):
    """Run one config in a child process. Returns (result_dict, wedged_bool).

    A device dispatch that hangs sits inside the PJRT C++ layer where signal
    handlers never run — killing the child is the only reliable escape, and
    it leaves the parent free to run the remaining configs."""
    import signal
    import subprocess

    env = dict(os.environ)
    if name in DEVICE_FREE:
        # host-plane lanes: explicitly on the CPU, never claiming the chip
        env["JAX_PLATFORMS"] = "cpu"
        if (name == "sharded_serving"
                and "xla_force_host_platform_device_count"
                not in env.get("XLA_FLAGS", "")):
            # the sharded lanes need a multi-device mesh; 8 virtual CPU
            # devices (the tests/conftest.py trick) — set before jax init
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
    # start_new_session: on timeout the whole process GROUP is killed —
    # a config's own children (spawned event/query servers) would otherwise
    # survive and hold the stdout pipe open, hanging the parent's drain
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--config", name],
        env=env, stdout=subprocess.PIPE, stderr=None,
        text=True, start_new_session=True,
    )
    try:
        stdout, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.communicate()
        return {"error": f"wedged: no result within {timeout_s:.0f}s"}, True
    for line in stdout.splitlines():
        if line.startswith("CONFIG_RESULT="):
            return json.loads(line.split("=", 1)[1]), False
    return {"error": f"child exited rc={proc.returncode} without a result"}, False


def main() -> int:
    if "--config" in sys.argv:
        run_one_config(sys.argv[sys.argv.index("--config") + 1])
        return 0

    t_start = time.monotonic()
    deadline = float(os.environ.get("PIO_BENCH_DEADLINE_S", "7200"))
    config_timeout = float(os.environ.get("PIO_BENCH_CONFIG_TIMEOUT_S", "1800"))

    configs: dict[str, dict] = {}
    wedged_reason = None
    # headline = the production-representative scaled config (VERDICT r3
    # weak #6: the MovieLens-shaped run is mostly dispatch and overstates
    # the chip story); the small config stays in configs for r3 deltas
    for name in CONFIG_NAMES:
        if ONLY and name not in ONLY:
            continue
        remaining = deadline - (time.monotonic() - t_start)
        if remaining < 60:
            configs[name] = {"error": "skipped: overall deadline exhausted"}
            continue
        result, wedged = _run_config_subprocess(
            name, min(config_timeout, remaining))
        configs[name] = result
        if wedged:
            wedged_reason = f"config '{name}': {result['error']}"
            _log(f"WATCHDOG: {wedged_reason}")

    # the device as the device lanes' own processes reported it
    device_info = next(
        ({"platform": r["platform"], "device": r.get("device")}
         for n, r in configs.items()
         if n not in DEVICE_FREE and "platform" in r), {})
    print(build_result_line(configs, device_info, wedged_reason), flush=True)
    failed = [n for n, r in configs.items() if "error" in r]
    if failed:
        _log(f"FAILED lanes: {failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
