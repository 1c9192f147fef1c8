"""Streaming incremental updates (ISSUE 8): feed tail-follow, delta
trainer, exactly-once delta deploys, cold-start buckets, divergence guard,
and two-stage index staleness — all in-process and deterministic (the
subprocess SIGKILL proofs live in tests/test_chaos_procs.py)."""

import datetime as dt
import json
import os
import struct

import numpy as np
import pytest

from incubator_predictionio_tpu.data import DataMap, Event
from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.data.storage.eventlog_backend import (
    EventLogEvents,
)
from incubator_predictionio_tpu.models.two_tower import (
    TwoTowerConfig,
    TwoTowerModel,
)
from incubator_predictionio_tpu.resilience import wal
from incubator_predictionio_tpu.streaming import delta as deltas
from incubator_predictionio_tpu.streaming import feed as feeds
from incubator_predictionio_tpu.streaming import guard as guards
from incubator_predictionio_tpu.streaming.coldstart import ColdStartBuckets
from incubator_predictionio_tpu.streaming.trainer import DeltaTrainer
from incubator_predictionio_tpu.streaming.updater import (
    StreamUpdater,
    UpdaterConfig,
)
from incubator_predictionio_tpu.templates.recommendation import (
    ALSAlgorithm,
    ALSAlgorithmParams,
    Query,
    RecModel,
    RecommendationEngine,
)

UTC = dt.timezone.utc
T0 = dt.datetime(2023, 5, 1, tzinfo=UTC)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _make_model(n_users=20, n_items=30, rank=8, seed=0) -> RecModel:
    rng = np.random.default_rng(seed)
    mf = TwoTowerModel(
        user_emb=(rng.normal(size=(n_users, rank)) * 0.3).astype(np.float32),
        item_emb=(rng.normal(size=(n_items, rank)) * 0.3).astype(np.float32),
        user_bias=np.zeros(n_users, np.float32),
        item_bias=np.zeros(n_items, np.float32),
        mean=2.5,
        config=TwoTowerConfig(rank=rank, learning_rate=0.05, reg=1e-4),
    )
    user_map = BiMap({f"u{i}": i for i in range(n_users)})
    item_map = BiMap({f"i{j}": j for j in range(n_items)})
    return RecModel(mf, user_map, item_map)


def _trainer_for(model: RecModel, **kw) -> DeltaTrainer:
    mf = model.mf
    return DeltaTrainer(
        mf.user_emb, mf.user_bias, mf.item_emb, mf.item_bias, mf.mean,
        dict(model.user_map.items()), dict(model.item_map.items()),
        learning_rate=mf.config.learning_rate, reg=mf.config.reg, **kw)


def _rate(user, item, rating, minute=0) -> Event:
    return Event(
        event="rate", entity_type="user", entity_id=user,
        target_entity_type="item", target_entity_id=item,
        properties=DataMap({"rating": float(rating)}),
        event_time=T0 + dt.timedelta(minutes=minute))


def _event_store(tmp_path, events=()):
    store = EventLogEvents(str(tmp_path / "eventlog"))
    store.init(1)
    if events:
        store.insert_batch(list(events), 1)
    return store, store.log_path(1)


# ---------------------------------------------------------------------------
# satellite: tail-follow of a live WAL/eventlog segment
# ---------------------------------------------------------------------------

def test_wal_tail_frames_torn_tail_waits_then_resumes(tmp_path):
    """A torn tail on a concurrently-appended WAL segment is 'wait and
    re-poll', never corruption and never a skip — interleaved
    writer/reader."""
    path = str(tmp_path / "seg.log")
    rec1 = json.dumps({"seq": 1}).encode()
    rec2 = json.dumps({"seq": 2, "pad": "x" * 64}).encode()

    def frame(payload):
        import zlib

        return struct.pack("<II", len(payload),
                           zlib.crc32(payload) & 0xFFFFFFFF) + payload

    with open(path, "wb") as f:
        f.write(wal.MAGIC + frame(rec1))
    records, off1, status = wal.tail_frames(path)
    assert [r["seq"] for _, r in records] == [1]
    assert status == "ok"

    full2 = frame(rec2)
    for cut in (2, len(full2) // 2, len(full2) - 1):  # header & payload torn
        with open(path, "wb") as f:
            f.write(wal.MAGIC + frame(rec1) + full2[:cut])
        records, off, status = wal.tail_frames(path, off1)
        assert status == "waiting", f"cut={cut}"
        assert records == []          # nothing phantom-decoded
        assert off == off1            # resume from the SAME offset
    # writer completes the frame: the re-poll yields it exactly once
    with open(path, "wb") as f:
        f.write(wal.MAGIC + frame(rec1) + full2)
    records, off2, status = wal.tail_frames(path, off1)
    assert [r["seq"] for _, r in records] == [2]
    assert status == "ok"
    # a COMPLETE frame with a bad CRC is corruption, not waiting
    bad = bytearray(frame(rec1))
    bad[-1] ^= 0xFF
    with open(path, "wb") as f:
        f.write(wal.MAGIC + full2 + bytes(bad))
    records, _, status = wal.tail_frames(path)
    assert status == "corrupt"
    assert [r["seq"] for _, r in records] == [2]


def test_eventlog_feed_torn_tail_waits_then_delivers_exactly_once(tmp_path):
    store, src = _event_store(tmp_path, [
        _rate("u1", "i1", 4.0, 0), _rate("u2", "i2", 3.0, 1)])
    with open(src, "rb") as f:
        base = f.read()
    store.insert_batch([_rate("u3", "i3", 5.0, 2)], 1)
    with open(src, "rb") as f:
        full = f.read()
    suffix = full[len(base):]
    live = str(tmp_path / "live.piolog")
    with open(live, "wb") as f:
        f.write(base)
    feed = feeds.EventLogFeed(live)
    batch = feed.poll()
    assert [e.entity_id for e in batch.events] == ["u1", "u2"]
    assert not batch.waiting
    pos = feed.position
    # writer appends half the third record: wait, don't skip, don't move
    for cut in (2, len(suffix) // 2, len(suffix) - 1):
        with open(live, "wb") as f:
            f.write(base + suffix[:cut])
        b = feed.poll()
        assert b.waiting and b.events == [], f"cut={cut}"
        assert feed.position == pos
    with open(live, "wb") as f:
        f.write(full)
    b = feed.poll()
    assert [e.entity_id for e in b.events] == ["u3"]  # exactly once
    assert not b.waiting
    assert feed.poll().events == []


def test_feed_cursor_is_crash_safe_and_atomic(tmp_path):
    d = str(tmp_path / "state")
    assert feeds.read_cursor(d) is None
    feeds.write_cursor(d, {"seq": 123, "chain_base": 8,
                           "base_instance": "inst"})
    assert feeds.read_cursor(d)["seq"] == 123
    assert not os.path.exists(
        os.path.join(d, feeds.CURSOR_FILE + ".tmp"))
    feeds.write_cursor(d, {"seq": 456, "chain_base": 8,
                           "base_instance": "inst"})
    assert feeds.read_cursor(d)["seq"] == 456


def test_feed_bootstrap_resumes_mid_log_with_string_table(tmp_path):
    """Resuming from a cursor must still decode events whose interned
    strings were introduced BEFORE the cursor."""
    store, src = _event_store(tmp_path, [_rate("alice", "widget", 4.0)])
    with open(src, "rb") as f:
        mid = len(f.read())
    store.insert_batch([_rate("alice", "widget", 5.0, 1)], 1)
    feed = feeds.EventLogFeed(src, from_seq=mid)
    batch = feed.poll()
    assert len(batch.events) == 1
    e = batch.events[0]
    assert (e.entity_id, e.target_entity_id) == ("alice", "widget")
    assert e.properties["rating"] == 5.0
    assert batch.from_seq == mid


# ---------------------------------------------------------------------------
# delta trainer
# ---------------------------------------------------------------------------

def test_trainer_fold_is_sparse_and_deterministic():
    model = _make_model()
    events = [_rate("u1", "i2", 5.0), _rate("u1", "i3", 1.0),
              _rate("u4", "i2", 4.0)]
    r1, p1 = _trainer_for(model).fold(events)
    r2, p2 = _trainer_for(model).fold(events)
    assert p1 == p2 == []
    assert set(r1.user_rows) == {1, 4}
    assert set(r1.item_rows) == {2, 3}
    assert r1.max_event_time_us > 0
    for idx in r1.user_rows:
        np.testing.assert_array_equal(r1.user_rows[idx], r2.user_rows[idx])
        assert not np.allclose(  # the step actually moved the row
            r1.user_rows[idx][:8], model.mf.user_emb[idx])
    # base tables untouched (the trainer works on overlays)
    assert float(model.mf.user_bias[1]) == 0.0


def test_trainer_state_roundtrip_continues_identically():
    model = _make_model()
    e1 = [_rate("u1", "i2", 5.0)]
    e2 = [_rate("u1", "i2", 4.0), _rate("u2", "i5", 2.0)]
    a = _trainer_for(model)
    a.fold(e1)
    b = _trainer_for(model)
    b.load_state(__import__("pickle").loads(
        __import__("pickle").dumps(a.to_state())))
    ra, _ = a.fold(e2)
    rb, _ = b.fold(e2)
    for idx in ra.user_rows:
        np.testing.assert_array_equal(ra.user_rows[idx], rb.user_rows[idx])


def test_trainer_poison_events_are_isolated():
    model = _make_model()
    bad = Event(event="rate", entity_type="user", entity_id="u1",
                target_entity_type="item", target_entity_id="i1",
                properties=DataMap({"rating": "five stars"}),
                event_time=T0)
    no_target = Event(event="rate", entity_type="user", entity_id="u1",
                      properties=DataMap({"rating": 4.0}), event_time=T0)
    good = _rate("u2", "i2", 3.0)
    result, poison = _trainer_for(model).fold([bad, good, no_target])
    assert len(poison) == 2
    assert result.n_folded == 1
    assert set(result.user_rows) == {2}


def test_trainer_unknown_entities_skip_or_bucket(monkeypatch):
    model = _make_model()
    ev = [_rate("stranger", "i1", 5.0), _rate("u1", "new-item", 4.0)]
    monkeypatch.delenv("PIO_COLDSTART_MODE", raising=False)
    r, _ = _trainer_for(model).fold(ev)
    assert r.n_skipped == 2 and r.n_folded == 0
    monkeypatch.setenv("PIO_COLDSTART_MODE", "hash")
    r, _ = _trainer_for(model).fold(ev)
    assert r.n_skipped == 0 and r.n_folded == 2
    assert len(r.cold_user_rows) == 1 and len(r.cold_item_rows) == 1
    # the known sides trained too ("i1" → row 1, "u1" → row 1)
    assert set(r.item_rows) == {1} and set(r.user_rows) == {1}


# ---------------------------------------------------------------------------
# delta artifacts + model apply
# ---------------------------------------------------------------------------

def _delta_for(model, instance="inst-1", from_seq=8, to_seq=100,
               chain_base=8, user_rows=None, item_rows=None,
               **kw) -> deltas.ModelDelta:
    return deltas.ModelDelta(
        base_instance=instance, chain_base=chain_base,
        from_seq=from_seq, to_seq=to_seq,
        user_rows=user_rows or {}, item_rows=item_rows or {},
        max_event_time_us=1_700_000_000_000_000, n_events=3, **kw)


def test_delta_artifact_roundtrip_and_crc(tmp_path):
    model = _make_model()
    d = _delta_for(model, user_rows={1: np.arange(9, dtype=np.float32)})
    data = deltas.encode_delta(d)
    back = deltas.decode_delta(data)
    assert back.from_seq == 8 and back.to_seq == 100
    np.testing.assert_array_equal(back.user_rows[1], d.user_rows[1])
    corrupted = bytearray(data)
    corrupted[-1] ^= 0xFF
    with pytest.raises(ValueError):
        deltas.decode_delta(bytes(corrupted))
    path = deltas.save_delta(str(tmp_path), d)
    assert deltas.load_delta(path).to_seq == 100
    assert deltas.list_archived(str(tmp_path)) == [(8, 100, path)]
    assert deltas.chain_from(str(tmp_path), None) == [path]
    assert deltas.chain_from(str(tmp_path), 100) == []


def test_apply_delta_builds_beside_and_is_exact():
    model = _make_model()
    before_u = model.mf.user_emb.copy()
    row = np.arange(9, dtype=np.float32)
    d = _delta_for(model, user_rows={3: row}, item_rows={5: row * 2})
    new = model.apply_delta(d)
    # new model carries the rows...
    np.testing.assert_array_equal(new.mf.user_emb[3], row[:8])
    assert float(new.mf.user_bias[3]) == row[8]
    np.testing.assert_array_equal(new.mf.item_emb[5], row[:8] * 2)
    # ...untouched rows are bit-identical, and the ORIGINAL is unmutated
    np.testing.assert_array_equal(new.mf.user_emb[0], before_u[0])
    np.testing.assert_array_equal(model.mf.user_emb, before_u)
    assert new.user_map is model.user_map  # vocab never grows via delta
    with pytest.raises(ValueError):
        model.apply_delta(_delta_for(model, user_rows={99: row}))


# ---------------------------------------------------------------------------
# satellite: cold-start hash buckets
# ---------------------------------------------------------------------------

def test_coldstart_buckets_deterministic_across_processes():
    a = ColdStartBuckets.build(rank=8, buckets=16, seed=0)
    b = ColdStartBuckets.build(rank=8, buckets=16, seed=0)
    np.testing.assert_array_equal(a.user_rows, b.user_rows)
    np.testing.assert_array_equal(a.item_rows, b.item_rows)
    assert a.user_bucket("stranger") == b.user_bucket("stranger")
    assert a.user_bucket("x") != a.item_bucket("x") or a.buckets == 1


def test_coldstart_mode_serves_unknown_users_with_parity(monkeypatch):
    model = _make_model()
    algo = ALSAlgorithm(ALSAlgorithmParams())
    known_q = Query(user="u1", num=5)
    unknown_q = Query(user="stranger", num=5)
    monkeypatch.delenv("PIO_COLDSTART_MODE", raising=False)
    off_known = algo.predict(model, known_q)
    assert algo.predict(model, unknown_q).item_scores == ()
    monkeypatch.setenv("PIO_COLDSTART_MODE", "hash")
    on_known = algo.predict(model, known_q)
    on_unknown = algo.predict(model, unknown_q)
    # parity: known entities bit-identical to before
    assert off_known == on_known
    # unknown users now get real recommendations, deterministically
    assert len(on_unknown.item_scores) == 5
    assert on_unknown == algo.predict(model, unknown_q)
    # blacklist still honored on the cold path
    banned = on_unknown.item_scores[0].item
    filtered = algo.predict(
        model, Query(user="stranger", num=5, black_list=(banned,)))
    assert banned not in [s.item for s in filtered.item_scores]
    # batch_predict agrees with predict on the cold path
    got = dict(algo.batch_predict(
        model, [(0, unknown_q), (1, known_q)]))
    assert got[0] == on_unknown
    assert got[1] == on_known


# ---------------------------------------------------------------------------
# exactly-once delta deploys through the query server
# ---------------------------------------------------------------------------

def _deployed_rec_server(model: RecModel, instance_id="inst-1", **cfg_kw):
    import asyncio  # noqa: F401

    from incubator_predictionio_tpu.core import EngineParams
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.server.query_server import (
        DeployedEngine,
        QueryServer,
        ServerConfig,
    )

    engine = RecommendationEngine().apply()
    engine_params = EngineParams.create(
        algorithms=[("als", ALSAlgorithmParams(rank=model.mf.config.rank))])
    instance = EngineInstance(
        id=instance_id, status="COMPLETED",
        start_time=dt.datetime.now(UTC), end_time=dt.datetime.now(UTC),
        engine_id="rec", engine_version="1", engine_variant="engine.json",
        engine_factory="rec.Factory")
    deployed = DeployedEngine(engine, engine_params, instance, [model],
                              warmup=False)
    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    server = QueryServer(ServerConfig(**cfg_kw), storage=storage,
                         deployed=deployed)
    return server


def _run_delta_server(model, coro_fn, **cfg_kw):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    async def runner():
        server = _deployed_rec_server(model, **cfg_kw)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            return await coro_fn(client, server)
        finally:
            await client.close()

    return asyncio.run(runner())


def test_delta_endpoint_exactly_once_semantics():
    model = _make_model()
    strong = np.zeros(9, np.float32)
    strong[:8] = model.mf.item_emb[7] * 50  # u2 now loves item i7
    d1 = _delta_for(model, from_seq=8, to_seq=50, chain_base=8,
                    user_rows={2: strong})
    d2 = _delta_for(model, from_seq=50, to_seq=90, chain_base=8,
                    user_rows={5: strong * 0.5})
    gap = _delta_for(model, from_seq=300, to_seq=400, chain_base=8)
    wrong_base = _delta_for(model, instance="other-instance",
                            from_seq=90, to_seq=120, chain_base=8)
    nan = _delta_for(model, from_seq=90, to_seq=120, chain_base=8,
                     user_rows={1: np.full(9, np.nan, np.float32)})

    async def t(client, server):
        # out-of-chain first delta: rejected (chain must start at base)
        resp = await client.post("/delta",
                                 data=deltas.encode_delta(d2))
        assert resp.status == 409
        assert (await resp.json())["reason"] == "out-of-order"
        # the chain head applies
        resp = await client.post("/delta", data=deltas.encode_delta(d1))
        assert resp.status == 200
        body = await resp.json()
        assert body["status"] == "applied" and body["lastDeltaSeq"] == 50
        # ...and is visible in serving: u2's top item is now i7
        resp = await client.post("/queries.json",
                                 json={"user": "u2", "num": 3})
        assert resp.status == 200
        q = await resp.json()
        assert q["itemScores"][0]["item"] == "i7"
        # duplicate (crash replay) → counted dedup, NOT re-applied
        resp = await client.post("/delta", data=deltas.encode_delta(d1))
        assert resp.status == 200
        assert (await resp.json())["status"] == "duplicate"
        # next in chain applies
        resp = await client.post("/delta", data=deltas.encode_delta(d2))
        assert (await resp.json())["status"] == "applied"
        # a gap is rejected with the replica's position for resync
        resp = await client.post("/delta", data=deltas.encode_delta(gap))
        assert resp.status == 409
        assert (await resp.json())["lastDeltaSeq"] == 90
        # wrong base instance: rejected
        resp = await client.post("/delta",
                                 data=deltas.encode_delta(wrong_base))
        assert resp.status == 409
        assert (await resp.json())["reason"] == "base-mismatch"
        # non-finite rows never reach a serving table
        resp = await client.post("/delta", data=deltas.encode_delta(nan))
        assert resp.status == 409
        assert (await resp.json())["reason"] == "non-finite"
        # garbage body → 400
        resp = await client.post("/delta", data=b"not a delta")
        assert resp.status == 400
        # health surfaces chain position, counts, and staleness
        health = await (await client.get("/health")).json()
        stream = health["deployment"]["streaming"]
        assert stream["lastDeltaSeq"] == 90
        assert stream["applied"] == 2 and stream["deduped"] == 1
        assert stream["stalenessSeconds"] is not None

    _run_delta_server(model, t)


def test_delta_rollback_restores_model_and_chain_position():
    model = _make_model()
    strong = np.zeros(9, np.float32)
    strong[:8] = model.mf.item_emb[7] * 50
    d1 = _delta_for(model, from_seq=8, to_seq=50, chain_base=8,
                    user_rows={2: strong})

    async def t(client, server):
        base = await (await client.post(
            "/queries.json", json={"user": "u2", "num": 1})).json()
        resp = await client.post("/delta", data=deltas.encode_delta(d1))
        assert (await resp.json())["status"] == "applied"
        # operator rollback inside the probation window: the delta is
        # un-deployed atomically and the chain position rolls back with it
        resp = await client.post("/rollback")
        assert resp.status == 200
        health = await (await client.get("/health")).json()
        assert health["deployment"]["streaming"] is None
        after = await (await client.post(
            "/queries.json", json={"user": "u2", "num": 1})).json()
        assert after["itemScores"] == base["itemScores"]

    _run_delta_server(model, t, reload_probation_sec=300.0)


def test_delta_smoke_gate_keeps_old_model():
    model = _make_model()
    d1 = _delta_for(model, from_seq=8, to_seq=50, chain_base=8,
                    user_rows={2: np.ones(9, np.float32)})

    async def t(client, server):
        resp = await client.post("/delta", data=deltas.encode_delta(d1))
        assert resp.status == 409
        assert (await resp.json())["reason"] == "smoke-gate"
        health = await (await client.get("/health")).json()
        assert health["deployment"]["streaming"] is None
        # still serving the base model
        resp = await client.post("/queries.json",
                                 json={"user": "u1", "num": 2})
        assert resp.status == 200

    # a smoke query that cannot bind fails the gate for ANY new engine
    _run_delta_server(model, t, smoke_queries=({"bogus": True},))


# ---------------------------------------------------------------------------
# updater loop: crash replay, dead letters, quarantine
# ---------------------------------------------------------------------------

class FakeReplica:
    """In-process replica implementing the server's exactly-once rules."""

    def __init__(self, model, instance_id="inst-1"):
        self.model = model
        self.instance_id = instance_id
        self.last = None
        self.applied = 0
        self.deduped = 0

    report_stale_once = False  # pretend /health hasn't caught up yet

    def applied_seq(self, url):
        if self.report_stale_once:
            self.report_stale_once = False
            return None, self.instance_id
        return self.last, self.instance_id

    def ship(self, url, payload):
        d = deltas.decode_delta(payload)
        assert d.base_instance == self.instance_id
        if self.last is not None and d.to_seq <= self.last:
            self.deduped += 1
            return {"status": "duplicate", "lastDeltaSeq": self.last}
        expected = self.last if self.last is not None else d.chain_base
        assert d.from_seq == expected, (d.from_seq, expected)
        self.model = self.model.apply_delta(d)
        self.last = d.to_seq
        self.applied += 1
        return {"status": "applied", "lastDeltaSeq": self.last}


class _Boom(Exception):
    pass


def _updater(tmp_path, model, feed_path, replica, **kw):
    cfg = UpdaterConfig(
        state_dir=str(tmp_path / "state"), feed_path=feed_path,
        replicas=("fake://replica",), **kw)
    return StreamUpdater(cfg, model, "inst-1", transport=replica)


def test_updater_folds_ships_and_commits(tmp_path):
    events = [_rate("u1", "i2", 5.0, m) for m in range(4)]
    _, src = _event_store(tmp_path, events)
    model = _make_model()
    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, model, src, replica, from_start=True)
    out = up.run_once()
    assert out["status"] == "applied"
    assert out["events"] == 4
    assert replica.applied == 1 and replica.deduped == 0
    # replica model == updater's own applied model, bit-for-bit
    np.testing.assert_array_equal(
        replica.model.mf.user_emb, up.model.mf.user_emb)
    # cursor committed: a fresh poll is idle
    assert up.run_once()["status"] == "idle"
    # and a RESTARTED updater resumes from the cursor, refolding nothing
    up2 = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    assert up2.run_once()["status"] == "idle"
    assert replica.applied == 1


def test_updater_crash_between_ship_and_commit_is_exactly_once(tmp_path):
    """The ISSUE's nastiest window, in-process: die after the delta
    shipped but before the cursor committed. The restarted updater
    re-folds the same range deterministically, the replica dedupes the
    replay, and the final state equals the no-crash run exactly."""
    events = [_rate("u1", "i2", 5.0, m) for m in range(3)]
    _, src = _event_store(tmp_path, events)

    # control: no crash
    ctrl_replica = FakeReplica(_make_model())
    ctrl = _updater(tmp_path / "ctrl", _make_model(), src, ctrl_replica,
                    from_start=True)
    assert ctrl.run_once()["status"] == "applied"

    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    real_commit = up._commit

    def exploding_commit(to_seq, delta_head=None):
        raise _Boom()

    up._commit = exploding_commit
    with pytest.raises(_Boom):
        up.run_once()
    assert replica.applied == 1  # the ship DID land before the crash
    # restart over the same state dir: the re-fold produces the SAME
    # range; the health resync skips it — and even when the replica's
    # health is stale (reports nothing applied), the replica-side range
    # check dedupes the replay instead of double-applying
    replica.report_stale_once = True
    up2 = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    out = up2.run_once()
    assert out["status"] == "applied"
    assert replica.applied == 1 and replica.deduped == 1
    assert out["ships"][0]["deduped"] == 1
    np.testing.assert_array_equal(
        replica.model.mf.user_emb, ctrl_replica.model.mf.user_emb)
    np.testing.assert_array_equal(
        replica.model.mf.item_emb, ctrl_replica.model.mf.item_emb)
    assert up2.run_once()["status"] == "idle"
    del real_commit


def test_updater_crash_between_state_and_cursor_write_recovers(tmp_path):
    """A SIGKILL between the trainer-state write and the cursor write
    leaves the state AHEAD of the cursor; init detects it and adopts the
    state's position (the archived delta covers the gap)."""
    events = [_rate("u1", "i2", 5.0, m) for m in range(3)]
    _, src = _event_store(tmp_path, events)
    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    real_write = feeds.write_cursor

    def no_cursor(state_dir, cursor):
        raise _Boom()

    feeds.write_cursor = no_cursor
    try:
        with pytest.raises(_Boom):
            up.run_once()
    finally:
        feeds.write_cursor = real_write
    up2 = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    out = up2.run_once()
    # nothing re-folded (state adopted), replica resynced via the chain
    assert out["status"] == "idle"
    assert replica.applied == 1 and replica.deduped == 0


def test_updater_dead_letters_poison_and_never_wedges(tmp_path):
    poison = Event(event="rate", entity_type="user", entity_id="u1",
                   target_entity_type="item", target_entity_id="i1",
                   properties=DataMap({"rating": "garbage"}), event_time=T0)
    _, src = _event_store(tmp_path, [poison, _rate("u2", "i2", 4.0, 1)])
    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    out = up.run_once()
    assert out["status"] == "applied"
    assert out["deadLettered"] == 1 and out["events"] == 1
    dl = os.path.join(str(tmp_path / "state"), "deadletter.log")
    records, _, status = wal.tail_frames(dl)
    assert status == "ok" and len(records) == 1
    assert records[0][1]["event"]["entityId"] == "u1"
    assert records[0][1]["reason"].startswith("fold rejected")
    # the loop moved on: nothing re-reads the poison window
    assert up.run_once()["status"] == "idle"


def test_guard_quarantines_and_full_retrain_clears(tmp_path):
    _, src = _event_store(tmp_path, [_rate("u1", "i2", 5.0)])
    model = _make_model()
    # an absurd learning rate detonates the touched rows → norm trip
    model.mf.config = TwoTowerConfig(rank=8, learning_rate=1e9, reg=1e-4)
    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, model, src, replica, from_start=True)
    out = up.run_once()
    assert out["status"] == "quarantined"
    assert "norm" in out["marker"]["reason"]
    assert replica.applied == 0  # a diverged delta never ships
    # durable across restarts of the SAME base instance
    up2 = _updater(tmp_path, model, src, replica, from_start=True)
    assert up2.run_once()["status"] == "quarantined"
    assert guards.read_quarantine(str(tmp_path / "state")) is not None
    # a full retrain (new instance id) clears the marker and resets state
    sane = _make_model()
    cfg = UpdaterConfig(state_dir=str(tmp_path / "state"), feed_path=src,
                        replicas=("fake://replica",), from_start=True)
    replica2 = FakeReplica(sane, instance_id="inst-2")
    up3 = StreamUpdater(cfg, sane, "inst-2", transport=replica2)
    assert up3.quarantined is None
    assert up3.run_once()["status"] == "applied"


def test_updater_resyncs_restarted_replica_from_archive(tmp_path):
    """A replica that lost its applied deltas (process restart) is brought
    back to the chain head from the archive — no events lost, none
    double-applied."""
    store, src = _event_store(tmp_path, [_rate("u1", "i2", 5.0, 0)])
    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    assert up.run_once()["status"] == "applied"
    store.insert_batch([_rate("u3", "i4", 2.0, 1)], 1)
    assert up.run_once()["status"] == "applied"
    snapshot = replica.model.mf.user_emb.copy()
    # replica restarts: base model, nothing applied
    replica.model = _make_model()
    replica.last = None
    replica.applied = 0
    out = up.run_once()  # idle poll still resyncs
    assert out["status"] == "idle"
    assert replica.applied == 2
    np.testing.assert_array_equal(replica.model.mf.user_emb, snapshot)


class _PerUrlTransport:
    """Route updater traffic to a distinct FakeReplica per url — the
    multi-owner fleet shape (each shard owner is its own process)."""

    def __init__(self, replicas):
        self.replicas = replicas

    def applied_seq(self, url):
        return self.replicas[url].applied_seq(url)

    def ship(self, url, payload):
        return self.replicas[url].ship(url, payload)


def test_updater_tracks_per_owner_seq_not_fleet_global(tmp_path):
    """Satellite fix (ISSUE 16): chain position is recorded PER OWNER. A
    fleet-global `lastDeltaSeq` would, after one owner is SIGKILLed and a
    standby promoted, treat the fresh owner as already at the head —
    silently skipping the whole chain (wrong rows served forever)."""
    store, src = _event_store(tmp_path, [_rate("u1", "i2", 5.0, 0)])
    a, b = FakeReplica(_make_model()), FakeReplica(_make_model())
    transport = _PerUrlTransport({"fake://a": a, "fake://b": b})
    cfg = UpdaterConfig(state_dir=str(tmp_path / "state"), feed_path=src,
                        replicas=("fake://a", "fake://b"), from_start=True)
    up = StreamUpdater(cfg, _make_model(), "inst-1", transport=transport)
    assert up.run_once()["status"] == "applied"
    store.insert_batch([_rate("u3", "i4", 2.0, 1)], 1)
    assert up.run_once()["status"] == "applied"
    head = a.last
    assert head is not None
    assert up.owner_seqs == {"fake://a": head, "fake://b": head}
    # owner B is SIGKILLed; its replacement restarts from base artifacts
    b.model, b.last, b.applied = _make_model(), None, 0
    out = up.run_once()
    assert out["status"] == "idle"
    # B replayed the FULL chain from ITS OWN (empty) position...
    assert b.applied == 2 and b.last == head
    # ...while A, already at the head, was not reshipped anything
    assert a.applied == 2 and a.deduped == 0
    st = up.status()
    assert st["ownerSeqs"] == {"fake://a": head, "fake://b": head}
    # both owners converge to the same table state
    np.testing.assert_array_equal(b.model.mf.user_emb,
                                  a.model.mf.user_emb)
    np.testing.assert_array_equal(b.model.mf.item_emb,
                                  a.model.mf.item_emb)


def test_untrainable_stretch_never_gaps_the_delta_chain(tmp_path):
    """An all-ignored batch (event names outside the training signal, or
    unknown entities with cold-start off) advances the FEED cursor but not
    the chain head — the next real delta spans the gap and replicas keep
    accepting (the review's wedge scenario)."""
    store, src = _event_store(tmp_path, [_rate("u1", "i2", 5.0, 0)])
    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    assert up.run_once()["status"] == "applied"
    first_head = replica.last
    # a stretch the trainer can't use: unknown event name + unknown user
    store.insert_batch([
        Event(event="view", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              event_time=T0),
        _rate("stranger", "i1", 3.0, 1),
    ], 1)
    out = up.run_once()
    assert out["status"] == "empty"  # cursor moved, no delta archived
    assert up.cursor["seq"] > up.cursor["delta_head"]
    # the next trainable batch still ships and the replica still accepts:
    # its from_seq is the chain head, not the batch start
    store.insert_batch([_rate("u2", "i3", 4.0, 2)], 1)
    out = up.run_once()
    assert out["status"] == "applied"
    assert out["fromSeq"] == first_head
    assert replica.applied == 2 and replica.last == out["toSeq"]
    # and a RESTARTED replica replays the whole chain cleanly
    replica.model, replica.last, replica.applied = _make_model(), None, 0
    assert up.run_once()["status"] in ("idle", "waiting")
    assert replica.applied == 2


def test_inspect_state_dir_is_read_only(tmp_path):
    from incubator_predictionio_tpu.streaming.updater import (
        inspect_state_dir,
    )

    d = str(tmp_path / "state")
    info = inspect_state_dir(d)
    assert info["cursor"] is None and info["quarantine"] is None
    # inspecting a nonexistent/fresh dir must not create ANY state
    assert not os.path.exists(os.path.join(d, feeds.CURSOR_FILE))
    _, src = _event_store(tmp_path, [_rate("u1", "i2", 5.0)])
    replica = FakeReplica(_make_model())
    up = _updater(tmp_path, _make_model(), src, replica, from_start=True)
    up.run_once()
    info = inspect_state_dir(str(tmp_path / "state"))
    assert info["cursor"]["seq"] == up.cursor["seq"]
    assert info["archivedDeltas"] == 1
    assert info["chainHead"] == up.cursor["delta_head"]


def test_feed_bounded_poll_consumes_backlog_incrementally(tmp_path):
    """The per-poll read bound must never skip, dupe, or falsely report
    'waiting' — a bound-cut record is 'poll again', and a record larger
    than the bound grows the read instead of wedging."""
    store, src = _event_store(
        tmp_path, [_rate(f"u{i % 20}", f"i{i % 30}", 4.0, i)
                   for i in range(50)])
    feed = feeds.EventLogFeed(src)
    seen = []
    rounds = 0
    while True:
        b = feed.poll(max_events=1000, max_bytes=256)  # tiny bound
        if not b.events:
            assert not b.waiting  # bound-cut is not writer-waiting
            break
        seen.extend(e for e in b.events)
        rounds += 1
        assert rounds < 1000
    assert len(seen) == 50  # exactly once, in order
    assert [e.entity_id for e in seen] == [f"u{i % 20}" for i in range(50)]


# ---------------------------------------------------------------------------
# two-stage index staleness (the pruned probe stays honest)
# ---------------------------------------------------------------------------

def test_two_stage_stale_rows_serve_current_embeddings(monkeypatch):
    from incubator_predictionio_tpu.models.two_tower import TwoTowerMF
    from incubator_predictionio_tpu.serving import ann

    monkeypatch.setenv("PIO_RETRIEVAL_MODE", "two_stage")
    monkeypatch.setenv("PIO_RETRIEVAL_PARTITIONS", "16")
    monkeypatch.setenv("PIO_RETRIEVAL_NPROBE", "2")
    rng = np.random.default_rng(3)
    n_items, rank = 400, 8
    model = _make_model(n_users=10, n_items=n_items, rank=rank, seed=3)
    mf = model.mf
    mf._ivf = ann.build_ivf(mf.item_emb, mf.item_bias,
                            key=ann.build_key(n_items))
    # move item 123 straight into u0's taste — far from its old partition
    target = 123
    row = np.zeros(rank + 1, np.float32)
    row[:rank] = mf.user_emb[0] * 40
    d = _delta_for(model, item_rows={target: row})
    new = model.apply_delta(d)
    assert new.mf._ivf.stale_count == 1
    assert new.mf._ivf.stats()["stale_rows"] == 1
    uidx = np.asarray([0], np.int32)
    pruned_idx, pruned_scores = TwoTowerMF.recommend_batch(new.mf, uidx, 5)
    exact_idx, exact_scores = TwoTowerMF.recommend_batch(
        new.mf, uidx, 5, exact=True)
    # the pruned probe CANNOT miss the moved row, and it serves the
    # post-update score, not the pre-update embedding
    assert exact_idx[0][0] == target
    assert pruned_idx[0][0] == target
    np.testing.assert_allclose(pruned_scores[0][0], exact_scores[0][0],
                               rtol=1e-5)
    # the OLD model's index view is untouched (shared arrays, no overlay)
    assert model.mf._ivf.stale_count == 0
    del rng


def test_two_stage_stale_threshold_triggers_rebuild(monkeypatch):
    from incubator_predictionio_tpu.serving import ann

    monkeypatch.setenv("PIO_RETRIEVAL_MODE", "two_stage")
    monkeypatch.setenv("PIO_RETRIEVAL_PARTITIONS", "8")
    monkeypatch.setenv("PIO_STREAM_STALE_REBUILD_FRAC", "0.01")
    model = _make_model(n_users=10, n_items=200, rank=8, seed=5)
    mf = model.mf
    mf._ivf = ann.build_ivf(mf.item_emb, mf.item_bias,
                            key=ann.build_key(200))
    rows = {j: np.ones(9, np.float32) * 0.1 for j in range(10)}
    new = model.apply_delta(_delta_for(model, item_rows=rows))
    # 5% stale > 1% threshold: re-clustered from current tables
    assert new.mf._ivf.stale_count == 0
    assert new.mf._ivf is not mf._ivf


# ---------------------------------------------------------------------------
# convergence parity vs a full retrain (the documented tolerance)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_incremental_convergence_tracks_full_retrain(tmp_path):
    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerMF,
    )
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.streaming.guard import (
        compare_to_reference,
    )

    rng = np.random.default_rng(7)
    n_users, n_items, rank = 40, 30, 8
    # low-rank ground truth ratings
    gu = rng.normal(size=(n_users, 4))
    gi = rng.normal(size=(n_items, 4))
    truth = gu @ gi.T + 3.0

    def sample(n, seed):
        r = np.random.default_rng(seed)
        u = r.integers(0, n_users, n)
        i = r.integers(0, n_items, n)
        return u.astype(np.int32), i.astype(np.int32), \
            truth[u, i].astype(np.float32)

    u1, i1, r1 = sample(600, 1)
    u2, i2, r2 = sample(200, 2)
    cfg = TwoTowerConfig(rank=rank, learning_rate=0.03, epochs=30,
                         batch_size=256, seed=0)
    ctx = MeshContext.create()
    base_mf = TwoTowerMF(cfg).fit(ctx, u1, i1, r1, n_users, n_items)
    full_mf = TwoTowerMF(cfg).fit(
        ctx, np.concatenate([u1, u2]), np.concatenate([i1, i2]),
        np.concatenate([r1, r2]), n_users, n_items)
    user_map = BiMap({f"u{i}": i for i in range(n_users)})
    item_map = BiMap({f"i{j}": j for j in range(n_items)})
    base = RecModel(base_mf, user_map, item_map)
    full = RecModel(full_mf, user_map, item_map)
    # stream the E2 events into the base model (a few passes — the
    # incremental path sees each event once per poll; extra passes stand
    # in for the updater folding a longer live window)
    trainer = _trainer_for(base)
    events = [_rate(f"u{u}", f"i{i}", float(r), m)
              for m, (u, i, r) in enumerate(zip(u2, i2, r2))]
    result = None
    for _ in range(10):
        result, poison = trainer.fold(events)
        assert not poison
    inc = base.apply_delta(deltas.ModelDelta(
        base_instance="x", chain_base=0, from_seq=0, to_seq=1,
        user_rows=result.user_rows, item_rows=result.item_rows))

    before = compare_to_reference(base, full, sample_users=n_users)
    after = compare_to_reference(inc, full, sample_users=n_users)
    # the incremental model moved TOWARD the full retrain...
    assert after["score_rmse"] < before["score_rmse"]
    assert after["topk_overlap"] >= before["topk_overlap"]
    # ...and the E2 events it folded are genuinely learned: its error on
    # them approaches the full retrain's
    def mse(m, u, i, r):
        ue = m.mf.user_emb[u]
        ie = m.mf.item_emb[i]
        pred = (ue * ie).sum(axis=1) + m.mf.user_bias[u] \
            + m.mf.item_bias[i] + m.mf.mean
        return float(np.mean((pred - r) ** 2))

    mse_base = mse(base, u2, i2, r2)
    mse_inc = mse(inc, u2, i2, r2)
    mse_full = mse(full, u2, i2, r2)
    assert mse_inc < mse_base
    assert mse_inc <= mse_full * 3.0 + 0.5  # documented tolerance band
