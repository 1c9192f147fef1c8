"""C ingest core parity: native parse→validate→encode vs the Python path.

VERDICT r4 next #4: the native fast path (native/src/ingest.cc via
EventLogEvents.ingest_raw) must reproduce the Python ingest path
bit-for-bit — statuses, error messages, and the stored events
(EventServer.scala:376-462 batch semantics). Two identical event servers run
side by side, one with PIO_NATIVE_DISABLE=1; every scenario (hand-written
matrix + randomized fuzz) must produce identical HTTP responses and
identical stored events, modulo the random event ids and server-stamped
creation times.
"""

import asyncio
import datetime as dt
import json
import random
import string

import pytest
from aiohttp.test_utils import TestClient, TestServer

from incubator_predictionio_tpu import native
from incubator_predictionio_tpu.data.storage import AccessKey, App, Storage
from incubator_predictionio_tpu.server.event_server import (
    EventServer,
    EventServerConfig,
)

UTC = dt.timezone.utc

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


def _mk_env(tmp_path, name, disable_native, backend="eventlog"):
    if backend == "eventlog":
        src_conf = {
            f"PIO_STORAGE_SOURCES_{name}_TYPE": "eventlog",
            f"PIO_STORAGE_SOURCES_{name}_PATH": str(tmp_path / name),
        }
    else:
        src_conf = {
            f"PIO_STORAGE_SOURCES_{name}_TYPE": "sqlite",
            f"PIO_STORAGE_SOURCES_{name}_PATH": str(tmp_path / f"{name}.db"),
        }
    conf = {
        **src_conf,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": name,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": name,
        # metadata still needs a home
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "MEM",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    }
    storage = Storage(conf)
    app_id = storage.get_meta_data_apps().insert(App(0, f"app-{name}"))
    storage.get_events().init(app_id)
    key = storage.get_meta_data_access_keys().insert(AccessKey("", app_id, ()))
    limited = storage.get_meta_data_access_keys().insert(
        AccessKey("", app_id, ("rate", "$set")))
    return storage, app_id, key, limited, disable_native


def _normalize(batch_resp):
    """Strip the random eventId; keep status/message structure."""
    out = []
    for item in batch_resp:
        item = dict(item)
        if "eventId" in item:
            assert len(item["eventId"]) == 32
            item["eventId"] = "<id>"
        out.append(item)
    return out


def _event_key(e, t0):
    """Comparable view of a stored Event. Server-generated values (ids,
    creation times, and the now() default for an absent eventTime) differ
    between the two servers — an eventTime stamped during this test run
    collapses to a sentinel."""
    event_time = "<now>" if e.event_time >= t0 else e.event_time
    return (
        e.event, e.entity_type, e.entity_id,
        e.target_entity_type, e.target_entity_id,
        dict(e.properties), event_time, tuple(e.tags), e.pr_id,
    )


def run_pair(tmp_path, scenarios, monkeypatch, backend="eventlog"):
    """POST every scenario to a native-path server and a Python-path server;
    assert identical responses and identical stored events."""

    async def drive(disable):
        name = "NATC" if not disable else "PYF"
        storage, app_id, key, _limited, _ = _mk_env(
            tmp_path, name, disable, backend)
        if disable:
            monkeypatch.setenv("PIO_NATIVE_DISABLE", "1")
        else:
            monkeypatch.delenv("PIO_NATIVE_DISABLE", raising=False)
        native._reset_for_tests()
        server = EventServer(EventServerConfig(), storage=storage)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        responses = []
        try:
            for sc in scenarios:
                if sc.get("single"):
                    resp = await client.post(
                        f"/events.json?accessKey={key}", data=sc["body"],
                        headers={"Content-Type": "application/json"})
                else:
                    url = f"/batch/events.json?accessKey={sc.get('key', key)}"
                    if sc.get("limited"):
                        url = f"/batch/events.json?accessKey={_limited}"
                    resp = await client.post(
                        url, data=sc["body"],
                        headers={"Content-Type": "application/json"})
                if resp.content_type == "application/json":
                    body = await resp.json()
                else:  # e.g. the 500 both paths produce on invalid UTF-8
                    body = await resp.text()
                responses.append((resp.status, body))
        finally:
            await client.close()
        events = list(storage.get_events().find(app_id))
        storage.close()
        native._reset_for_tests()
        return responses, events

    t0 = dt.datetime.now(UTC) - dt.timedelta(seconds=1)
    native_resp, native_events = asyncio.run(drive(False))
    python_resp, python_events = asyncio.run(drive(True))

    assert len(native_resp) == len(python_resp)
    for i, ((ns, nb), (ps, pb)) in enumerate(zip(native_resp, python_resp)):
        assert ns == ps, (i, ns, ps, nb, pb)
        if isinstance(nb, str) or isinstance(pb, str):
            # non-JSON bodies (the 500 on invalid UTF-8): status compared
            # above; the text is aiohttp's generic error page
            assert isinstance(nb, str) and isinstance(pb, str), (i, nb, pb)
        elif isinstance(nb, list):
            assert _normalize(nb) == _normalize(pb), (i, nb, pb)
        else:
            nb2, pb2 = dict(nb), dict(pb)
            if "eventId" in nb2 and "eventId" in pb2:
                nb2["eventId"] = pb2["eventId"] = "<id>"
            if ns >= 500:
                # the obs middleware mints a fresh trace id into every 5xx
                # body: both fronts must carry one, and it is theirs alone
                assert nb2.pop("traceId") and pb2.pop("traceId"), (i, nb, pb)
            assert nb2 == pb2, (i, nb, pb)

    nk = sorted(map(repr, (_event_key(e, t0) for e in native_events)))
    pk = sorted(map(repr, (_event_key(e, t0) for e in python_events)))
    assert nk == pk


MATRIX = [
    # plain happy path + unicode + nested properties + tags
    [{"event": "rate", "entityType": "user", "entityId": "u1",
      "targetEntityType": "item", "targetEntityId": "i€1",
      "properties": {"rating": 4.5, "note": "café \U0001F600",
                     "nested": {"a": [1, 2.5, None, True, "x"], "b": {}},
                     "big": 12345678901234567890123456789,
                     "neg": -9223372036854775808},
      "eventTime": "2020-01-02T03:04:05.123456+05:30",
      "tags": ["a", "b"], "prId": "pr-1"}],
    # every validation failure, one per item (order + message parity)
    [{"event": "", "entityType": "user", "entityId": "u"},
     {"event": "e", "entityType": "", "entityId": "u"},
     {"event": "e", "entityType": "user", "entityId": ""},
     {"event": "e", "entityType": "user", "entityId": "u",
      "targetEntityType": "item"},
     {"event": "e", "entityType": "user", "entityId": "u",
      "targetEntityType": "", "targetEntityId": "i"},
     {"event": "e", "entityType": "user", "entityId": "u",
      "targetEntityType": "item", "targetEntityId": ""},
     {"event": "$unset", "entityType": "user", "entityId": "u"},
     {"event": "$bogus", "entityType": "user", "entityId": "u"},
     {"event": "pio_x", "entityType": "user", "entityId": "u"},
     {"event": "$set", "entityType": "user", "entityId": "u",
      "targetEntityType": "item", "targetEntityId": "i",
      "properties": {"a": 1}},
     {"event": "e", "entityType": "pio_bad", "entityId": "u"},
     {"event": "e", "entityType": "user", "entityId": "u",
      "targetEntityType": "pio_bad", "targetEntityId": "i"},
     {"event": "e", "entityType": "user", "entityId": "u",
      "properties": {"pio_p": 1}},
     {"event": "e", "entityType": "user", "entityId": "u",
      "properties": {"$p": 1}},
     {"event": "e", "entityType": "user", "entityId": "u", "tags": "notalist"},
     {"event": "e", "entityType": "user", "entityId": "u",
      "properties": "notanobject"},
     {"event": 5, "entityType": "user", "entityId": "u"},
     {"event": "e", "entityType": None, "entityId": "u"},
     {"event": "e", "entityType": "user"},
     "not an object",
     42],
    # specials that must succeed: pio_pr entity, $delete, $set with props
    [{"event": "$delete", "entityType": "user", "entityId": "u9"},
     {"event": "predict", "entityType": "pio_pr", "entityId": "p1"},
     {"event": "$set", "entityType": "user", "entityId": "u10",
      "properties": {"a": False}}],
    # timestamp shapes: Z, offsets, date-only, epoch int, absent, bad
    [{"event": "e", "entityType": "u", "entityId": "1",
      "eventTime": "2021-06-01T10:20:30Z"},
     {"event": "e", "entityType": "u", "entityId": "2",
      "eventTime": "2021-06-01T10:20:30-08:00"},
     {"event": "e", "entityType": "u", "entityId": "3",
      "eventTime": "2021-06-01"},
     {"event": "e", "entityType": "u", "entityId": "4",
      "eventTime": 1622543999},
     {"event": "e", "entityType": "u", "entityId": "5"},
     {"event": "e", "entityType": "u", "entityId": "6",
      "eventTime": "not-a-time"},
     {"event": "e", "entityType": "u", "entityId": "7",
      "eventTime": "2021-13-45T99:99:99Z"},
     {"event": "e", "entityType": "u", "entityId": "8",
      "eventTime": 1622543999.25},
     {"event": "e", "entityType": "u", "entityId": "9",
      "eventTime": "2021-06-01T10:20:30.5Z"}],
    # constructs that force the C fallback: non-string tags, weird unicode
    [{"event": "e", "entityType": "u", "entityId": "1", "tags": ["x", 3]},
     {"event": "e", "entityType": "u", "entityId": "2",
      "properties": {"f": 1e999}},
     {"event": "e", "entityType": "u", "entityId": "3",
      "properties": {"nan": float("nan") if False else 1}}],
]


@pytest.fixture(params=["eventlog", "sqlite"])
def backend(request):
    return request.param


def test_matrix_parity(tmp_path, monkeypatch, backend):
    scenarios = [{"body": json.dumps(batch).encode()} for batch in MATRIX]
    # malformed JSON / wrong top-level type / oversized batch
    scenarios.append({"body": b"{nope"})
    scenarios.append({"body": b"\"a string\""})
    scenarios.append({"body": json.dumps(
        [{"event": "e", "entityType": "u", "entityId": str(i)}
         for i in range(51)]).encode()})
    # review-finding regressions: invalid UTF-8 body, leading-zero numbers,
    # empty client eventId (both must behave exactly like the Python path)
    scenarios.append({"body": b'[{"event":"e","entityType":"\xff","entityId":"x"}]'})
    scenarios.append({"body": b'[{"event":"e","entityType":"t","entityId":"i",'
                              b'"properties":{"x":01}}]'})
    scenarios.append({"body": json.dumps(
        [{"event": "e", "entityType": "t", "entityId": "i",
          "eventId": ""}]).encode()})
    # whitelist: limited key allows only rate and $set
    scenarios.append({"limited": True, "body": json.dumps(
        [{"event": "rate", "entityType": "u", "entityId": "1"},
         {"event": "buy", "entityType": "u", "entityId": "2"},
         {"event": "$set", "entityType": "u", "entityId": "3",
          "properties": {"a": 1}}]).encode()})
    # single-event endpoint: success, validation error, bad JSON
    scenarios.append({"single": True, "body": json.dumps(
        {"event": "e", "entityType": "u", "entityId": "s1",
         "properties": {"k": [True, None]}}).encode()})
    scenarios.append({"single": True, "body": json.dumps(
        {"event": "$unset", "entityType": "u", "entityId": "s2"}).encode()})
    scenarios.append({"single": True, "body": b"[1,2]"})
    run_pair(tmp_path, scenarios, monkeypatch, backend)


def _rand_value(rng, depth=0):
    kind = rng.randrange(8 if depth < 3 else 5)
    if kind == 0:
        return None
    if kind == 1:
        return rng.choice([True, False])
    if kind == 2:
        return rng.randrange(-(2 ** 70), 2 ** 70)  # crosses the i64 boundary
    if kind == 3:
        return rng.uniform(-1e6, 1e6)
    if kind == 4:
        return "".join(rng.choice(string.printable) for _ in range(rng.randrange(6))) \
            + rng.choice(["", "é", "€", "\U0001F600"])
    if kind == 5:
        return [_rand_value(rng, depth + 1) for _ in range(rng.randrange(3))]
    return {("k%d" % i) + rng.choice(["", "é"]): _rand_value(rng, depth + 1)
            for i in range(rng.randrange(3))}


def _rand_event(rng):
    d = {
        "event": rng.choice(["rate", "buy", "$set", "$unset", "$delete",
                             "pio_x", "", "e€"]),
        "entityType": rng.choice(["user", "pio_pr", "pio_bad", "", "t"]),
        "entityId": rng.choice(["", "u1", "idé"]),
    }
    if rng.random() < 0.5:
        d["targetEntityType"] = rng.choice(["item", "", "pio_t"])
    if rng.random() < 0.5:
        d["targetEntityId"] = rng.choice(["i1", ""])
    if rng.random() < 0.7:
        d["properties"] = {("p%d" % i) + rng.choice(["", "é", "pio_"]):
                           _rand_value(rng) for i in range(rng.randrange(4))}
    if rng.random() < 0.3:
        d["tags"] = [rng.choice(["a", "b", 3, None])
                     for _ in range(rng.randrange(3))]
    if rng.random() < 0.5:
        d["eventTime"] = rng.choice([
            "2020-01-02T03:04:05Z", "2020-01-02T03:04:05.999999+01:00",
            "2020-02-29", "1999-12-31T23:59:59-11:30", 0, 1622543999,
            "garbage", 1e9 + 0.5, None,
        ])
    if rng.random() < 0.2:
        d["prId"] = "pr"
    return d


def test_fuzz_parity(tmp_path, monkeypatch, backend):
    rng = random.Random(20260730)
    scenarios = []
    for _ in range(40):
        batch = [_rand_event(rng) for _ in range(rng.randrange(1, 8))]
        scenarios.append({"body": json.dumps(batch).encode()})
    run_pair(tmp_path, scenarios, monkeypatch, backend)


def test_sqlite_fast_path_actually_engages(tmp_path, monkeypatch):
    """Same guard for the sqlite sink (pl_ingest_sqlite over libsqlite3):
    a silent permanent fallback would make the sqlite parity params prove
    nothing."""
    monkeypatch.delenv("PIO_NATIVE_DISABLE", raising=False)
    native._reset_for_tests()
    storage, app_id, key, _l, _ = _mk_env(tmp_path, "SQL", False, "sqlite")
    store = storage.get_events()
    body = json.dumps([
        {"event": "rate", "entityType": "user", "entityId": "u1",
         "properties": {"x": 1.5}}]).encode()
    out = store.ingest_raw(body, False, 50, [], app_id)
    assert out is not None and out[0]["status"] == 201
    ev = list(store.find(app_id))
    assert len(ev) == 1 and ev[0].properties["x"] == 1.5
    got = store.get(out[0]["eventId"], app_id)
    assert got is not None and got.entity_id == "u1"
    # the time-prefixed id scheme (btree locality) is preserved
    assert len(out[0]["eventId"]) == 32 and out[0]["eventId"].endswith("0")
    storage.close()


def test_sqlite_entity_shard_matches_python(tmp_path, monkeypatch):
    """The C sink's crc32 entity_shard column must be bit-identical to
    data/storage/base.entity_shard — a divergence would silently corrupt
    find_sharded reads (a wrong-shard row never appears in any shard scan)."""
    import sqlite3 as _sq

    from incubator_predictionio_tpu.data.storage.base import entity_shard
    from incubator_predictionio_tpu.data.storage.sqlite_backend import (
        N_SHARD_BUCKETS,
        _event_table,
    )

    monkeypatch.delenv("PIO_NATIVE_DISABLE", raising=False)
    native._reset_for_tests()
    storage, app_id, key, _l, _ = _mk_env(tmp_path, "SHD", False, "sqlite")
    store = storage.get_events()
    ids = ["u1", "idé", "€uro", "x" * 40, ""]
    body = json.dumps([
        {"event": "e", "entityType": "t", "entityId": eid or "z"}
        for eid in ids]).encode()
    out = store.ingest_raw(body, False, 50, [], app_id)
    assert all(r["status"] == 201 for r in out)
    db = _sq.connect(str(tmp_path / "SHD.db"))
    rows = db.execute(
        f"SELECT entity_id, entity_shard FROM {_event_table(app_id, None)}"
    ).fetchall()
    db.close()
    assert len(rows) == len(ids)
    for entity_id, shard in rows:
        assert shard == entity_shard(entity_id, N_SHARD_BUCKETS), entity_id
    storage.close()


def test_sqlite_concurrent_ingest_serializes(tmp_path, monkeypatch):
    """Two threads ingesting through the C sink concurrently: both commit
    (the per-connection mutex serializes BEGIN..COMMIT; without it the
    second transaction errors and silently falls back)."""
    import threading

    monkeypatch.delenv("PIO_NATIVE_DISABLE", raising=False)
    native._reset_for_tests()
    storage, app_id, key, _l, _ = _mk_env(tmp_path, "CON", False, "sqlite")
    store = storage.get_events()
    outs = [None, None]

    def work(slot):
        body = json.dumps([
            {"event": "e", "entityType": "t", "entityId": f"t{slot}_{i}"}
            for i in range(50)]).encode()
        outs[slot] = store.ingest_raw(body, False, 50, [], app_id)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # both went through the C path (None would mean a fallback under
    # contention — the pre-fix failure mode) and everything landed
    assert outs[0] is not None and outs[1] is not None
    assert all(r["status"] == 201 for o in outs for r in o)
    assert sum(1 for _ in store.find(app_id)) == 100
    storage.close()


def test_fast_path_actually_engages(tmp_path, monkeypatch):
    """Guard against the fast path silently never running (e.g. a signature
    drift making _try_native_ingest return None forever)."""
    monkeypatch.delenv("PIO_NATIVE_DISABLE", raising=False)
    native._reset_for_tests()
    storage, app_id, key, _l, _ = _mk_env(tmp_path, "ENG", False)
    store = storage.get_events()
    body = json.dumps([
        {"event": "rate", "entityType": "user", "entityId": "u1",
         "properties": {"x": 1}}]).encode()
    out = store.ingest_raw(body, False, 50, [], app_id)
    assert out is not None and out[0]["status"] == 201
    ev = list(store.find(app_id))
    assert len(ev) == 1 and ev[0].properties["x"] == 1
    # round-trips through the C++ scanner index too
    got = store.get(out[0]["eventId"], app_id)
    assert got is not None and got.entity_id == "u1"
    storage.close()
