"""PostgreSQL wire client: SCRAM-SHA-256 correctness + handshake behaviors.

The full storage contract runs against the protocol fake in
test_storage_contract.py (param "postgres"); this file covers the pieces the
contract can't: the RFC 7677 SCRAM test vector (pinning the client-side
derivation against the spec, independent of our own server fake), the
authenticated handshake, auth failure, and bytea/typed round-trips.
"""

import base64

import pytest

from incubator_predictionio_tpu.data.storage.base import Model, StorageError
from incubator_predictionio_tpu.data.storage.postgres import (
    PostgresStorageClient,
    scram_client_proofs,
)
from tests.fixtures.fake_pg import FakePG
from tests.fixtures.pg_capability import pg_fake_skip_reason

_PG_SKIP = pg_fake_skip_reason()


def test_scram_rfc7677_vector():
    """RFC 7677 §3 example: user=user pass=pencil, known nonces/salt."""
    client_first_bare = "n=user,r=rOprNGfwEbeRWgbNEkqO"
    server_first = ("r=rOprNGfwEbeRWgbNEkqO%hvYDpWUa2RaTCAfuxFIlj)hNlF$k0,"
                    "s=W22ZaJ0SNY7soEsUEjb6gQ==,i=4096")
    client_final_bare = ("c=biws,r=rOprNGfwEbeRWgbNEkqO%hvYDpWUa2RaTCAfuxFIlj"
                         ")hNlF$k0")
    auth_message = ",".join(
        [client_first_bare, server_first, client_final_bare]).encode()
    salt = base64.b64decode("W22ZaJ0SNY7soEsUEjb6gQ==")
    proof, server_sig = scram_client_proofs("pencil", salt, 4096, auth_message)
    assert base64.b64encode(proof).decode() == \
        "dHzbZapWIk4jUhN+Ute9ytag9zjfMHgsqmmiz7AndVQ="
    assert base64.b64encode(server_sig).decode() == \
        "6rriTRBi23WpRR/wtup+mMhUZUn/dB5nLTJRsjl95G4="


def test_scram_handshake_and_auth_failure():
    server = FakePG(password="sekret")
    try:
        c = PostgresStorageClient({
            "HOST": "127.0.0.1", "PORT": str(server.port),
            "USERNAME": "pio", "PASSWORD": "sekret"})
        assert c.apps().get_all() == []
        c.close()
        with pytest.raises(StorageError, match="28P01|authentication"):
            PostgresStorageClient({
                "HOST": "127.0.0.1", "PORT": str(server.port),
                "USERNAME": "pio", "PASSWORD": "wrong"})
    finally:
        server.close()


@pytest.mark.skipif(_PG_SKIP is not None, reason=_PG_SKIP or "")
def test_bytea_and_null_round_trip():
    server = FakePG()
    try:
        c = PostgresStorageClient({"HOST": "127.0.0.1",
                                   "PORT": str(server.port)})
        blob = bytes(range(256)) * 3  # every byte value through \x encoding
        c.models().insert(Model("m", blob))
        assert c.models().get("m").models == blob
        # NULL params and results (description=None)
        from incubator_predictionio_tpu.data.storage.base import App

        app_id = c.apps().insert(App(0, "nulldesc", None))
        assert c.apps().get(app_id).description is None
        c.close()
    finally:
        server.close()


def test_digit_only_text_values_stay_verbatim():
    """entity ids like "007" are TEXT: they must round-trip unmangled and
    keep matching find(entity_id=...) (real PG binds by column type)."""
    import datetime as dt

    from incubator_predictionio_tpu.data import Event

    server = FakePG()
    try:
        c = PostgresStorageClient({"HOST": "127.0.0.1",
                                   "PORT": str(server.port)})
        ev = c.events()
        ev.init(1)
        ev.insert(Event(event="rate", entity_type="user", entity_id="007",
                        target_entity_type="item", target_entity_id="0042",
                        event_time=dt.datetime(2020, 1, 1,
                                               tzinfo=dt.timezone.utc)), 1)
        got = list(ev.find(1, entity_id="007"))
        assert len(got) == 1
        assert got[0].entity_id == "007" and got[0].target_entity_id == "0042"
        assert list(ev.find(1, entity_id="7")) == []
        c.close()
    finally:
        server.close()


@pytest.mark.skipif(_PG_SKIP is not None, reason=_PG_SKIP or "")
def test_poisoned_connection_reconnects():
    """A mid-exchange socket failure must not leave stale frames for the
    next query: the connection is poisoned and transparently re-established.
    An idempotent read answers through ONE reconnect inside the call; a
    mutation keeps its single attempt (a lost response may have committed),
    so it raises and the NEXT call reconnects."""
    from incubator_predictionio_tpu.data.storage.base import App

    server = FakePG()
    try:
        c = PostgresStorageClient({"HOST": "127.0.0.1",
                                   "PORT": str(server.port)})
        app_id = c.apps().insert(App(0, "pre-crash", None))
        assert len(server._threads) == 1
        # sever the socket under the client mid-session
        c._conn._sock.close()
        assert [a.name for a in c.apps().get_all()] == ["pre-crash"]
        assert len(server._threads) == 2
        # the same fault under a mutation: one attempt, no silent re-send
        c._conn._sock.close()
        with pytest.raises(StorageError):
            c.apps().insert(App(0, "lost", None))
        assert len(server._threads) == 2
        # next call reconnects and sees the (server-side) state again
        assert c.apps().get(app_id).name == "pre-crash"
        assert c.apps().get_by_name("lost") is None
        assert len(server._threads) == 3
        c.close()
    finally:
        server.close()


def test_batch_with_duplicate_ids_is_last_wins():
    """Real PG rejects a multi-row upsert touching one id twice (21000);
    the backend must collapse duplicates last-wins like the other backends."""
    import datetime as dt

    from incubator_predictionio_tpu.data import DataMap, Event

    server = FakePG()
    try:
        c = PostgresStorageClient({"HOST": "127.0.0.1",
                                   "PORT": str(server.port)})
        ev = c.events()
        ev.init(1)
        t0 = dt.datetime(2020, 1, 1, tzinfo=dt.timezone.utc)

        def mk(v):
            return Event(event_id="dup", event="rate", entity_type="user",
                         entity_id="u1", target_entity_type="item",
                         target_entity_id="i1",
                         properties=DataMap({"rating": v}), event_time=t0)

        ids = ev.insert_batch([mk(1.0), mk(5.0)], 1)
        assert ids == ["dup", "dup"]
        [got] = list(ev.find(1))
        assert got.properties.get("rating") == 5.0  # last wins
        c.close()
    finally:
        server.close()


def test_url_config_form():
    server = FakePG(password="pw")
    try:
        c = PostgresStorageClient({
            "URL": f"postgresql://pio:pw@127.0.0.1:{server.port}/pio"})
        assert c.apps().get_all() == []
        c.close()
        # the reference's literal pio-env.sh form: jdbc: URL without
        # credentials + separate USERNAME/PASSWORD keys
        c = PostgresStorageClient({
            "URL": f"jdbc:postgresql://127.0.0.1:{server.port}/pio",
            "USERNAME": "pio", "PASSWORD": "pw"})
        assert c.apps().get_all() == []
        c.close()
    finally:
        server.close()


def test_unreachable_reports_cleanly():
    with pytest.raises(StorageError, match="unreachable"):
        PostgresStorageClient({"HOST": "127.0.0.1", "PORT": "1",
                               "TIMEOUT": "2"})


def test_keyset_streaming_pagination():
    """find() streams in keyset-paginated pages (ADVICE r3: no full-scan
    buffering); with chunk=3 a 10-event scan takes 4 pages and must still
    return every event exactly once, in order, both directions."""
    import datetime as dt

    from incubator_predictionio_tpu.data import Event

    server = FakePG()
    try:
        c = PostgresStorageClient({"HOST": "127.0.0.1",
                                   "PORT": str(server.port)})
        ev = c.events()
        ev.init(1)
        for i in range(10):
            ev.insert(
                Event(event="rate", entity_type="user", entity_id=f"u{i}",
                      event_time=dt.datetime(2020, 1, 1, 0, 0, i % 4,
                                             tzinfo=dt.timezone.utc)), 1)
        from incubator_predictionio_tpu.data.storage.base import UNSET

        sql, params = ev._find_sql(
            1, None, None, None, None, None, None, UNSET, UNSET)
        got = list(ev._stream_find(sql, params, chunk=3))
        assert len(got) == 10
        assert sorted(e.entity_id for e in got) == sorted(f"u{i}"
                                                          for i in range(10))
        times = [e.event_time for e in got]
        assert times == sorted(times)
        rev = list(ev._stream_find(sql, params, reversed=True, chunk=3))
        assert [e.event_id for e in rev] == [e.event_id for e in got][::-1]
        lim = list(ev._stream_find(sql, params, limit=7, chunk=3))
        assert len(lim) == 7 and [e.event_id for e in lim] == \
            [e.event_id for e in got][:7]
        c.close()
    finally:
        server.close()
