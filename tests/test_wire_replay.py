"""Recorded-transcript replay: the wire clients vs captured byte streams.

VERDICT r3 #2 (offline half): the committed transcripts replay in default
CI with no service running. The replay server asserts the client still
emits the recorded request stream and feeds back the recorded responses;
the scenario then re-asserts the parsed results recorded at capture time.
This pins framing AND parsing in both directions — any refactor of
postgres.py / elasticsearch.py that changes what goes on the wire, or how
responses are interpreted, fails here immediately.

``meta.captured_against`` says what produced the server bytes (currently
the in-process protocol fakes; re-capturing against real services —
tests/tools/capture_transcripts.py, tests/LIVE_TESTS.md — upgrades the
same files to real-server oracles with no test change).
"""

import json
import os

import pytest

from tests.fixtures.pg_capability import pg_fake_skip_reason
from tests.fixtures.wire_capture import ReplayServer

TRANSCRIPTS = os.path.join(os.path.dirname(__file__), "transcripts")

# The postgres transcript is captured against (and re-captured via) the
# fake-pg protocol server; a host whose sqlite cannot back the fake cannot
# validate or refresh the recording either, so it gates on the same probe.
_PG_SKIP = pg_fake_skip_reason()


#: connect / handshake and read timeouts (seconds) of the replayed client: a
#: replay that stalls fails the test in seconds, not in the client's default
#: 30 s a handshake x 3 attempts
_PG_FAST = {"TIMEOUT": "1", "READ_TIMEOUT": "2"}


def _load(name: str) -> dict:
    with open(os.path.join(TRANSCRIPTS, name)) as f:
        return json.load(f)


@pytest.mark.skipif(_PG_SKIP is not None, reason=_PG_SKIP or "")
def test_postgres_wire_replay(monkeypatch):
    from incubator_predictionio_tpu.data.storage.postgres import (
        PostgresStorageClient,
    )
    from tests.wire_scenarios import pg_scenario

    tr = _load("postgres_scenario.json")
    assert tr["meta"]["mode"] == "exact"
    # identical startup/auth bytes: same (test) credentials and the pinned
    # SCRAM nonce the capture ran with — this is what makes a real-server
    # capture (password auth) replayable byte-exactly
    from incubator_predictionio_tpu.data.storage import postgres as _pg
    monkeypatch.setattr(_pg, "_gen_nonce",
                        lambda: tr["meta"]["scram_nonce"])
    server = ReplayServer(tr, mode="exact")
    try:
        client = PostgresStorageClient(
            {"HOST": "127.0.0.1", "PORT": str(server.port), **_PG_FAST,
             **tr["meta"].get("client_config", {})})
        results = pg_scenario(client)
        client.close()
    finally:
        server.close()
    assert server.errors == [], server.errors
    assert results == tr["meta"]["expected_results"]


@pytest.mark.skipif(_PG_SKIP is not None, reason=_PG_SKIP or "")
def test_postgres_replay_with_nothing_left_fails_the_client_fast():
    """A client whose stream outgrew the recording (a statement the capture
    never saw) must get an error, never a hang: the replay server hangs up
    on the divergence and REFUSES the retry's connection, so no attempt of
    the client waits out a handshake timeout."""
    import socket
    import time

    from incubator_predictionio_tpu.data.storage.base import StorageError
    from incubator_predictionio_tpu.data.storage.postgres import (
        PostgresStorageClient,
    )

    tr = _load("postgres_scenario.json")
    # the recording, cut off after the handshake: no statement is answered
    conn = tr["connections"][0]
    ddl = next(i for i, (tag, h) in enumerate(conn)
               if tag == "C" and b"CREATE TABLE" in bytes.fromhex(h))
    server = ReplayServer({"connections": [conn[:ddl]]}, mode="exact")
    t0 = time.monotonic()
    try:
        with pytest.raises(StorageError):
            PostgresStorageClient(
                {"HOST": "127.0.0.1", "PORT": str(server.port), **_PG_FAST})
        took = time.monotonic() - t0
        # what makes it fast: the one recorded connection is spent and the
        # listener is gone, so a reconnect is refused, not parked
        with pytest.raises(ConnectionRefusedError):
            socket.create_connection(("127.0.0.1", server.port), timeout=1.0)
    finally:
        server.close()
    # 0.14 s on an idle host; the bound is a loaded host's slack, and still
    # far under the 61 s that parked handshakes once cost the test above
    assert took < 5.0


def test_elasticsearch_wire_replay():
    from incubator_predictionio_tpu.data.storage.elasticsearch import (
        ESStorageClient,
    )
    from tests.wire_scenarios import es_scenario

    tr = _load("elasticsearch_scenario.json")
    assert tr["meta"]["mode"] == "http"
    server = ReplayServer(tr, mode="http")
    try:
        client = ESStorageClient({"URL": f"http://127.0.0.1:{server.port}"})
        results = es_scenario(client)
        client.close()
    finally:
        server.close()
    assert server.errors == [], server.errors
    assert results == tr["meta"]["expected_results"]


def test_s3_wire_replay():
    from incubator_predictionio_tpu.data.storage import Storage
    from tests.wire_scenarios import s3_scenario

    tr = _load("s3_scenario.json")
    assert tr["meta"]["mode"] == "http"
    server = ReplayServer(tr, mode="http")
    try:
        s = Storage({
            "PIO_STORAGE_SOURCES_S3_TYPE": "s3",
            "PIO_STORAGE_SOURCES_S3_ENDPOINT": f"http://127.0.0.1:{server.port}",
            "PIO_STORAGE_SOURCES_S3_BUCKET_NAME": tr["meta"]["bucket"],
            "PIO_STORAGE_SOURCES_S3_ACCESS_KEY": "test-access",
            "PIO_STORAGE_SOURCES_S3_SECRET_KEY": "test-secret",
            "PIO_STORAGE_SOURCES_S3_REGION": "us-east-1",
        })
        results = s3_scenario(s.get_model_data_models())
        s.close()
    finally:
        server.close()
    assert server.errors == [], server.errors
    assert results == tr["meta"]["expected_results"]


def test_webhdfs_wire_replay():
    from incubator_predictionio_tpu.data.storage import Storage
    from tests.wire_scenarios import webhdfs_scenario

    tr = _load("webhdfs_scenario.json")
    assert tr["meta"]["mode"] == "http"
    # the recorded 307 Location carries the capture-time proxy port; rewrite
    # it to the replay server's so the datanode write lands here too
    old = f"127.0.0.1:{tr['meta']['capture_port']}".encode()
    server = ReplayServer(tr, mode="http")
    # the port is only known after bind; nothing connects before this line
    server.rewrite = (old, f"127.0.0.1:{server.port}".encode())
    try:
        s = Storage({
            "PIO_STORAGE_SOURCES_H_TYPE": "webhdfs",
            "PIO_STORAGE_SOURCES_H_URL": f"http://127.0.0.1:{server.port}",
            "PIO_STORAGE_SOURCES_H_PATH": "/pio/models",
        })
        results = webhdfs_scenario(s.get_model_data_models())
        s.close()
    finally:
        server.close()
    assert server.errors == [], server.errors
    assert results == tr["meta"]["expected_results"]


def test_replay_detects_divergence():
    """The replay harness itself must FAIL when the client's bytes change —
    otherwise the two tests above prove nothing."""
    import socket

    tr = {"connections": [[["C", b"hello".hex()], ["S", b"ok".hex()]]]}
    server = ReplayServer(tr, mode="exact")
    try:
        s = socket.create_connection(("127.0.0.1", server.port))
        s.sendall(b"hellX")  # diverges at the last byte
        s.settimeout(2.0)
        try:
            s.recv(16)
        except OSError:
            pass
        s.close()
        import time

        for _ in range(50):
            if server.errors:
                break
            time.sleep(0.05)
    finally:
        server.close()
    assert server.errors and "diverged" in server.errors[0]
