"""Subprocess chaos tests (ISSUE 4 acceptance): SIGKILL a real event
server at every interesting point in the ack lifecycle — store up, store
down (WAL-spilling), mid-drain — restart it, and assert ZERO acked-event
loss with exactly-once storage; then SIGTERM for the graceful-drain exit.

Topology: the test process owns the real store (sqlite) and serves it over
a ThreadedStorageServer on a fixed port; the event server subprocess
points at it with the ``remote`` backend, so 'store down' is simply
closing the storage server — exactly the split deployment the WAL is for.

Also here (ISSUE 5 acceptance): the overload storm — a real deployed
query-server subprocess driven at ~3× its measured closed-loop capacity
through the admission layer, asserting zero in-deadline sheds below
capacity, goodput ≥ 70% of capacity, and a bounded admitted-request p99.

Marked ``slow``: real subprocess boots exceed the tier-1 budget."""

import asyncio
import json
import os
import subprocess
import sys
import time

import pytest

from incubator_predictionio_tpu.data.storage import AccessKey, App, Storage
from incubator_predictionio_tpu.server.storage_server import (
    StorageServerConfig,
    ThreadedStorageServer,
)
from tests.fixtures.procs import REPO_ROOT, ServerProc, free_port, http_json

pytestmark = pytest.mark.slow

EVENT = {"event": "rate", "entityType": "user",
         "eventTime": "2022-03-01T00:00:00Z"}


def _storage(tmp_path):
    s = Storage({
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "store.db"),
    })
    app_id = s.get_meta_data_apps().insert(App(0, "chaos"))
    s.get_events().init(app_id)
    key = s.get_meta_data_access_keys().insert(AccessKey("", app_id, ()))
    return s, app_id, key


def _es_env(storage_port: int, wal_dir: str) -> dict:
    name = "R"
    return {
        f"PIO_STORAGE_SOURCES_{name}_TYPE": "remote",
        f"PIO_STORAGE_SOURCES_{name}_URL": f"http://127.0.0.1:{storage_port}",
        f"PIO_STORAGE_SOURCES_{name}_TIMEOUT": "3",
        # fail fast so spilling starts on the first refused connection
        f"PIO_STORAGE_SOURCES_{name}_RETRY_MAX_ATTEMPTS": "1",
        **{f"PIO_STORAGE_REPOSITORIES_{repo}_{k}": name
           for repo in ("METADATA", "EVENTDATA", "MODELDATA")
           for k in ("NAME", "SOURCE")},
        "PIO_EVENT_WAL_DIR": wal_dir,
        # auth must survive the storage outage window from cache
        "PIO_EVENTSERVER_AUTH_TTL": "600",
        "PIO_EVENTSERVER_BREAKER_THRESHOLD": "2",
        "PIO_EVENTSERVER_BREAKER_RESET": "0.3",
        # the REMOTE backend's own breaker must also recover within the
        # drain window, or the final flush waits out a 30s default reset
        # the deadline doesn't cover (the WAL would keep the events —
        # durable either way — but these tests assert the flush lands)
        "PIO_RESILIENCE_BREAKER_RESET": "0.3",
        "PIO_DRAIN_DEADLINE": "20",
    }


def _post_acked(eport, key, entity_id) -> str:
    status, body = http_json(
        "POST", f"http://127.0.0.1:{eport}/events.json?accessKey={key}",
        dict(EVENT, entityId=entity_id))
    assert status == 201, (status, body)
    return body["eventId"]


def _wait_health(eport, pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            status, health = http_json(
                "GET", f"http://127.0.0.1:{eport}/health", timeout=2.0)
            if status == 200 and pred(health):
                return health
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.1)
    raise TimeoutError("health predicate not reached")


def test_event_server_kill9_and_restart_loses_zero_acked_events(tmp_path):
    storage, app_id, key = _storage(tmp_path)
    sport = free_port()
    eport = free_port()
    wal_dir = str(tmp_path / "wal")
    env = _es_env(sport, wal_dir)
    sserver = ThreadedStorageServer(
        storage, StorageServerConfig(ip="127.0.0.1", port=sport))
    es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                     "--port", str(eport)], env=env)
    acked = []
    try:
        es.wait_ready(f"http://127.0.0.1:{eport}/")
        # phase 1 — store up: synchronous inserts, acked before 201
        for i in range(8):
            acked.append(_post_acked(eport, key, f"up-{i}"))
        # phase 2 — store DOWN: acks keep flowing, now WAL-backed
        sserver.close()
        for i in range(8):
            acked.append(_post_acked(eport, key, f"down-{i}"))
        # phase 3 — kill -9 with the spill queue full of acked events
        es.kill9()
        # phase 4 — store back up, fresh event-server process: WAL replay
        # + drain must land every acked event exactly once
        sserver = ThreadedStorageServer(
            storage, StorageServerConfig(ip="127.0.0.1", port=sport))
        es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                         "--port", str(eport)], env=env)
        es.wait_ready(f"http://127.0.0.1:{eport}/")
        _wait_health(eport, lambda h: h["spillQueueDepth"] == 0
                     and h["status"] == "ok")
        # phase 5 — availability throughout: the restarted server ingests
        acked.append(_post_acked(eport, key, "post-restart"))
    finally:
        es.stop()
        sserver.close()
    ids = [e.event_id for e in storage.get_events().find(app_id)]
    assert len(ids) == len(set(ids)), "duplicate replay"
    missing = set(acked) - set(ids)
    assert not missing, f"ACKED EVENTS LOST: {missing}"
    assert len(ids) == len(acked)
    storage.close()


def test_event_server_kill9_mid_drain_then_replay_is_exactly_once(tmp_path):
    """The nastiest window: the drainer is mid-flush (some WAL records
    committed, some not) when the process dies. The replay must re-insert
    only what the cursor says is pending — and pre-assigned ids make even
    a stale cursor idempotent."""
    storage, app_id, key = _storage(tmp_path)
    sport = free_port()
    eport = free_port()
    wal_dir = str(tmp_path / "wal")
    env = _es_env(sport, wal_dir)
    sserver = ThreadedStorageServer(
        storage, StorageServerConfig(ip="127.0.0.1", port=sport))
    es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                     "--port", str(eport)], env=env)
    acked = []
    try:
        es.wait_ready(f"http://127.0.0.1:{eport}/")
        acked.append(_post_acked(eport, key, "prime"))  # warm the auth cache
        sserver.close()  # store down → spill
        for i in range(20):
            acked.append(_post_acked(eport, key, f"d-{i}"))
        # store comes back: the drainer starts committing batches…
        sserver = ThreadedStorageServer(
            storage, StorageServerConfig(ip="127.0.0.1", port=sport))
        # …and we kill -9 somewhere inside the drain window
        time.sleep(0.6)
        es.kill9()
        es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                         "--port", str(eport)], env=env)
        es.wait_ready(f"http://127.0.0.1:{eport}/")
        _wait_health(eport, lambda h: h["spillQueueDepth"] == 0
                     and h["status"] == "ok")
    finally:
        es.stop()
        sserver.close()
    ids = [e.event_id for e in storage.get_events().find(app_id)]
    assert len(ids) == len(set(ids)), "duplicate replay"
    assert set(acked) == set(ids)
    storage.close()


# ---------------------------------------------------------------------------
# overload storm (ISSUE 5): goodput under saturation through a REAL
# deployed query-server process
# ---------------------------------------------------------------------------

QUERY_DEADLINE_S = 0.4


def _train_classification(tmp_path, factory=None):
    """Train the classification template into sqlite so a `deploy`
    subprocess can serve it (the storm needs a real engine behind the
    admission layer, not a stub). ``factory`` swaps in a wrapper engine
    (e.g. the trace-plane fixture's storage-touching one) around the same
    MLP training."""
    import datetime as dt

    import numpy as np

    from incubator_predictionio_tpu.core.controller import (
        resolve_engine_factory,
    )
    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import use_storage
    from incubator_predictionio_tpu.data.storage.base import EngineInstance

    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    factory = factory or ("incubator_predictionio_tpu.templates."
                          "classification.ClassificationEngine")
    utc = dt.timezone.utc
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "store.db"),
    }
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        app_id = storage.get_meta_data_apps().insert(App(0, "storm-app"))
        events = storage.get_events()
        events.init(app_id)
        rng = np.random.default_rng(1)
        x = rng.normal(size=(64, 3))
        y = (x[:, 0] > 0).astype(int)
        batch = [
            Event(event="$set", entity_type="user", entity_id=f"u{i}",
                  properties=DataMap({"attr0": float(x[i, 0]),
                                      "attr1": float(x[i, 1]),
                                      "attr2": float(x[i, 2]),
                                      "plan": int(y[i])}),
                  event_time=dt.datetime(2020, 1, 1, tzinfo=utc))
            for i in range(64)
        ]
        events.insert_batch(batch, app_id)
        variant_path = str(tmp_path / "engine.json")
        variant = {
            "id": "storm", "version": "1",
            "engineFactory": factory,
            "datasource": {"params": {"appName": "storm-app"}},
            "algorithms": [{"name": "mlp", "params": {
                "hiddenDims": [8], "epochs": 40, "learningRate": 0.03,
                "batchSize": 64}}],
        }
        with open(variant_path, "w") as f:
            json.dump(variant, f)
        engine = resolve_engine_factory(factory)()
        engine_params = engine.engine_params_from_variant(variant)
        instance = EngineInstance(
            id="", status="INIT", start_time=dt.datetime.now(utc),
            end_time=None, engine_id="storm", engine_version="1",
            engine_variant=os.path.abspath(variant_path),
            engine_factory=variant["engineFactory"])
        run_train(engine, engine_params, instance, storage=storage,
                  ctx=MeshContext.create())
    finally:
        use_storage(prev)
        storage.close()
    return store_cfg, variant_path


# the raw-socket driver and load shapes are shared with drills.py's
# overload drill — ONE implementation (tests/fixtures/loadgen.py)
from tests.fixtures.loadgen import (  # noqa: E402
    closed_loop,
    open_loop,
    pct,
    post,
    request_bytes,
)

_STORM_BODY = json.dumps({"features": [0.5, -0.2, 0.1]}).encode()


def _status_counts(counts: dict) -> dict:
    """Integer-status slice of a loadgen counts dict (drops the
    'degraded' bookkeeping key)."""
    return {k: v for k, v in counts.items() if isinstance(k, int)}


def test_query_server_overload_storm(tmp_path):
    """ISSUE 5 acceptance, against a real subprocess:

    - `pio-tpu health` passes as the smoke gate before the storm;
    - below capacity: every request 200, ZERO sheds/rejections;
    - at ~3× measured capacity: goodput ≥ 70% of the under-capacity qps
      and the p99 of admitted requests stays bounded (≤ 2× the capacity
      p99, or the deadline-bounded ceiling the shedding order guarantees).
    """
    store_cfg, variant_path = _train_classification(tmp_path)
    qport = free_port()
    qs = ServerProc(
        ["deploy", "-v", variant_path, "--ip", "127.0.0.1",
         "--port", str(qport), "--query-timeout", str(QUERY_DEADLINE_S)],
        env={**store_cfg,
             "PIO_ADMISSION_MAX_QUEUE": "128",
             "PIO_BROWNOUT_ENTER_SEC": "0.3",
             "PIO_BROWNOUT_EXIT_SEC": "1.0"})
    base = f"http://127.0.0.1:{qport}"
    try:
        qs.wait_ready(f"{base}/", timeout=180.0)

        # smoke gate: the health verb must see a green server (non-zero
        # exit would mean breakers open / draining before we even start)
        gate = subprocess.run(
            [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "health", base], capture_output=True, text=True, timeout=30)
        assert gate.returncode == 0, gate.stdout + gate.stderr

        req = request_bytes("127.0.0.1", qport, _STORM_BODY)

        # phase 1 — strictly below capacity: serial requests
        async def warm():
            r, w = await asyncio.open_connection("127.0.0.1", qport)
            out = [await post(r, w, req) for _ in range(40)]
            w.close()
            return out

        warm_out = asyncio.run(warm())
        assert all(s == 200 for s, _, _ in warm_out)
        _, health = http_json("GET", f"{base}/health")
        adm = health["admission"]
        assert adm["rejected"] == 0, "shed below capacity"
        assert adm["shedExpired"] == 0, "in-deadline shed below capacity"

        # phase 2 — measured capacity (16 closed-loop connections)
        cap_counts, cap_lat = asyncio.run(
            closed_loop("127.0.0.1", qport, 16, 2.0, lambda: req))
        cap_qps = cap_counts.get(200, 0) / 2.0
        cap_p99 = pct(cap_lat, 0.99)
        assert cap_qps > 0

        # phase 3 — offered load at ~3× capacity, open loop
        over_counts, over_lat = asyncio.run(
            open_loop("127.0.0.1", qport, 32, 3.0, 3.0 * cap_qps,
                      lambda: req))
        goodput = over_counts.get(200, 0) / 3.0
        assert goodput >= 0.7 * cap_qps, (
            f"goodput {goodput:.0f} qps < 70% of capacity {cap_qps:.0f}")
        # every non-200 must be an orderly shed (429/504), never a 5xx
        # error or a hang
        assert set(_status_counts(over_counts)) <= {200, 429, 504}, \
            over_counts
        # bounded tail for admitted requests: 2× the under-capacity p99,
        # or the structural ceiling the 504-evict guarantees (no admitted
        # request waits past the deadline, then pays one dispatch)
        p99_over = pct(over_lat, 0.99)
        bound = max(2.0 * cap_p99, QUERY_DEADLINE_S * 1e3 + cap_p99)
        assert p99_over <= bound, (
            f"admitted p99 {p99_over:.0f}ms exceeds bound {bound:.0f}ms "
            f"(capacity p99 {cap_p99:.0f}ms)")

        # the admission layer observed the storm: its tallies are on
        # /health and the always-admitted routes stayed reachable
        _, health = http_json("GET", f"{base}/health")
        assert "admission" in health
    finally:
        qs.stop()


# ---------------------------------------------------------------------------
# multi-tenant chaos (ISSUE 20): noisy-neighbor containment + packing,
# against one real multi-tenant query-server subprocess
# ---------------------------------------------------------------------------


async def _post_hdrs(r, w, req: bytes):
    """Like loadgen.post but keeps the response headers — the tenant
    attribution oracle reads X-PIO-Tenant off every answer."""
    t0 = time.perf_counter()
    w.write(req)
    await w.drain()
    status = int((await r.readline()).split()[1])
    headers = {}
    length = 0
    while True:
        line = await r.readline()
        if line in (b"\r\n", b""):
            break
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
        if k.strip().lower() == "content-length":
            length = int(v)
    await r.readexactly(length)
    return status, headers, (time.perf_counter() - t0) * 1e3


async def _victim_loop(host, port, n_conns, duration, target_qps, req):
    """Fixed-rate open loop over the victim's path, recording status
    counts, 200-latencies, and EVERY X-PIO-Tenant header seen."""
    import itertools as it

    conns = [await asyncio.open_connection(host, port)
             for _ in range(n_conns)]
    t0 = time.perf_counter()
    slots = it.count()
    counts: dict = {}
    lat_ms: list = []
    tenants_seen: set = set()

    async def worker(conn):
        r, w = conn
        while True:
            t_sched = t0 + next(slots) / target_qps
            if t_sched - t0 >= duration or time.perf_counter() - t0 >= duration:
                return
            now = time.perf_counter()
            if t_sched > now:
                await asyncio.sleep(t_sched - now)
            status, headers, ms = await _post_hdrs(r, w, req)
            counts[status] = counts.get(status, 0) + 1
            if status == 200:
                lat_ms.append(ms)
            tenants_seen.add(headers.get("x-pio-tenant"))

    await asyncio.gather(*(worker(c) for c in conns))
    for _, w in conns:
        w.close()
    return counts, lat_ms, tenants_seen


def _http_with_headers(method: str, url: str, body=None, timeout=10.0):
    """(status, headers dict, parsed json) — the Retry-After forensics."""
    import urllib.error
    import urllib.request

    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return (resp.status, dict(resp.headers),
                    json.loads(resp.read() or b"null"))
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"null")


def test_multi_tenant_noisy_neighbor_contained(tmp_path):
    """ISSUE 20 tentpole acceptance, against a real subprocess: one
    multi-tenant query server hosts three tenants under a byte budget that
    provably cannot fit them all. A noisy tenant drives ~3× its quota
    while the victim runs steady:

    - the victim's goodput holds (≥ 0.95× its solo run) and its p99 stays
      bounded (≤ 1.5× solo, plus a small scheduler-noise floor);
    - the noisy tenant's excess is shed ORDERLY — only 429/503 with a
      Retry-After header, never a 5xx error or a cross-tenant answer;
    - attribution forensics: every victim answer carries
      ``X-PIO-Tenant: victim`` — no request is ever answered by another
      tenant's engine;
    - packing: first touch of the third tenant under the full budget
      evicts the LRU resident and cold-loads (both counted), and
      ``pio-tpu tenants`` renders the packing state.
    """
    store_cfg, variant_path = _train_classification(tmp_path)
    quota_qps = 30.0
    tenants = [
        {"tenant": "noisy", "engineVariant": variant_path,
         "quotaQps": quota_qps, "quotaBurst": quota_qps,
         "residentBytes": 1000},
        {"tenant": "victim", "engineVariant": variant_path,
         "residentBytes": 1000},
        {"tenant": "spare", "engineVariant": variant_path,
         "residentBytes": 1000},
    ]
    tenants_file = str(tmp_path / "tenants.json")
    with open(tenants_file, "w") as f:
        json.dump(tenants, f)
    qport = free_port()
    qs = ServerProc(
        ["deploy", "-v", variant_path, "--tenants", tenants_file,
         "--ip", "127.0.0.1", "--port", str(qport),
         "--query-timeout", str(QUERY_DEADLINE_S)],
        env={**store_cfg, "PIO_TENANT_HBM_BUDGET": "2000"})
    base = f"http://127.0.0.1:{qport}"
    body = {"features": [0.5, -0.2, 0.1]}
    try:
        qs.wait_ready(f"{base}/", timeout=180.0)
        # cold loads are off the hot path by design: pay them here, once,
        # per tenant the storm will touch (spare stays cold → lazy)
        for t in ("noisy", "victim"):
            status, hdrs, got = _http_with_headers(
                "POST", f"{base}/engines/{t}/queries.json", body,
                timeout=60.0)
            assert status == 200, (t, status, got)
            assert hdrs.get("X-PIO-Tenant") == t
        _, health = http_json("GET", f"{base}/health")
        assert health["deployment"]["multiTenant"] is True
        assert sorted(health["deployment"]["resident"]) == [
            "noisy", "victim"]

        victim_req = request_bytes("127.0.0.1", qport, _STORM_BODY,
                                   path="/engines/victim/queries.json")
        noisy_req = request_bytes("127.0.0.1", qport, _STORM_BODY,
                                  path="/engines/noisy/queries.json")

        # warm BOTH tenants' serving paths at real concurrency before any
        # measurement: micro-batch sizes vary under load, and each core
        # compiles its batch buckets on first use — a mid-storm compile
        # would masquerade as neighbor interference
        asyncio.run(closed_loop(
            "127.0.0.1", qport, 8, 1.0, lambda: noisy_req))
        cap_counts, _ = asyncio.run(closed_loop(
            "127.0.0.1", qport, 8, 2.0, lambda: victim_req))
        # victim's steady rate: well inside its solo capacity — headroom
        # the neighbor is NOT entitled to eat
        victim_rate = max(10.0, 0.35 * cap_counts.get(200, 0) / 2.0)

        def drive_noisy(offered_qps: float) -> subprocess.Popen:
            # the noisy driver runs in its OWN subprocess — a driver
            # thread here would pollute the victim's latency measurement
            # through client-side GIL contention
            return subprocess.Popen(
                [sys.executable, "-c",
                 "import sys; "
                 "from tests.fixtures.loadgen import tenant_main; "
                 "tenant_main(sys.argv[1:])",
                 "127.0.0.1", str(qport), "/engines/noisy/queries.json",
                 "3.0", str(offered_qps), "16", json.dumps(body)],
                cwd=REPO_ROOT, stdout=subprocess.PIPE, text=True)

        def measure(offered_qps: float):
            driver = drive_noisy(offered_qps)
            vic = asyncio.run(_victim_loop(
                "127.0.0.1", qport, 16, 3.0, victim_rate, victim_req))
            out, _ = driver.communicate(timeout=60)
            assert driver.returncode == 0
            res = json.loads(out)
            counts = {int(k) if k.isdigit() else k: v
                      for k, v in res["counts"].items()}
            return counts, vic

        # BASELINE vs STORM: the neighbor behaving (offered = 1× quota)
        # vs rogue (3×). The quota can only shed EXCESS — the
        # within-quota admitted load shares the host's CPU legitimately,
        # so the containment claim is "3× offered load looks exactly
        # like 1× to the victim", not "the victim cannot tell the
        # neighbor exists". One re-measure of the pair is allowed: on a
        # single-core host a one-off ~100ms scheduler stall in either
        # 3s window moves that window's p99 by itself, while a REAL
        # containment failure reproduces in every pair.
        for attempt in (1, 2):
            _, (solo_counts, solo_lat, solo_seen) = measure(quota_qps)
            solo_good = solo_counts.get(200, 0) / 3.0
            solo_p99 = pct(solo_lat, 0.99)
            assert solo_good > 0 and solo_seen == {"victim"}

            noisy_counts, (vic_counts, vic_lat, vic_seen) = (
                measure(3.0 * quota_qps))
            # the hard invariants hold on EVERY attempt: attribution
            # (each victim answer came from the victim's engine) and
            # orderly statuses — never a wrong answer, never a 5xx error
            assert vic_seen == {"victim"}
            assert set(_status_counts(vic_counts)) <= {200, 504}, \
                vic_counts

            # victim containment: goodput ratio ≥ 0.95, p99 ratio ≤ 1.5
            # (a few ms of floor damps scheduler noise on tiny p99s)
            vic_good = vic_counts.get(200, 0) / 3.0
            vic_p99 = pct(vic_lat, 0.99)
            bound = max(1.5 * solo_p99, solo_p99 + 25.0)
            if (vic_good >= 0.95 * solo_good and vic_p99 <= bound):
                break
        else:
            raise AssertionError(
                f"noisy neighbor NOT contained in 2 measurement pairs: "
                f"victim goodput {vic_good:.1f} qps (solo "
                f"{solo_good:.1f}, need ≥ 95%), p99 {vic_p99:.1f}ms "
                f"(solo {solo_p99:.1f}ms, bound {bound:.1f}ms)")

        # the noisy tenant got ONLY orderly answers: 200 within quota,
        # 429 (quota) / 503 (budget) / 504 (deadline) beyond it — and its
        # served rate stayed pinned near the quota, not at its offer
        assert set(_status_counts(noisy_counts)) <= {200, 429, 503, 504}, \
            noisy_counts
        assert noisy_counts.get(429, 0) > 0, "the quota never engaged"
        noisy_good = noisy_counts.get(200, 0) / 3.0
        assert noisy_good <= 1.6 * quota_qps, (
            f"noisy served {noisy_good:.1f} qps — quota {quota_qps} "
            "did not contain it")

        # Retry-After forensics on a live 429
        status, hdrs, got = (0, {}, None)
        for _ in range(80):
            status, hdrs, got = _http_with_headers(
                "POST", f"{base}/engines/noisy/queries.json", body)
            if status == 429:
                break
        assert status == 429, "could not re-exhaust the quota"
        assert int(hdrs["Retry-After"]) >= 1
        assert hdrs.get("X-PIO-Tenant") == "noisy"
        assert "over quota" in got["message"]

        # per-tenant ledger: throttles landed on noisy, none on victim
        _, snap = http_json("GET", f"{base}/tenants.json")
        assert snap["budgetBytes"] == 2000
        assert snap["tenants"]["noisy"]["throttled"] > 0
        assert snap["tenants"]["victim"]["throttled"] == 0

        # packing proof: three 1000-byte tenants under a 2000-byte budget
        # cannot all fit — touching the cold spare evicts the LRU and
        # cold-loads the spare (one query, one right answer, both counted)
        status, hdrs, got = _http_with_headers(
            "POST", f"{base}/engines/spare/queries.json", body,
            timeout=60.0)
        assert status == 200 and hdrs.get("X-PIO-Tenant") == "spare"
        _, snap = http_json("GET", f"{base}/tenants.json")
        assert snap["residentCount"] == 2
        assert snap["tenants"]["spare"]["resident"]
        assert snap["tenants"]["spare"]["coldLoads"] == 1
        evicted = [t for t, row in snap["tenants"].items()
                   if not row["resident"]]
        assert len(evicted) == 1 and evicted[0] in ("noisy", "victim")
        assert snap["tenants"][evicted[0]]["evictions"] == 1

        # the operator view renders the same packing state, and paints
        # the quota exhaustion red (exit 1 — red rows, not a crash)
        cli = subprocess.run(
            [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "tenants", "--json", "--interval", "0.5", base],
            capture_output=True, text=True, timeout=60)
        assert cli.returncode in (0, 1), cli.stdout + cli.stderr
        rows = {r["tenant"]: r for r in json.loads(cli.stdout)
                if "tenant" in r}
        assert set(rows) == {"noisy", "victim", "spare"}
        assert rows["spare"]["coldLoads"] >= 1
        assert rows["noisy"]["throttled"] > 0
        assert rows[evicted[0]]["evictions"] >= 1
        assert rows["spare"]["residentBytes"] == 1000
    finally:
        qs.stop()


# ---------------------------------------------------------------------------
# fleet chaos (ISSUE 6): rolling deploy halt-and-rollback through real
# replica processes, and a replica SIGKILL mid-storm absorbed by the router
# ---------------------------------------------------------------------------


def _train_second_instance(store_cfg: dict, variant_path: str) -> None:
    """Add another COMPLETED engine instance to the shared store so each
    replica's /reload has a NEW version to hot-swap to (ids differ — the
    rollback assertions are meaningful)."""
    import datetime as dt

    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data.storage import use_storage
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.templates.classification import (
        ClassificationEngine,
    )

    utc = dt.timezone.utc
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        with open(variant_path) as f:
            variant = json.load(f)
        engine = ClassificationEngine().apply()
        engine_params = engine.engine_params_from_variant(variant)
        instance = EngineInstance(
            id="", status="INIT", start_time=dt.datetime.now(utc),
            end_time=None, engine_id=variant["id"],
            engine_version=variant["version"],
            engine_variant=os.path.abspath(variant_path),
            engine_factory=variant["engineFactory"])
        run_train(engine, engine_params, instance, storage=storage,
                  ctx=MeshContext.create())
    finally:
        use_storage(prev)
        storage.close()


def _deploy_replica(store_cfg, variant_path, port, *extra) -> ServerProc:
    return ServerProc(
        ["deploy", "-v", variant_path, "--ip", "127.0.0.1",
         "--port", str(port), "--query-timeout", str(QUERY_DEADLINE_S),
         "--reload-probation", "120", "--server-access-key", "sk",
         *extra],
        env={**store_cfg,
             "PIO_ADMISSION_MAX_QUEUE": "128",
             "PIO_BROWNOUT_ENTER_SEC": "0.3",
             "PIO_BROWNOUT_EXIT_SEC": "1.0"})


def _router_proc(store_cfg, replica_urls, port, *extra) -> ServerProc:
    args = ["fleet", "route", "--ip", "127.0.0.1", "--port", str(port),
            "--health-interval", "0.3", "--probe-timeout", "1.0",
            "--deadline", "3.0", *extra]
    for url in replica_urls:
        args += ["--replica", url]
    return ServerProc(args, env=dict(store_cfg))


class _SteadyTraffic:
    """Background client posting queries through the router for the whole
    rollout, recording every status — the 'no client-visible 5xx from the
    deploy itself' witness."""

    def __init__(self, url: str):
        import threading

        self.url = url
        self.statuses: list[int] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                status, _ = http_json(
                    "POST", self.url,
                    {"features": [0.5, -0.2, 0.1]}, timeout=5.0)
                self.statuses.append(status)
            except Exception:  # noqa: BLE001 - a hang/refusal is the bug
                self.statuses.append(-1)
            time.sleep(0.05)

    def stop(self) -> list[int]:
        self._stop.set()
        self._thread.join(timeout=10.0)
        return self.statuses


def test_fleet_rollout_halts_rolls_back_and_serves_throughout(tmp_path):
    """ISSUE 6 acceptance: a `pio-tpu fleet rollout` where one replica's
    smoke gate trips must halt, roll the already-updated replicas back to
    last-good, and never surface a client-visible 5xx through the router."""
    store_cfg, variant_path = _train_classification(tmp_path)
    pa, pb, pr = free_port(), free_port(), free_port()
    url_a, url_b = (f"http://127.0.0.1:{pa}", f"http://127.0.0.1:{pb}")
    # replica A reloads clean; replica B's smoke gate can never pass (the
    # payload can't bind) — the fleet-wide halt fires AFTER A swapped
    ra = _deploy_replica(store_cfg, variant_path, pa)
    rb = _deploy_replica(store_cfg, variant_path, pb,
                         "--smoke-query", '{"bogus": "nope"}')
    router = traffic = None
    try:
        ra.wait_ready(f"{url_a}/", timeout=180.0)
        rb.wait_ready(f"{url_b}/", timeout=180.0)
        # train the NEW version only after the replicas booted on v1, so
        # /reload has a genuinely different instance to hot-swap to
        _train_second_instance(store_cfg, variant_path)
        _, ha = http_json("GET", f"{url_a}/health")
        _, hb = http_json("GET", f"{url_b}/health")
        a_v1 = ha["deployment"]["instanceId"]
        b_v1 = hb["deployment"]["instanceId"]
        router = _router_proc(store_cfg, [url_a, url_b], pr)
        router.wait_ready(f"http://127.0.0.1:{pr}/")
        traffic = _SteadyTraffic(f"http://127.0.0.1:{pr}/queries.json")
        # a couple of pre-rollout answers prove traffic is really flowing
        deadline = time.monotonic() + 20.0
        while len(traffic.statuses) < 3 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert traffic.statuses, "no traffic reached the router"

        rollout = subprocess.run(
            [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "fleet", "rollout", url_a, url_b, "--server-access-key", "sk",
             "--observe", "1.0", "--poll", "0.2", "--json"],
            capture_output=True, text=True, timeout=300)
        statuses = traffic.stop()
        traffic = None
        assert rollout.returncode == 1, rollout.stdout + rollout.stderr
        report = json.loads(rollout.stdout)
        assert report["haltedAt"] == url_b
        assert report["rolledBack"] == [url_a]
        assert report["updated"] == []

        # replica A: swapped to the new instance, then restored to v1
        _, ha = http_json("GET", f"{url_a}/health")
        dep_a = ha["deployment"]
        assert dep_a["instanceId"] == a_v1
        assert dep_a["lastReload"]["status"] == "rolled_back"
        assert dep_a["lastReload"]["rolledBackFrom"] != a_v1
        # replica B: the gate kept the new instance from ever serving
        _, hb = http_json("GET", f"{url_b}/health")
        dep_b = hb["deployment"]
        assert dep_b["instanceId"] == b_v1
        assert dep_b["lastReload"]["status"] == "rejected"

        # the deploy itself was invisible to clients: every request
        # through the router answered 200 (no 5xx, no hangs/refusals)
        assert statuses and set(statuses) == {200}, (
            f"client saw non-200s during rollout: "
            f"{sorted(set(statuses))} of {len(statuses)}")
        # and the fleet still serves after the halt
        status, body = http_json(
            "POST", f"http://127.0.0.1:{pr}/queries.json",
            {"features": [0.5, -0.2, 0.1]})
        assert status == 200 and "label" in body
    finally:
        if traffic is not None:
            traffic.stop()
        if router is not None:
            router.stop()
        ra.stop()
        rb.stop()


def test_fleet_router_absorbs_replica_kill9_mid_storm(tmp_path):
    """SIGKILL one of three replicas mid-storm at offered load well below
    the remaining capacity: the router retries/ejects and sheds NOTHING —
    zero non-orderly statuses, zero sheds (every request answers 200)."""
    import threading

    from tests.fixtures.loadgen import closed_loop, open_loop, request_bytes

    store_cfg, variant_path = _train_classification(tmp_path)
    ports = [free_port() for _ in range(3)]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    pr = free_port()
    replicas = [_deploy_replica(store_cfg, variant_path, p) for p in ports]
    router = None
    try:
        for url, proc in zip(urls, replicas):
            proc.wait_ready(f"{url}/", timeout=180.0)
        router = _router_proc(store_cfg, urls, pr,
                              "--eject-threshold", "2")
        router.wait_ready(f"http://127.0.0.1:{pr}/")

        req = request_bytes("127.0.0.1", pr, _STORM_BODY)
        # measured 3-replica capacity through the router (closed loop)
        cap_counts, _ = asyncio.run(
            closed_loop("127.0.0.1", pr, 8, 2.0, lambda: req))
        cap_qps = cap_counts.get(200, 0) / 2.0
        assert cap_qps > 0
        # offered load ~40% of 3-replica capacity — comfortably below the
        # 2-replica capacity that remains after the kill
        offered = max(5.0, 0.4 * cap_qps)
        killer = threading.Timer(1.5, replicas[0].kill9)
        killer.start()
        try:
            counts, _lat = asyncio.run(
                open_loop("127.0.0.1", pr, 16, 4.0, offered, lambda: req))
        finally:
            killer.cancel()
        statuses = _status_counts(counts)
        assert set(statuses) == {200}, (
            f"non-orderly/shed statuses below remaining capacity: "
            f"{statuses}")
        # the dead replica was ejected from rotation (probe cycle keeps
        # it out until it comes back)
        _, health = http_json("GET", f"http://127.0.0.1:{pr}/health")
        dead = next(r for r in health["replicas"]
                    if r["url"] == urls[0])
        assert not dead["healthy"]
        assert health["availableReplicas"] == 2
    finally:
        if router is not None:
            router.stop()
        for proc in replicas:
            proc.stop()


# ---------------------------------------------------------------------------
# streaming chaos (ISSUE 8): SIGKILL the updater between delta-ship and
# cursor-commit, and a replica mid-delta-apply — zero events lost, zero
# applied twice, serving never observes a half-applied table
# ---------------------------------------------------------------------------


def _train_recommendation_eventlog(tmp_path):
    """Train the recommendation template with EVENTDATA on the eventlog
    backend (the streaming change feed) and META/MODEL on sqlite; returns
    (store_cfg, variant_path, app_user_items). The test process keeps the
    single eventlog writer and appends live events mid-test; the updater
    and replicas only read."""
    import datetime as dt

    import numpy as np

    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import use_storage
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.templates.recommendation import (
        RecommendationEngine,
    )

    utc = dt.timezone.utc
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "store.db"),
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": str(tmp_path / "eventlog"),
        **{f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE": src
           for repo, src in (("METADATA", "SQ"), ("EVENTDATA", "EL"),
                             ("MODELDATA", "SQ"))},
    }
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        app_id = storage.get_meta_data_apps().insert(App(0, "stream-app"))
        events = storage.get_events()
        events.init(app_id)
        rng = np.random.default_rng(11)
        batch = [
            Event(event="rate", entity_type="user",
                  entity_id=f"u{int(rng.integers(0, 20))}",
                  target_entity_type="item",
                  target_entity_id=f"i{int(rng.integers(0, 30))}",
                  properties=DataMap(
                      {"rating": float(rng.integers(1, 6))}),
                  event_time=dt.datetime(2023, 1, 1, tzinfo=utc))
            for _ in range(240)
        ]
        events.insert_batch(batch, app_id)
        variant_path = str(tmp_path / "engine.json")
        variant = {
            "id": "stream", "version": "1",
            "engineFactory": ("incubator_predictionio_tpu.templates."
                              "recommendation.RecommendationEngine"),
            "datasource": {"params": {"appName": "stream-app"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 8, "numIterations": 8, "batchSize": 256}}],
        }
        with open(variant_path, "w") as f:
            json.dump(variant, f)
        engine = RecommendationEngine().apply()
        engine_params = engine.engine_params_from_variant(variant)
        instance = EngineInstance(
            id="", status="INIT", start_time=dt.datetime.now(utc),
            end_time=None, engine_id="stream", engine_version="1",
            engine_variant=os.path.abspath(variant_path),
            engine_factory=variant["engineFactory"])
        run_train(engine, engine_params, instance, storage=storage,
                  ctx=MeshContext.create())
    finally:
        use_storage(prev)
    return storage, store_cfg, variant_path, app_id


def _append_live_events(storage, app_id, tag, n=12):
    """Post-train events the streaming pipeline must fold (the test
    process is the single eventlog writer)."""
    import datetime as dt

    from incubator_predictionio_tpu.data import DataMap, Event

    utc = dt.timezone.utc
    storage.get_events().insert_batch([
        Event(event="rate", entity_type="user", entity_id=f"u{i % 20}",
              target_entity_type="item", target_entity_id=f"i{i % 30}",
              properties=DataMap({"rating": 5.0}),
              event_time=dt.datetime(2023, 6, 1, i % 20, tzinfo=utc))
        for i in range(n)
    ], app_id)


def _run_stream_once(store_cfg, variant_path, state_dir, replica_url,
                     fault=None, timeout=240):
    env = {**os.environ, **store_cfg, "JAX_PLATFORMS": "cpu",
           "PIO_NATIVE_HTTP": "0"}
    if fault:
        env["PIO_STREAM_FAULT"] = fault
    else:
        env.pop("PIO_STREAM_FAULT", None)
    from tests.fixtures.procs import REPO_ROOT

    return subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
         "stream", "-v", variant_path, "--app", "stream-app",
         "--state-dir", state_dir, "--replica", replica_url, "--once"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)


def _stream_health(base):
    _, health = http_json("GET", f"{base}/health")
    return (health["deployment"] or {}).get("streaming")


def test_streaming_updater_kill9_between_ship_and_commit(tmp_path):
    """ISSUE 8 acceptance: SIGKILL the updater after the delta shipped but
    before the cursor committed. The restarted updater re-folds the same
    range; the replica ends with the chain applied EXACTLY once and the
    cursor catches up — zero lost, zero double-applied."""
    storage, store_cfg, variant_path, app_id = \
        _train_recommendation_eventlog(tmp_path)
    qport = free_port()
    base = f"http://127.0.0.1:{qport}"
    qs = ServerProc(["deploy", "-v", variant_path, "--ip", "127.0.0.1",
                     "--port", str(qport)], env=store_cfg)
    try:
        qs.wait_ready(f"{base}/", timeout=180.0)
        state_dir = str(tmp_path / "stream-state")
        # run 0 establishes the crash-safe cursor at the log's current end
        # (the updater tails from where it starts, like production)
        r0 = _run_stream_once(store_cfg, variant_path, state_dir, base)
        assert r0.returncode == 0, r0.stdout + r0.stderr
        _append_live_events(storage, app_id, "a")
        # run 1: dies by SIGKILL right after shipping, before the commit
        r1 = _run_stream_once(store_cfg, variant_path, state_dir, base,
                              fault="kill:after_ship")
        assert r1.returncode == -9, (r1.returncode, r1.stdout, r1.stderr)
        s1 = _stream_health(base)
        assert s1 is not None and s1["applied"] == 1, s1
        applied_seq = s1["lastDeltaSeq"]
        # run 2: clean restart over the same state dir — the re-fold
        # produces the identical range; the replica must NOT apply twice
        r2 = _run_stream_once(store_cfg, variant_path, state_dir, base)
        assert r2.returncode == 0, r2.stdout + r2.stderr
        out = json.loads(r2.stdout.strip().splitlines()[-1])
        assert out["status"] == "applied"
        assert out["toSeq"] == applied_seq
        s2 = _stream_health(base)
        assert s2["applied"] == 1, f"delta applied twice: {s2}"
        assert s2["lastDeltaSeq"] == applied_seq
        # freshness is now reported
        assert s2["stalenessSeconds"] is not None
        # run 3: nothing new — idle, still exactly once
        r3 = _run_stream_once(store_cfg, variant_path, state_dir, base)
        out3 = json.loads(r3.stdout.strip().splitlines()[-1])
        assert out3["status"] in ("idle", "waiting")
        assert _stream_health(base)["applied"] == 1
        # serving stayed healthy throughout
        status, body = http_json(
            "POST", f"{base}/queries.json", {"user": "u1", "num": 3})
        assert status == 200 and body["itemScores"]
    finally:
        qs.stop()
        storage.close()


def test_streaming_replica_kill9_mid_delta_apply_resyncs(tmp_path):
    """SIGKILL the replica in the middle of a delta apply (tables built,
    swap not reached). After restart it serves the BASE model — never a
    half-applied table — and the updater's resync replays the archived
    chain so nothing is lost and nothing applies twice."""
    storage, store_cfg, variant_path, app_id = \
        _train_recommendation_eventlog(tmp_path)
    qport = free_port()
    base = f"http://127.0.0.1:{qport}"
    qs = ServerProc(["deploy", "-v", variant_path, "--ip", "127.0.0.1",
                     "--port", str(qport)],
                    env={**store_cfg,
                         "PIO_DELTA_FAULT": "kill:mid_apply"})
    try:
        qs.wait_ready(f"{base}/", timeout=180.0)
        state_dir = str(tmp_path / "stream-state")
        r0 = _run_stream_once(store_cfg, variant_path, state_dir, base)
        assert r0.returncode == 0, r0.stdout + r0.stderr
        _append_live_events(storage, app_id, "b")
        # the ship kills the replica mid-apply; the updater still commits
        # (the archive is the source of truth; resync delivers later)
        r1 = _run_stream_once(store_cfg, variant_path, state_dir, base)
        assert r1.returncode == 0, r1.stdout + r1.stderr
        out = json.loads(r1.stdout.strip().splitlines()[-1])
        assert out["status"] == "applied"
        assert "error" in out["ships"][0]
        qs.proc.wait(timeout=30)
        # restart WITHOUT the fault: base model, nothing half-applied
        qs2 = ServerProc(["deploy", "-v", variant_path, "--ip", "127.0.0.1",
                          "--port", str(qport)], env=store_cfg)
        try:
            qs2.wait_ready(f"{base}/", timeout=180.0)
            assert _stream_health(base) is None  # clean base, no partial
            status, _ = http_json(
                "POST", f"{base}/queries.json", {"user": "u1", "num": 3})
            assert status == 200
            # idle round resyncs the archived chain into the replica
            r2 = _run_stream_once(store_cfg, variant_path, state_dir, base)
            assert r2.returncode == 0, r2.stdout + r2.stderr
            s = _stream_health(base)
            assert s is not None and s["applied"] == 1
            assert s["lastDeltaSeq"] == out["toSeq"]
            status, body = http_json(
                "POST", f"{base}/queries.json", {"user": "u1", "num": 3})
            assert status == 200 and body["itemScores"]
        finally:
            qs2.stop()
    finally:
        qs.stop()
        storage.close()


# ---------------------------------------------------------------------------
# storage replication chaos (ISSUE 9): SIGKILL the primary mid-ingest →
# epoch-fenced failover with zero acked loss; a stale restarted primary
# gets every write fenced; a flipped byte is scrubbed back to bit-identity
# ---------------------------------------------------------------------------


def _repl_store_env(tmp_path, name) -> dict:
    return {
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": str(tmp_path / f"{name}-log"),
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / f"{name}.db"),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
    }


def _start_storage(tmp_path, name, port, role, peers,
                   sync="quorum") -> ServerProc:
    args = ["storageserver", "--ip", "127.0.0.1", "--port", str(port),
            "--repl-role", role, "--repl-sync", sync]
    for p in peers:
        args += ["--repl-peer", p]
    proc = ServerProc(args, env=_repl_store_env(tmp_path, name))
    proc.wait_ready(f"http://127.0.0.1:{port}/")
    return proc


def _repl_es_env(tmp_path, urls: list) -> dict:
    return {
        "PIO_STORAGE_SOURCES_R_TYPE": "remote",
        "PIO_STORAGE_SOURCES_R_URLS": ",".join(urls),
        "PIO_STORAGE_SOURCES_R_TIMEOUT": "3",
        "PIO_STORAGE_SOURCES_R_RETRY_MAX_ATTEMPTS": "1",
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "es-meta.db"),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
        "PIO_EVENT_WAL_DIR": str(tmp_path / "wal"),
        "PIO_EVENTSERVER_AUTH_TTL": "600",
        "PIO_EVENTSERVER_BREAKER_THRESHOLD": "2",
        "PIO_EVENTSERVER_BREAKER_RESET": "0.3",
        "PIO_RESILIENCE_BREAKER_RESET": "0.3",
        "PIO_DRAIN_DEADLINE": "20",
    }


def _seed_es_meta(tmp_path):
    """The event server's auth metadata lives in ITS OWN sqlite (only
    EVENTDATA is the replicated remote source)."""
    meta = Storage({
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "es-meta.db"),
    })
    app_id = meta.get_meta_data_apps().insert(App(0, "repl-chaos"))
    key = meta.get_meta_data_access_keys().insert(AccessKey("", app_id, ()))
    meta.close()
    return app_id, key


def _find_ids_via(url: str, app_id: int) -> list:
    from incubator_predictionio_tpu.data.storage.remote import (
        RemoteStorageClient,
    )

    client = RemoteStorageClient({"URL": url, "TIMEOUT": "10"})
    return [e.event_id for e in client.events().find(app_id)]


def test_storage_failover_kill9_primary_zero_acked_loss(tmp_path):
    """ISSUE 9 acceptance (a): SIGKILL the primary storage server
    mid-ingest under load (quorum replication) → the follower is promoted
    with a bumped epoch, the event server's multi-endpoint client fails
    over, and every acked event is stored exactly once (verified by id
    set) — the outage window's acks ride the WAL spill, never a lie."""
    import threading

    app_id, key = _seed_es_meta(tmp_path)
    pport, fport, eport = free_port(), free_port(), free_port()
    purl, furl = f"http://127.0.0.1:{pport}", f"http://127.0.0.1:{fport}"
    follower = _start_storage(tmp_path, "f", fport, "follower", [purl])
    primary = _start_storage(tmp_path, "p", pport, "primary", [furl])
    es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                     "--port", str(eport)],
                    env=_repl_es_env(tmp_path, [purl, furl]))
    acked: list = []
    stop = threading.Event()

    def ingest_loop():
        i = 0
        while not stop.is_set():
            try:
                status, body = http_json(
                    "POST",
                    f"http://127.0.0.1:{eport}/events.json?accessKey={key}",
                    dict(EVENT, entityId=f"load-{i}"), timeout=10.0)
                if status == 201:
                    acked.append(body["eventId"])
            except Exception:  # noqa: BLE001 - ambiguous: not acked
                pass
            i += 1
            time.sleep(0.02)

    loader = threading.Thread(target=ingest_loop, daemon=True)
    try:
        es.wait_ready(f"http://127.0.0.1:{eport}/")
        # phase 1 — replicated steady state
        for i in range(6):
            acked.append(_post_acked(eport, key, f"pre-{i}"))
        loader.start()
        time.sleep(0.5)
        # phase 2 — SIGKILL the primary mid-ingest, promote the follower
        # (the replica set shrinks to the survivor until a scrub rejoin)
        primary.kill9()
        st, body = http_json("POST", f"{furl}/repl/promote",
                             {"peers": []}, timeout=10.0)
        assert st == 200 and body["epoch"] == 2, (st, body)
        # phase 3 — ingest keeps flowing; the spill drains onto the
        # promoted primary and direct acks succeed again
        time.sleep(1.5)
        stop.set()
        loader.join(timeout=10.0)
        acked.append(_post_acked(eport, key, "post-failover"))
        _wait_health(eport, lambda h: h["spillQueueDepth"] == 0
                     and h["status"] == "ok")
        # epoch bumped, follower is the primary now
        _, fh = http_json("GET", f"{furl}/health")
        assert fh["replication"]["role"] == "primary"
        assert fh["replication"]["epoch"] == 2
        # exactly-once by id set, read from the promoted primary: every
        # acked event present, nothing served twice
        ids = _find_ids_via(furl, app_id)
        assert len(ids) == len(set(ids)), "duplicate ids served"
        missing = set(acked) - set(ids)
        assert not missing, f"ACKED EVENTS LOST: {missing}"
    finally:
        stop.set()
        es.stop()
        primary.stop()
        follower.stop()


def test_stale_primary_restart_every_write_fenced(tmp_path):
    """ISSUE 9 acceptance (b): the demoted primary restarted with its
    stale persisted epoch announces at boot, learns it was deposed, and
    every write aimed at it is rejected 409 with
    pio_repl_fenced_writes_total incremented; `pio-tpu health` turns
    red on the fenced store."""
    pport, fport = free_port(), free_port()
    purl, furl = f"http://127.0.0.1:{pport}", f"http://127.0.0.1:{fport}"
    follower = _start_storage(tmp_path, "f", fport, "follower", [purl],
                              sync="async")
    primary = _start_storage(tmp_path, "p", pport, "primary", [furl],
                             sync="async")
    try:
        # some replicated data, then the failover
        from incubator_predictionio_tpu.data.event import Event
        from incubator_predictionio_tpu.data.storage.remote import (
            RemoteStorageClient,
        )

        client = RemoteStorageClient({"URL": purl, "TIMEOUT": "10"})
        client.events().init(1)
        client.events().insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{i}",
                  target_entity_type="item", target_entity_id="i1")
            for i in range(4)], 1)
        primary.kill9()
        st, body = http_json("POST", f"{furl}/repl/promote",
                             {"peers": [purl]}, timeout=10.0)
        assert st == 200 and body["epoch"] == 2
        # restart the deposed primary with its STALE persisted epoch and
        # its original self-image (role=primary)
        primary = _start_storage(tmp_path, "p", pport, "primary", [furl],
                                 sync="async")
        # its boot announce met epoch 2 → fenced before serving a write
        fenced_statuses = []
        for i in range(3):
            st, body = http_json(
                "POST", f"{purl}/rpc/events/insert",
                {"event": dict(EVENT, entityId=f"stale-{i}"),
                 "app_id": 1}, timeout=10.0)
            fenced_statuses.append(st)
        assert fenced_statuses == [409, 409, 409], fenced_statuses
        _, h = http_json("GET", f"{purl}/health")
        repl = h["replication"]
        assert repl["fenced"] is True
        assert repl["fencedWrites"] >= 3
        assert repl["epoch"] == 2  # adopted the deposing epoch
        # the fleet probe goes red on a fenced store (satellite)
        gate = subprocess.run(
            [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "health", purl], capture_output=True, text=True, timeout=30)
        assert gate.returncode == 1, gate.stdout + gate.stderr
        assert "FENCED" in gate.stdout
        # reads still serve from the fenced replica (bounded staleness)
        st, _ = http_json("POST", f"{purl}/rpc/events/get",
                          {"event_id": "nope", "app_id": 1}, timeout=10.0)
        assert st == 200
    finally:
        primary.stop()
        follower.stop()


def test_store_scrub_detects_and_repairs_flipped_byte(tmp_path):
    """ISSUE 9 acceptance (c): a single flipped byte injected into a
    follower segment is detected by `pio-tpu store scrub` and repaired
    to bit-identical digests."""
    pport, fport = free_port(), free_port()
    purl, furl = f"http://127.0.0.1:{pport}", f"http://127.0.0.1:{fport}"
    follower = _start_storage(tmp_path, "f", fport, "follower", [purl],
                              sync="async")
    primary = _start_storage(tmp_path, "p", pport, "primary", [furl],
                             sync="async")
    try:
        from incubator_predictionio_tpu.data.event import Event
        from incubator_predictionio_tpu.data.storage.remote import (
            RemoteStorageClient,
        )

        client = RemoteStorageClient({"URL": purl, "TIMEOUT": "10"})
        ev = client.events()
        ev.init(1)
        ev.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{i}",
                  target_entity_type="item", target_entity_id=f"i{i % 5}")
            for i in range(50)], 1)
        p_log = os.path.join(str(tmp_path / "p-log"), "app_1.piolog")
        f_log = os.path.join(str(tmp_path / "f-log"), "app_1.piolog")
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if (os.path.exists(f_log)
                    and os.path.getsize(f_log) == os.path.getsize(p_log)):
                break
            time.sleep(0.05)
        with open(p_log, "rb") as f:
            authoritative = f.read()
        assert open(f_log, "rb").read() == authoritative
        # silent bitrot on the follower copy
        blob = bytearray(authoritative)
        blob[len(blob) // 2] ^= 0x20
        with open(f_log, "wb") as f:
            f.write(blob)
        scrub = subprocess.run(
            [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "store", "scrub", purl, furl, "--segment-bytes", "4096",
             "--json"], capture_output=True, text=True, timeout=60)
        assert scrub.returncode == 0, scrub.stdout + scrub.stderr
        report = json.loads(scrub.stdout)[furl]
        assert report["divergentSegments"] >= 1
        assert report["repairedBytes"] > 0
        assert report["clean"] is True
        assert open(f_log, "rb").read() == authoritative
        # the repaired replica serves correct reads again
        got = _find_ids_via(furl, 1)
        assert len(got) == 50
        # second scrub pass: nothing left to repair
        scrub2 = subprocess.run(
            [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "store", "scrub", purl, furl, "--segment-bytes", "4096",
             "--json"], capture_output=True, text=True, timeout=60)
        assert scrub2.returncode == 0
        assert json.loads(scrub2.stdout)[furl]["divergentSegments"] == 0
    finally:
        primary.stop()
        follower.stop()


def test_event_server_sigterm_drains_and_exits_clean(tmp_path):
    """Graceful drain end-to-end: SIGTERM → new ingest 503s, the spilled
    acks flush to the recovered store, the process exits 0 within the
    deadline."""
    storage, app_id, key = _storage(tmp_path)
    sport = free_port()
    eport = free_port()
    env = _es_env(sport, str(tmp_path / "wal"))
    sserver = ThreadedStorageServer(
        storage, StorageServerConfig(ip="127.0.0.1", port=sport))
    es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                     "--port", str(eport)], env=env)
    acked = []
    try:
        es.wait_ready(f"http://127.0.0.1:{eport}/")
        acked.append(_post_acked(eport, key, "prime"))  # warm the auth cache
        sserver.close()
        for i in range(5):
            acked.append(_post_acked(eport, key, f"g-{i}"))
        sserver = ThreadedStorageServer(
            storage, StorageServerConfig(ip="127.0.0.1", port=sport))
        es.sigterm()
        rc = es.wait_exit(timeout=45.0)
        assert rc == 0, es.output()
    finally:
        es.stop()
        sserver.close()
    ids = {e.event_id for e in storage.get_events().find(app_id)}
    assert set(acked) <= ids
    storage.close()


# ---------------------------------------------------------------------------
# continuous-training control plane chaos (ISSUE 12): SIGKILL the training
# worker mid-epoch (reclaim + checkpoint resume + exactly one deploy) and
# between the eval-gate pass and the deploy (reclaimed job deploys once)
# ---------------------------------------------------------------------------


def _train_jobs_recommendation(tmp_path, n_events=6000, iterations=10):
    """Seed rating events + train a base instance of the recommendation
    template (checkpointing ON) into sqlite, returning (store_cfg,
    variant_path, ckpt_dir). The base instance is the incumbent the gate
    scores against and the engine the deploy subprocess serves first."""
    import datetime as dt

    import numpy as np

    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import use_storage
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.templates.recommendation import (
        RecommendationEngine,
    )

    utc = dt.timezone.utc
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "store.db"),
    }
    ckpt_dir = str(tmp_path / "ckpt")
    variant_path = str(tmp_path / "engine.json")
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
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        app_id = storage.get_meta_data_apps().insert(App(0, "ct-app"))
        events = storage.get_events()
        events.init(app_id)
        rng = np.random.default_rng(7)
        batch = [
            Event(event="rate", entity_type="user",
                  entity_id=f"u{rng.integers(0, 400)}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, 300)}",
                  properties=DataMap(
                      {"rating": float(1 + 4 * rng.random())}),
                  event_time=dt.datetime(2022, 1, 1, tzinfo=utc))
            for _ in range(n_events)
        ]
        events.insert_batch(batch, app_id)
        with open(variant_path) as f:
            variant = json.load(f)
        engine = RecommendationEngine().apply()
        engine_params = engine.engine_params_from_variant(variant)
        instance = EngineInstance(
            id="", status="INIT", start_time=dt.datetime.now(utc),
            end_time=None, engine_id="ct", engine_version="1",
            engine_variant=os.path.abspath(variant_path),
            engine_factory=variant["engineFactory"])
        run_train(engine, engine_params, instance, storage=storage,
                  ctx=MeshContext.create())
    finally:
        use_storage(prev)
        storage.close()
    # the base train leaves completed-run checkpoints; the orchestrated
    # job must start from a CLEAN dir so the mid-epoch kill window is
    # detected from ITS fresh steps, not the stale ones
    import shutil

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    return store_cfg, variant_path, ckpt_dir


def _worker_proc(store_cfg, lease_sec=2.0, extra_env=None) -> ServerProc:
    return ServerProc(
        ["jobs", "worker", "--poll", "0.2"],
        env={**store_cfg,
             "PIO_JOBS_LEASE_SEC": str(lease_sec),
             **(extra_env or {})})


def _reload_200_count(base_url: str) -> int:
    """Successful POST /reload count from the query server's own
    /metrics — the 'exactly ONE deploy reached serving' oracle."""
    import urllib.request

    from incubator_predictionio_tpu.obs.metrics import parse_prometheus_text

    with urllib.request.urlopen(f"{base_url}/metrics", timeout=10) as resp:
        fams = parse_prometheus_text(resp.read().decode())
    fam = fams.get("pio_http_requests_total")
    total = 0
    for _, labels, value in (fam["samples"] if fam else ()):
        if "reload" in labels.get("route", "") \
                and labels.get("status") == "200":
            total += int(value)
    return total


def _wait_job(jobs_store, job_id, statuses, timeout=420.0, procs=()):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        j = jobs_store.get(job_id)
        if j is not None and j.status in statuses:
            return j
        time.sleep(0.25)
    outs = "\n---\n".join(p.output()[-3000:] for p in procs)
    raise TimeoutError(
        f"job {job_id} never reached {statuses} "
        f"(now {jobs_store.get(job_id)});\nworker output:\n{outs}")


def test_jobs_worker_kill9_mid_epoch_resumes_and_deploys_once(tmp_path):
    """ISSUE 12 chaos proof #1: SIGKILL the training worker mid-epoch.
    The job is reclaimed under a new fence, the second worker RESUMES
    from the epoch checkpoint (strictly fewer epochs than from scratch,
    pinned via the resume log line), and exactly ONE deploy reaches
    serving."""
    store_cfg, variant_path, ckpt_dir = _train_jobs_recommendation(
        tmp_path, n_events=6000, iterations=16)
    qport = free_port()
    base = f"http://127.0.0.1:{qport}"
    qs = ServerProc(
        ["deploy", "-v", variant_path, "--ip", "127.0.0.1",
         "--port", str(qport)], env=dict(store_cfg))
    storage = Storage(store_cfg)
    w1 = w2 = None
    try:
        qs.wait_ready(f"{base}/", timeout=180.0)
        _, h0 = http_json("GET", f"{base}/health")
        incumbent = h0["deployment"]["instanceId"]

        from incubator_predictionio_tpu.jobs import Orchestrator

        orch = Orchestrator(storage.get_meta_data_jobs())
        job = orch.submit("train", {
            "engine_variant": os.path.abspath(variant_path),
            "server_url": base})
        w1 = _worker_proc(store_cfg, lease_sec=2.0)
        # wait until training is genuinely mid-run: the job is RUNNING and
        # at least one epoch checkpoint landed (so the resume is real)
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            j = storage.get_meta_data_jobs().get(job.id)
            steps = [d for d in (os.listdir(ckpt_dir)
                                 if os.path.isdir(ckpt_dir) else [])
                     if d.isdigit()]
            if j.status == "RUNNING" and steps \
                    and max(int(s) for s in steps) >= 2:
                break
            if j.status in ("COMPLETED", "FAILED"):
                raise AssertionError(
                    f"train finished before the kill window: {j.status}\n"
                    + w1.output()[-2000:])
            time.sleep(0.1)
        else:
            raise TimeoutError("no mid-epoch checkpoint appeared\n"
                               + w1.output()[-2000:])
        w1.kill9()   # mid-epoch, mid-lease

        # the lease lapses; a fresh worker reclaims under a bumped fence
        w2 = _worker_proc(store_cfg, lease_sec=30.0)
        done = _wait_job(storage.get_meta_data_jobs(), job.id,
                         ("COMPLETED", "FAILED", "REFUSED"),
                         procs=(w2,))
        assert done.status == "COMPLETED", (done, w2.output()[-3000:])
        assert done.fence == 2 and done.attempt == 2

        # resume proof: the reclaiming worker continued from a checkpoint
        out2 = w2.output()
        assert "resuming from epoch" in out2, out2[-3000:]
        resumed_epoch = int(
            out2.split("resuming from epoch", 1)[1].split()[0])
        assert resumed_epoch >= 1   # strictly fewer epochs than scratch

        # exactly ONE deploy reached serving, and it serves the new
        # instance the job trained
        assert _reload_200_count(base) == 1
        _, h1 = http_json("GET", f"{base}/health")
        assert h1["deployment"]["instanceId"] == \
            done.result["instanceId"] != incumbent
    finally:
        for p in (w1, w2, qs):
            if p is not None:
                p.stop()
        storage.close()


def test_jobs_worker_kill9_between_gate_pass_and_deploy(tmp_path):
    """ISSUE 12 chaos proof #2 (the satellite's second case): the worker
    dies AFTER the eval gate passed but BEFORE the deploy. The reclaimed
    job re-runs on a fresh worker and serving sees exactly one reload —
    never zero (lost deploy) and never two (double deploy)."""
    store_cfg, variant_path, _ = _train_jobs_recommendation(
        tmp_path, n_events=2500, iterations=3)
    qport = free_port()
    base = f"http://127.0.0.1:{qport}"
    qs = ServerProc(
        ["deploy", "-v", variant_path, "--ip", "127.0.0.1",
         "--port", str(qport)], env=dict(store_cfg))
    storage = Storage(store_cfg)
    w1 = w2 = None
    try:
        qs.wait_ready(f"{base}/", timeout=180.0)
        from incubator_predictionio_tpu.jobs import Orchestrator

        orch = Orchestrator(storage.get_meta_data_jobs())
        job = orch.submit("train", {
            "engine_variant": os.path.abspath(variant_path),
            "server_url": base})
        w1 = _worker_proc(store_cfg, lease_sec=2.0,
                          extra_env={"PIO_JOBS_FAULT": "kill:before_deploy"})
        # the fault point SIGKILLs w1 right before its /reload: wait for
        # the process to die, with the job still RUNNING and undeployed
        deadline = time.monotonic() + 300.0
        while w1.proc.poll() is None and time.monotonic() < deadline:
            time.sleep(0.2)
        assert w1.proc.poll() is not None, "fault point never tripped"
        assert _reload_200_count(base) == 0
        j = storage.get_meta_data_jobs().get(job.id)
        assert j.status == "RUNNING"   # died holding the lease

        w2 = _worker_proc(store_cfg, lease_sec=30.0)
        done = _wait_job(storage.get_meta_data_jobs(), job.id,
                         ("COMPLETED", "FAILED", "REFUSED"),
                         procs=(w2,))
        assert done.status == "COMPLETED", (done, w2.output()[-3000:])
        assert done.fence == 2
        assert _reload_200_count(base) == 1   # exactly one deploy landed
        _, h1 = http_json("GET", f"{base}/health")
        assert h1["deployment"]["instanceId"] == done.result["instanceId"]
    finally:
        for p in (w1, w2, qs):
            if p is not None:
                p.stop()
        storage.close()


def test_dr_backup_restore_after_data_dir_loss(tmp_path):
    """ISSUE 13 chaos proof: a real event-server subprocess is SIGKILLed
    mid-ingest, its data dir (eventlog + WAL + metadata) is rm -rf'd, a
    backup taken IN FLIGHT restores it, and the restarted server serves
    with exactly-once ack parity by id set (the PR 9 forensic pattern):
    every event acked before the backup is stored exactly once, the only
    losses are provably from the post-backup window (RPO = backup cadence
    + WAL tail), and new ingest lands on the restored log."""
    import shutil

    from incubator_predictionio_tpu.backup import (
        BackupSource,
        RestoreTargets,
        create_backup,
        restore_backup,
    )
    from incubator_predictionio_tpu.native import format as fmt

    elog_dir = str(tmp_path / "live-elog")
    wal_dir = str(tmp_path / "wal")
    meta_db = str(tmp_path / "meta.db")
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
    seed = Storage({
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": meta_db,
    })
    app_id = seed.get_meta_data_apps().insert(App(0, "dr-chaos"))
    key = seed.get_meta_data_access_keys().insert(AccessKey("", app_id, ()))
    seed.close()

    eport = free_port()
    base = f"http://127.0.0.1:{eport}"
    es = ServerProc(["eventserver", "--ip", "127.0.0.1",
                     "--port", str(eport)], env=env)
    es2 = None
    try:
        es.wait_ready(f"{base}/")
        # first insert pays the server's one-time lazy init (native-lib
        # probe, several seconds on this box): give it its own budget so
        # the steady-state acks below keep the short default timeout
        status, body = http_json(
            "POST", f"{base}/events.json?accessKey={key}",
            dict(EVENT, entityId="pre-warm"), timeout=60.0)
        assert status == 201, (status, body)
        pre_backup = [body["eventId"]]
        pre_backup += [_post_acked(eport, key, f"pre-{i}")
                       for i in range(40)]
        # backup taken while the server is live and mid-ingest — the
        # create path is read-only file access from THIS process, the
        # real cross-process topology a cron backup runs in
        bdir = str(tmp_path / "backups")
        meta_storage = Storage({
            "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQ_PATH": meta_db,
        })
        rep = create_backup(bdir, BackupSource(
            eventlog_dir=elog_dir, wal_dir=wal_dir,
            storage=meta_storage))
        meta_storage.close()
        assert rep["verify"]["clean"], rep["verify"]["errors"]
        # post-backup acks: the honest RPO window — whatever of these the
        # disaster eats must be provably FROM this window, nothing else
        post_backup = [_post_acked(eport, key, f"post-{i}")
                       for i in range(20)]
        es.kill9()

        # the disaster: the whole data surface is gone
        shutil.rmtree(elog_dir)
        shutil.rmtree(wal_dir, ignore_errors=True)
        os.remove(meta_db)

        # the restore storage must carry the FULL repository config: the
        # WAL tail has to replay into the restored EVENTLOG, not into
        # whatever EVENTDATA a bare sqlite source would default to
        restore_storage = Storage(env)
        rr = restore_backup(bdir, RestoreTargets(
            eventlog_dir=elog_dir, wal_dir=wal_dir),
            storage=restore_storage, replay_wal=True)
        restore_storage.close()
        assert rr["filesRestored"] >= 1

        # restart on the restored dirs: startup replays any remaining WAL
        # tail; new ingest must land beside the restored history
        es2 = ServerProc(["eventserver", "--ip", "127.0.0.1",
                          "--port", str(eport)], env=env)
        es2.wait_ready(f"{base}/")
        status, body = http_json(
            "POST", f"{base}/events.json?accessKey={key}",
            dict(EVENT, entityId="probe-after-restore"), timeout=60.0)
        assert status == 201, (status, body)
        probe = body["eventId"]
        es2.sigterm()
        assert es2.wait_exit() == 0
    finally:
        es.stop()
        if es2 is not None:
            es2.stop()

    # forensics by id set on the restored log itself
    with open(os.path.join(elog_dir, "app_1.piolog"), "rb") as f:
        buf = f.read()
    strings, live, _ = fmt.read_log(buf)
    stored_counts: dict = {}
    for off, kind, payload in fmt.iter_records(buf):
        if kind != fmt.KIND_EVENT:
            continue
        event_id, _ = fmt.decode_event_payload(payload, strings)
        stored_counts[event_id] = stored_counts.get(event_id, 0) + 1
    stored = set(stored_counts)
    dup = {eid: n for eid, n in stored_counts.items() if n > 1}
    assert dup == {}, f"events stored more than once: {dup}"
    lost_pre = set(pre_backup) - stored
    assert lost_pre == set(), (
        f"acked-before-backup events lost: {sorted(lost_pre)[:8]} — "
        f"backup cut {rep['cuts']}")
    lost_overall = (set(pre_backup) | set(post_backup)) - stored
    assert lost_overall <= set(post_backup), (
        "a loss outside the post-backup window slipped through")
    assert probe in stored


# ---------------------------------------------------------------------------
# trace-plane chaos (ISSUE 14): one query's spans shredded across router,
# replica, and storage-server PROCESSES assemble from the durable spool into
# a single tree; a SIGKILLed replica's fragment still assembles with the
# error span present
# ---------------------------------------------------------------------------

_TRACE_FACTORY = "tests.fixtures.trace_engine.TraceClassificationEngine"


def _remote_store_env(storage_port: int) -> dict:
    name = "R"
    return {
        f"PIO_STORAGE_SOURCES_{name}_TYPE": "remote",
        f"PIO_STORAGE_SOURCES_{name}_URL": f"http://127.0.0.1:{storage_port}",
        f"PIO_STORAGE_SOURCES_{name}_TIMEOUT": "5",
        f"PIO_STORAGE_SOURCES_{name}_RETRY_MAX_ATTEMPTS": "1",
        **{f"PIO_STORAGE_REPOSITORIES_{repo}_{k}": name
           for repo in ("METADATA", "EVENTDATA", "MODELDATA")
           for k in ("NAME", "SOURCE")},
    }


def _post_traced(url: str, body: dict, timeout=30.0):
    """POST returning (status, parsed_body, trace_id) — the router echoes
    X-PIO-Trace on success AND error paths."""
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return (resp.status, json.loads(resp.read() or b"null"),
                    resp.headers.get("X-PIO-Trace"))
    except urllib.error.HTTPError as e:
        payload = e.read()
        try:
            parsed = json.loads(payload or b"null")
        except ValueError:
            parsed = {"raw": payload.decode(errors="replace")}
        return e.code, parsed, e.headers.get("X-PIO-Trace")


def _assemble_via_cli(spool_dir: str, trace_id: str) -> dict:
    """The acceptance path: `pio-tpu trace show <id>` over the spool."""
    out = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
         "trace", "show", trace_id, "--spool", spool_dir, "--json"],
        capture_output=True, text=True, timeout=60,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout)


def test_trace_plane_assembles_one_query_across_three_processes(tmp_path):
    """ISSUE 14 acceptance: one query driven through router → replica →
    storage assembles via `pio-tpu trace show` into a single tree with
    spans from ≥ 3 distinct processes, correct parent/child edges, and
    complete: true."""
    store_cfg, variant_path = _train_classification(
        tmp_path, factory=_TRACE_FACTORY)
    spool_dir = str(tmp_path / "spool")
    trace_env = {"PIO_TRACE_SPOOL_DIR": spool_dir}
    sport, qport, rport = free_port(), free_port(), free_port()
    store = replica = router = None
    try:
        store = ServerProc(
            ["storageserver", "--ip", "127.0.0.1", "--port", str(sport)],
            env={**store_cfg, **trace_env})
        store.wait_ready(f"http://127.0.0.1:{sport}/", timeout=60.0)
        replica = ServerProc(
            ["deploy", "-v", variant_path, "--ip", "127.0.0.1",
             "--port", str(qport), "--query-timeout", "10"],
            env={**_remote_store_env(sport), **trace_env})
        replica.wait_ready(f"http://127.0.0.1:{qport}/", timeout=180.0)
        router = ServerProc(
            ["fleet", "route", "--ip", "127.0.0.1", "--port", str(rport),
             "--replica", f"http://127.0.0.1:{qport}",
             "--health-interval", "0.5"],
            env=dict(trace_env))
        router.wait_ready(f"http://127.0.0.1:{rport}/")

        status, body, trace_id = _post_traced(
            f"http://127.0.0.1:{rport}/queries.json",
            {"features": [0.5, -0.2, 0.1]})
        assert status == 200, (status, body)
        assert trace_id, "router did not echo X-PIO-Trace"

        tree = _assemble_via_cli(spool_dir, trace_id)
        assert tree["traceId"] == trace_id
        # spans from >= 3 distinct PROCESSES: the three services map 1:1
        # to the three subprocesses, and the spool segment names carry
        # three distinct pids
        assert {"fleet_router", "query_server", "storage_server"} <= set(
            tree["services"])
        pids = {os.path.basename(p).split("-")[-2]
                for p in os.listdir(spool_dir)}
        assert len(pids) >= 3, pids
        # correct parent/child edges, nothing dangling
        assert tree["complete"] is True and not tree["orphans"]
        by_id = {s["spanId"]: s for s in tree["spans"]}
        roots = [s for s in tree["spans"] if s["parentId"] is None]
        assert len(roots) == 1 and roots[0]["service"] == "fleet_router"
        # the replica's server span hangs off the router's forward span,
        # and the storage server's span is below the replica's route span
        serve = [s for s in tree["spans"]
                 if s["service"] == "query_server"
                 and s["name"].startswith("POST")][0]
        assert by_id[serve["parentId"]]["name"] == "forward"
        storage_spans = [s for s in tree["spans"]
                         if s["service"] == "storage_server"]
        assert storage_spans, "storage hop produced no spans"

        def ancestors(s):
            seen = []
            while s["parentId"] is not None:
                s = by_id[s["parentId"]]
                seen.append(s["spanId"])
            return seen

        assert serve["spanId"] in ancestors(storage_spans[0])
    finally:
        for p in (router, replica, store):
            if p is not None:
                p.stop()


def test_trace_plane_sigkill_replica_mid_request_fragments_assemble(
        tmp_path):
    """ISSUE 14 chaos variant: SIGKILL the replica mid-request. The spooled
    fragments — the router's error span AND the storage hop the victim
    completed before dying — still assemble; the tree is marked incomplete
    (the victim's route span was never written)."""
    import threading

    store_cfg, variant_path = _train_classification(
        tmp_path, factory=_TRACE_FACTORY)
    spool_dir = str(tmp_path / "spool")
    trace_env = {"PIO_TRACE_SPOOL_DIR": spool_dir}
    sport, qport, rport = free_port(), free_port(), free_port()
    store = replica = router = None
    try:
        store = ServerProc(
            ["storageserver", "--ip", "127.0.0.1", "--port", str(sport)],
            env={**store_cfg, **trace_env})
        store.wait_ready(f"http://127.0.0.1:{sport}/", timeout=60.0)
        replica = ServerProc(
            ["deploy", "-v", variant_path, "--ip", "127.0.0.1",
             "--port", str(qport), "--query-timeout", "30"],
            env={**_remote_store_env(sport), **trace_env,
                 # predict: storage read (spooled), THEN a 5s floor the
                 # SIGKILL lands inside
                 "PIO_TRACE_TEST_PREDICT_SLEEP_MS": "5000"})
        replica.wait_ready(f"http://127.0.0.1:{qport}/", timeout=180.0)
        router = ServerProc(
            ["fleet", "route", "--ip", "127.0.0.1", "--port", str(rport),
             "--replica", f"http://127.0.0.1:{qport}",
             "--health-interval", "0.5", "--deadline", "20"],
            env=dict(trace_env))
        router.wait_ready(f"http://127.0.0.1:{rport}/")

        result: dict = {}

        def fire():
            result["out"] = _post_traced(
                f"http://127.0.0.1:{rport}/queries.json",
                {"features": [0.5, -0.2, 0.1]}, timeout=40.0)

        t = threading.Thread(target=fire)
        t.start()
        time.sleep(2.0)  # inside the 5s predict floor, storage hop done
        replica.kill9()
        t.join(timeout=60.0)
        assert not t.is_alive(), "query through the router hung"
        status, body, trace_id = result["out"]
        assert status in (500, 502, 503), (status, body)
        assert trace_id, "router did not echo X-PIO-Trace on the error"

        tree = _assemble_via_cli(spool_dir, trace_id)
        # the victim's fragment (its storage-attempt span) IS in the tree:
        # what the replica was doing when it was SIGKILLed
        statuses = [s["status"] for s in tree["spans"]]
        services = set(tree["services"])
        assert "fleet_router" in services
        assert any(st.startswith("error:") for st in statuses), statuses
        # the replica's route span died unwritten -> assembly says so
        # instead of passing the fragment off as a whole trace
        victim_spans = [s for s in tree["spans"]
                        if s["service"] != "fleet_router"]
        if victim_spans:  # storage hop completed before the kill
            assert tree["complete"] is False and tree["orphans"]
    finally:
        for p in (router, replica, store):
            if p is not None:
                p.stop()


# ---------------------------------------------------------------------------
# multi-host shard-owner serving chaos (ISSUE 16): SIGKILL one of three
# real shard-owner subprocesses mid-storm — zero wrong answers vs the
# single-process oracle; degraded answers flagged and counted; restart
# restores full answers and green health
# ---------------------------------------------------------------------------


def _post_query_hdrs(url, body, timeout=10.0):
    """(status, lowercase-header dict, parsed json) — the storm needs the
    X-PIO-Partial flag, which http_json drops."""
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return (resp.status,
                    {k.lower(): v for k, v in resp.headers.items()},
                    json.loads(resp.read() or b"null"))
    except urllib.error.HTTPError as e:
        payload = e.read()
        try:
            parsed = json.loads(payload or b"null")
        except ValueError:
            parsed = {"raw": payload.decode(errors="replace")}
        return e.code, {k.lower(): v for k, v in (e.headers or {}).items()}, \
            parsed


def _router_metric(rport: int, name: str) -> float:
    import urllib.request

    with urllib.request.urlopen(
            f"http://127.0.0.1:{rport}/metrics", timeout=5.0) as resp:
        text = resp.read().decode()
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and " " in line:
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_sharded_fleet_kill9_owner_mid_storm_zero_wrong_answers(tmp_path):
    """ISSUE 16 acceptance: three real shard-owner subprocesses behind a
    real router process; SIGKILL one owner mid-storm. Every UNFLAGGED 200
    must equal the single-process oracle exactly (merge tie discipline
    included); answers missing the dead range are flagged X-PIO-Partial
    with declared missingRows and counted; after the owner restarts (same
    state dir — its persisted epoch identity survives the SIGKILL) the
    fleet serves full oracle-exact answers again and health is green."""
    import threading

    from tests.fixtures.procs import ShardOwnerProc

    storage, store_cfg, variant_path, app_id = \
        _train_recommendation_eventlog(tmp_path)
    n_shards = 3
    oport = free_port()
    owner_ports = [free_port() for _ in range(n_shards)]
    rport = free_port()
    oracle_url = f"http://127.0.0.1:{oport}"
    owner_urls = [f"http://127.0.0.1:{p}" for p in owner_ports]
    router_q = f"http://127.0.0.1:{rport}/queries.json"

    def _owner(s: int) -> ShardOwnerProc:
        return ShardOwnerProc(
            s, n_shards, str(tmp_path / f"owner{s}"),
            ["-v", variant_path, "--ip", "127.0.0.1",
             "--port", str(owner_ports[s]), "--server-access-key", "sk"],
            env=store_cfg)

    oracle = ServerProc(["deploy", "-v", variant_path, "--ip", "127.0.0.1",
                         "--port", str(oport)], env=store_cfg)
    owners = [_owner(s) for s in range(n_shards)]
    router = None
    stop = threading.Event()
    try:
        oracle.wait_ready(f"{oracle_url}/", timeout=240.0)
        for url, o in zip(owner_urls, owners):
            o.wait_ready(f"{url}/", timeout=240.0)
        # the owners' announced ranges tile the catalog exactly
        annos = [o.announce(u) for o, u in zip(owners, owner_urls)]
        spans = sorted(tuple(a["rows"]) for a in annos)
        n_rows = annos[0]["nRows"]
        assert spans[0][0] == 0 and spans[-1][1] == n_rows
        assert all(spans[i][1] == spans[i + 1][0]
                   for i in range(len(spans) - 1)), spans

        router = _router_proc(store_cfg, owner_urls, rport,
                              "--server-access-key", "sk")
        router.wait_ready(f"http://127.0.0.1:{rport}/")
        # wait for the health watcher to adopt every shardOwner claim
        _wait_health(rport, lambda h: (h.get("sharding") or {})
                     .get("nRanges") == n_shards
                     and not h["sharding"]["downRanges"])

        # the oracle's answers for the whole user universe
        queries = [{"user": f"u{u}", "num": 5} for u in range(20)]
        oracle_ans = {}
        for q in queries:
            st, _h, body = _post_query_hdrs(
                f"{oracle_url}/queries.json", q)
            assert st == 200, (st, body)
            oracle_ans[q["user"]] = body["itemScores"]

        # steady state: scatter/gather over 3 owners == oracle, bitwise
        st, hdrs, body = _post_query_hdrs(router_q, queries[0])
        assert st == 200 and hdrs.get("x-pio-fleet-sharded") == "3"
        assert body["itemScores"] == oracle_ans["u0"]

        # ---- storm + SIGKILL owner 1 mid-storm -------------------------
        results: list = []

        def storm(offset: int) -> None:
            i = offset
            while not stop.is_set():
                q = queries[i % len(queries)]
                try:
                    out = _post_query_hdrs(router_q, q, timeout=15.0)
                except Exception:  # noqa: BLE001 - refused/reset/timeout
                    out = (-1, {}, None)
                results.append((q["user"], *out))
                i += 1
                time.sleep(0.01)

        threads = [threading.Thread(target=storm, args=(k * 5,),
                                    daemon=True) for k in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.5)
        victim_rows = owners[1].announce(owner_urls[1])["rows"]
        owners[1].kill9()
        time.sleep(3.0)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)

        # ---- forensics -------------------------------------------------
        assert len(results) > 50, "storm produced no meaningful traffic"
        wrong, partials, failed = [], 0, 0
        for user, st, hdrs, body in results:
            if st == 200 and "x-pio-partial" not in hdrs:
                if body["itemScores"] != oracle_ans[user]:
                    wrong.append((user, body["itemScores"]))
            elif st == 200:
                partials += 1
                missing = (body.get("partial") or {}).get("missingRows")
                assert missing, "flagged partial without declared rows"
                assert list(victim_rows) in [list(m) for m in missing]
            else:
                # orderly refusals only — never a silent short answer
                assert st in (503, 504, -1), (user, st, body)
                failed += 1
        assert not wrong, (
            f"WRONG unflagged answers vs oracle: {wrong[:3]} "
            f"({len(wrong)} total)")
        # the dead range was actually exercised: degraded answers exist
        # (default policy) and the router counted every one
        assert partials > 0, (
            f"kill window produced no degraded answers "
            f"(partials=0, failed={failed}, n={len(results)})")
        assert _router_metric(
            rport, "pio_fleet_partial_answers_total") >= partials

        # ---- recovery: restart the owner from its state dir ------------
        owners[1] = _owner(1)
        owners[1].wait_ready(f"{owner_urls[1]}/", timeout=240.0)
        ann = owners[1].announce(owner_urls[1])
        assert ann["rows"] == victim_rows  # same identity, same slice
        _wait_health(rport, lambda h: h["status"] == "ok"
                     and (h.get("sharding") or {}).get("nRanges") == n_shards
                     and not h["sharding"]["downRanges"])
        # a promote still works end-to-end (the operator fence-clearing
        # path) and a promoted owner keeps serving oracle-exact rows
        st, body = owners[1].promote(owner_urls[1], "sk")
        assert st == 200 and body["epoch"] >= 2, (st, body)
        for q in queries[:8]:
            st, hdrs, body = _post_query_hdrs(router_q, q)
            assert st == 200 and "x-pio-partial" not in hdrs, (st, hdrs)
            assert hdrs.get("x-pio-fleet-sharded") == "3"
            assert body["itemScores"] == oracle_ans[q["user"]]

        # `pio-tpu health` over the owners: green, with per-shard
        # coverage rows (satellite 1)
        gate = subprocess.run(
            [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli",
             "health", *owner_urls], capture_output=True, text=True,
            timeout=60)
        assert gate.returncode == 0, gate.stdout + gate.stderr
        assert "shard:" in gate.stdout
    finally:
        stop.set()
        if router is not None:
            router.stop()
        oracle.stop()
        for o in owners:
            o.stop()
        storage.close()


# ---------------------------------------------------------------------------
# ISSUE 19: fault-tolerant multi-host training
# ---------------------------------------------------------------------------

def _dist_recommendation(tmp_path, tag: str, n_events=4000, iterations=10):
    """Seed rating events into a fresh sqlite store and write a
    recommendation variant with slice checkpointing on, returning
    (run_env, variant_path, ckpt_dir). No incumbent train — the
    distributed supervisor runs are the only training here."""
    import datetime as dt

    import numpy as np

    from incubator_predictionio_tpu.data import DataMap, Event
    from incubator_predictionio_tpu.data.storage import use_storage

    utc = dt.timezone.utc
    store_cfg = {
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / f"store-{tag}.db"),
    }
    ckpt_dir = str(tmp_path / f"ckpt-{tag}")
    variant_path = str(tmp_path / f"engine-{tag}.json")
    with open(variant_path, "w") as f:
        json.dump({
            "id": f"dt-{tag}", "version": "1",
            "engineFactory": "incubator_predictionio_tpu.templates."
                             "recommendation.RecommendationEngine",
            "datasource": {"params": {"appName": "dt-app"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 32, "numIterations": iterations,
                "batchSize": 1024,
                "checkpointDir": ckpt_dir, "checkpointEvery": 1}}],
        }, f)
    storage = Storage(store_cfg)
    prev = use_storage(storage)
    try:
        app_id = storage.get_meta_data_apps().insert(App(0, "dt-app"))
        events = storage.get_events()
        events.init(app_id)
        rng = np.random.default_rng(11)
        events.insert_batch([
            Event(event="rate", entity_type="user",
                  entity_id=f"u{rng.integers(0, 400)}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, 300)}",
                  properties=DataMap({"rating": float(1 + 4 * rng.random())}),
                  event_time=dt.datetime(2022, 1, 1, tzinfo=utc))
            for _ in range(n_events)
        ], app_id)
    finally:
        use_storage(prev)
        storage.close()
    run_env = {**store_cfg, "PIO_FS_BASEDIR": str(tmp_path / f"fs-{tag}")}
    return run_env, variant_path, ckpt_dir


def test_distributed_train_survives_member_kill9_mid_epoch(tmp_path):
    """ISSUE 19 chaos proof: SIGKILL one member of a 2-process distributed
    train mid-epoch. The supervisor detects the loss, fences the old
    generation, re-forms the mesh on a fresh coordinator port, and the new
    generation RESUMES from the last committed slice checkpoint — final
    committed state is bit-identical to an uninterrupted control run
    (zero divergence), with bounded MTTR and a fenced zombie that can no
    longer commit."""
    import threading

    import numpy as np

    from incubator_predictionio_tpu.distributed.checkpoint import (
        DistSliceCheckpointer,
    )
    from incubator_predictionio_tpu.distributed.errors import (
        FencedGenerationError,
    )
    from incubator_predictionio_tpu.distributed.meshdir import MeshDirectory
    from incubator_predictionio_tpu.distributed.supervisor import Supervisor
    from incubator_predictionio_tpu.utils import checkpoint as ckpt_fs

    def make_supervisor(tag, run_env, variant_path):
        return Supervisor(
            ["train", "-v", variant_path, "--distributed",
             "--mesh-axes", '{"model": 2}'],
            num_processes=2,
            state_dir=str(tmp_path / f"mesh-{tag}"),
            heartbeat_ms=2000,
            max_recoveries=2,
            cpu_devices_per_process=1,
            env=run_env,
            timeout=600.0,
        )

    # -- control: uninterrupted 2-member run --------------------------------
    env_a, variant_a, ckpt_a = _dist_recommendation(tmp_path, "control")
    res_a = make_supervisor("control", env_a, variant_a).run()
    assert res_a.ok, (res_a, res_a.logs_text()[-4000:])
    assert res_a.recoveries == 0
    steps_a = ckpt_fs.committed_steps(ckpt_a)
    assert steps_a and steps_a[-1] == 10, steps_a
    # two members wrote disjoint row slices (real sharded ownership)
    import glob

    manifests = sorted(glob.glob(
        os.path.join(ckpt_a, "slices", f"step-{steps_a[-1]}", "member-*.json")))
    assert len(manifests) == 2, manifests

    # -- chaos: same data/seed, SIGKILL a member after the first commits ----
    env_b, variant_b, ckpt_b = _dist_recommendation(tmp_path, "chaos")
    sup = make_supervisor("chaos", env_b, variant_b)
    box = {}
    t = threading.Thread(target=lambda: box.update(res=sup.run()))
    t.start()
    deadline = time.monotonic() + 420.0
    killed = None
    while time.monotonic() < deadline:
        steps = ckpt_fs.committed_steps(ckpt_b)
        alive = sup.alive_pids()
        if steps and steps[-1] >= 2 and alive:
            rank, pid = sorted(alive.items())[-1]
            os.kill(pid, 9)
            killed = (rank, steps[-1])
            break
        if not t.is_alive():
            raise AssertionError(
                "run finished before the kill window: "
                + box["res"].logs_text()[-4000:])
        time.sleep(0.05)
    assert killed is not None, "no mid-epoch commit window appeared"
    t.join(timeout=600.0)
    assert not t.is_alive(), "supervised run wedged after the kill"
    res_b = box["res"]
    assert res_b.ok, (res_b, res_b.logs_text()[-4000:])

    # exactly one recovery, bounded MTTR (detect -> respawn)
    assert res_b.recoveries == 1, res_b
    assert res_b.generation == 2
    assert len(res_b.mttr_s) == 1 and 0.0 <= res_b.mttr_s[0] < 60.0, res_b

    # resume is real: the new generation restarted from a committed epoch,
    # not from scratch (pinned log line from utils/checkpoint.maybe_resume)
    logs = res_b.logs_text()
    assert "resuming from epoch" in logs, logs[-4000:]
    resumed_epoch = int(logs.split("resuming from epoch", 1)[1].split()[0])
    assert resumed_epoch >= 2, resumed_epoch

    # zero divergence: final committed state matches the control bit-for-bit
    steps_b = ckpt_fs.committed_steps(ckpt_b)
    assert steps_b and steps_b[-1] == 10, steps_b
    leaves_a = ckpt_fs.assemble_committed_step(ckpt_a, 10)
    leaves_b = ckpt_fs.assemble_committed_step(ckpt_b, 10)
    assert len(leaves_a) == len(leaves_b)
    for la, lb in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    # fencing: a zombie from the killed generation can no longer commit
    md = MeshDirectory(str(tmp_path / "mesh-chaos"))
    assert md.read_generation()[0] == 2
    zombie = DistSliceCheckpointer(
        ckpt_b, members=2, member=0, generation=1, meshdir=md,
        slice_fn=lambda i, leaf, m, n: [(np.asarray(leaf), None)])
    with pytest.raises(FencedGenerationError):
        zombie.save(11, {"w": np.zeros(2, np.float32)})
