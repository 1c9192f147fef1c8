"""Host-plane drills: the failure, overload and recovery exercises the docs
tell an operator to run (docs/dr.md, jobs.md, replication.md, resilience.md,
serving.md, sharding.md, streaming.md, tenancy.md). Not a benchmark: the
repo's benchmark is ``BENCHMARK.json`` + ``benchmarks/``, and nothing here
measures an accelerator.

``python drills.py`` runs every drill, each in a child process of its own
held to the CPU, and prints ONE JSON line ``{"configs": {<name>: <result>}}``;
it exits 1 if a drill failed. ``python drills.py --config <name>`` runs one
in this process and prints ``CONFIG_RESULT=<json>``. ``PIO_BENCH_SMALL=1``
cuts the shapes down, ``PIO_BENCH_CONFIGS=a,b`` selects drills.
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


def _log(msg: str) -> None:
    print(f"[drill] {msg}", file=sys.stderr, flush=True)


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
    serving drills (one training recipe, several load shapes);
    ``factory_path`` lets a scenario deploy a wrapped engine (the fleet
    scenario's service-floor fixture) around the same model."""
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


# ---------------------------------------------------------------------------
# goodput under overload (docs/resilience.md "Overload & admission
# control"): offered load at ~3× measured capacity through the real
# admission layer — goodput and admitted-p99, not peak qps, are what a
# production stack is judged on
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
# fleet serving (docs/serving.md "Fleet serving"): 1 vs 3 query-server
# replicas behind the fleet router at a FIXED offered load — the
# horizontal-scaling story the router exists for
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
# multi-tenant serving (docs/tenancy.md): four tenants in ONE
# query-server process under a shared byte budget, one tenant offering
# 3× its quota — the noisy-neighbor containment + packing numbers whose
# acceptance bars the chaos test asserts
# (tests/test_chaos_procs.py::test_multi_tenant_noisy_neighbor_contained)
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
# sharded fleet (docs/sharding.md "Multi-host shard owners"): the
# catalog split ACROSS processes — scatter/gather parity cost vs one
# process holding everything, plus failover MTTR when an owner takes
# a SIGKILL
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
# storage failover (docs/replication.md): sustained ingest, SIGKILL the
# primary storage server, promote the follower — MTTR and zero acked
# loss through the quorum-replicated eventlog
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
# event-server ingestion throughput (EventServer.scala:261-462 hot path)
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
    # the headline ratio: how much of the in-memory ack rate survives the
    # fsync-on-ack contract
    out["fsync_tax_vs_memory"] = (
        out["wal_fsync_events_per_sec"] / out["memory_events_per_sec"])
    out["fsync_tax_vs_nofsync"] = (
        out["wal_fsync_events_per_sec"] / out["wal_nofsync_events_per_sec"])
    return out


def _build_suite(ctx) -> dict:
    """The drills in suite order, by the name ``--config`` takes."""
    return {
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


CONFIG_NAMES = list(_build_suite(None))


# ---------------------------------------------------------------------------
# streaming freshness (docs/streaming.md): event→recommendation-visible
# latency through the incremental delta pipeline vs the full
# retrain+redeploy cycle, plus the updater's sustained fold throughput
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
# continuous training (docs/jobs.md): SIGKILL the training worker
# mid-epoch and measure retrain MTTR (kill → new instance serving),
# then trip the streaming quarantine and measure the auto-retrain loop's
# quarantine → fresh-recommendations end-to-end time
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
# distributed training (docs/sharding.md "Multi-host training"): 1 vs N
# supervised member processes training the recommendation template with
# row-sharded tables, then SIGKILL one member mid-epoch — MTTR, the
# pinned resume epoch, and zero divergence vs the uninterrupted N-member
# run, plus the supervisor plane's pio_dist_* metric deltas
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
    """Child mode: run exactly one drill and print ``CONFIG_RESULT=<json>``.

    Every drill exercises the host plane (admission, routing, storage,
    recovery), so the process and the servers it spawns are held to the
    CPU: beside a live deploy a drill must never claim the accelerator."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    t0 = time.perf_counter()
    result = _build_suite(MeshContext.create())[name]()
    _log(f"{name}: {result} ({time.perf_counter() - t0:.1f}s)")
    print("CONFIG_RESULT=" + json.dumps(result), flush=True)


def _child_argv(name: str) -> list[str]:
    """One drill's child process: this file's child mode, so the child is
    held to the CPU by ``run_one_config`` like a drill started by hand."""
    return [sys.executable, os.path.abspath(__file__), "--config", name]


def _run_config_subprocess(name: str, timeout_s: float):
    """Run one drill in a child process. Returns (result_dict, wedged_bool).

    A drill that hangs (a spawned server that never answers, a wedged
    runtime call where signal handlers never run) is killed with its whole
    process group, which leaves the parent free to run the remaining
    drills."""
    import signal
    import subprocess

    # start_new_session: on timeout the whole process GROUP is killed —
    # a config's own children (spawned event/query servers) would otherwise
    # survive and hold the stdout pipe open, hanging the parent's drain
    proc = subprocess.Popen(
        _child_argv(name), stdout=subprocess.PIPE, stderr=None,
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
            _log(f"WATCHDOG: config '{name}': {result['error']}")

    print(json.dumps({"configs": configs}), flush=True)
    failed = [n for n, r in configs.items() if "error" in r]
    if failed:
        _log(f"FAILED lanes: {failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
