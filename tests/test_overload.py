"""Overload protection (resilience/admission.py): admission control,
deadline-aware shedding, brownout, adaptive concurrency, and per-client
fairness across all three servers.

Every timing-dependent decision runs on FakeClock — limit changes, sheds,
brownout enter/exit, and Retry-After values are asserted exactly, with no
wall-clock sleeps (the ISSUE 5 acceptance bar). The asyncio plumbing
(futures resolving, semaphores resizing) uses the event loop but never
waits out a timing window. The exception: what the limiter hears from a live
batcher is a real dispatch's duration, so those tests drive a stub that
sleeps 10-30 ms a batch, with margins of 2x and more around every threshold.
"""

import asyncio
import contextvars
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

from incubator_predictionio_tpu.obs.metrics import (
    LatencyReservoir as ObsLatencyReservoir,
)
from incubator_predictionio_tpu.resilience.admission import (
    ADMIT,
    BROWNOUT,
    REJECT,
    AdaptiveConcurrencyLimiter,
    AdmissionConfig,
    AdmissionController,
    FairnessGate,
    InflightGate,
    RateEstimator,
    ShedExpired,
    TokenBucket,
    derive_retry_after,
)
from incubator_predictionio_tpu.resilience.clock import FakeClock


# ---------------------------------------------------------------------------
# units: estimator / retry-after / buckets / gates
# ---------------------------------------------------------------------------

def test_rate_estimator_windowed_rate_on_fake_clock():
    clk = FakeClock()
    est = RateEstimator(window_sec=10.0, clock=clk)
    assert est.rate() == 0.0
    est.record(10)
    clk.advance(2.0)
    est.record(10)
    # 20 events over the 2s observed span — NOT over the whole 10s window
    # (the full-window denominator starved young servers of rate signal)
    assert est.rate() == pytest.approx(10.0)
    clk.advance(9.0)  # first record falls out of the window
    # a single retained event is "no signal": its observed span can be
    # arbitrarily small (right after an idle gap it is ~0), and a floored
    # division would overestimate the rate by orders of magnitude
    assert est.rate() == 0.0
    est.record(10)
    # 20 events over the 9s span from the surviving record to now
    assert est.rate() == pytest.approx(20 / 9.0)
    clk.advance(20.0)
    assert est.rate() == 0.0


def test_derive_retry_after_math_fallback_and_clamp():
    assert derive_retry_after(0, 50.0, fallback=5) == 1       # no pressure
    assert derive_retry_after(100, 0.0, fallback=7) == 7      # no signal
    assert derive_retry_after(100, 20.0, fallback=5) == 5     # 100/20
    assert derive_retry_after(7, 2.0, fallback=5) == 4        # ceil(3.5)
    assert derive_retry_after(10_000, 1.0, fallback=5) == 60  # hi clamp
    assert derive_retry_after(1, 1000.0, fallback=5) == 1     # lo clamp


def test_token_bucket_burst_refill_and_retry_after():
    clk = FakeClock()
    b = TokenBucket(rate=2.0, burst=4.0, clock=clk)
    assert all(b.try_acquire() for _ in range(4))  # the whole burst
    assert not b.try_acquire()
    # 1 token needs 0.5s at 2/s
    assert b.retry_after(1) == pytest.approx(0.5)
    clk.advance(0.5)
    assert b.try_acquire()
    assert not b.try_acquire()
    clk.advance(10.0)  # refill caps at burst
    assert b.retry_after(1) == 0.0
    assert sum(b.try_acquire() for _ in range(10)) == 4


def test_fairness_gate_throttles_one_client_alone():
    clk = FakeClock()
    gate = FairnessGate(rate=2.0, burst=2.0, clock=clk)
    assert gate.admit("keyA") is None
    assert gate.admit("keyA") is None
    retry = gate.admit("keyA")  # burst spent
    assert retry is not None and retry >= 1
    # a different client is untouched by A's debt
    assert gate.admit("keyB") is None
    clk.advance(1.0)  # 2 tokens back at 2/s
    assert gate.admit("keyA") is None
    assert gate.throttled_count == 1
    snap = gate.snapshot()
    assert snap["enabled"] and snap["trackedClients"] == 2


def test_fairness_gate_oversized_batch_pays_full_cost_as_debt():
    """A batch larger than the burst is admitted once the full burst has
    accumulated, but its WHOLE event count is charged into debt — the
    configured events/sec holds even for batch-heavy clients (charging
    only the burst would under-enforce by batch_size/burst)."""
    clk = FakeClock()
    gate = FairnessGate(rate=1.0, burst=2.0, clock=clk)
    assert gate.admit("k", cost=50.0) is None  # full bucket covers entry
    # the 48-token debt pays off at 1/s before the next single event
    assert gate.admit("k", cost=1.0) == 49
    clk.advance(48.9)
    assert gate.admit("k", cost=1.0) is not None  # still 0.9 tokens
    clk.advance(0.1)
    assert gate.admit("k", cost=1.0) is None  # debt cleared


def test_fairness_gate_disabled_admits_everything():
    gate = FairnessGate(rate=0.0, clock=FakeClock())
    assert not gate.enabled
    for _ in range(100):
        assert gate.admit("k") is None


def test_inflight_gate_caps_per_client():
    gate = InflightGate(max_in_flight=2)
    assert gate.acquire("a") and gate.acquire("a")
    assert not gate.acquire("a")       # a queues behind itself
    assert gate.acquire("b")           # b is unaffected
    gate.release("a")
    assert gate.acquire("a")
    snap = gate.snapshot()
    assert snap["inFlight"] == 3 and snap["throttled"] == 1
    gate.release("a"), gate.release("a"), gate.release("b")
    assert gate.snapshot()["inFlight"] == 0


# ---------------------------------------------------------------------------
# adaptive concurrency limiter (AIMD)
# ---------------------------------------------------------------------------

def _feed(limiter, latency, n):
    changed = None
    for _ in range(n):
        got = limiter.observe(latency)
        if got is not None:
            changed = got
    return changed


def test_adaptive_limiter_aimd_shrinks_and_grows():
    clk = FakeClock()
    lim = AdaptiveConcurrencyLimiter(
        min_limit=1, max_limit=4, target_sec=0.010, window=8,
        cooldown_sec=1.0, clock=clk)
    assert lim.limit == 4  # starts optimistic
    # a window of 50ms medians vs the 10ms target → multiplicative decrease
    assert _feed(lim, 0.050, 8) == 2
    clk.advance(1.1)  # cooldown
    assert _feed(lim, 0.050, 8) == 1
    clk.advance(1.1)
    assert _feed(lim, 0.050, 8) is None  # pinned at min
    assert lim.limit == 1
    # comfortable latency (< headroom × target) → additive increase
    clk.advance(1.1)
    assert _feed(lim, 0.002, 8) == 2
    clk.advance(1.1)
    assert _feed(lim, 0.002, 8) == 3
    assert lim.changes == 4


def test_adaptive_limiter_cooldown_rate_limits_changes():
    clk = FakeClock()
    lim = AdaptiveConcurrencyLimiter(
        min_limit=1, max_limit=4, target_sec=0.010, window=4,
        cooldown_sec=5.0, clock=clk)
    assert _feed(lim, 0.050, 4) == 2
    # a second bad window inside the cooldown must NOT move the limit
    assert _feed(lim, 0.050, 4) is None
    assert lim.limit == 2
    clk.advance(5.1)
    assert _feed(lim, 0.050, 4) == 1


def test_adaptive_limiter_gradient_mode_tracks_baseline():
    clk = FakeClock()
    lim = AdaptiveConcurrencyLimiter(
        min_limit=1, max_limit=2, target_sec=None, tolerance=2.0,
        window=4, cooldown_sec=0.0, clock=clk)
    # window of identical samples: baseline == median → within tolerance
    assert _feed(lim, 0.010, 4) is None
    assert lim.current_target() == pytest.approx(0.020)
    # congestion: median 3× the learned baseline → shrink
    assert _feed(lim, 0.030, 4) == 1


def test_adaptive_limiter_set_bounds_clamps_and_resets():
    clk = FakeClock()
    lim = AdaptiveConcurrencyLimiter(
        min_limit=1, max_limit=4, target_sec=0.010, window=4,
        cooldown_sec=0.0, clock=clk)
    assert lim.set_bounds(1, 2) == 2  # 4 clamped into the new bound
    assert lim.limit == 2
    assert lim.set_bounds(1, 8) == 2  # raising the cap keeps the limit


# ---------------------------------------------------------------------------
# admission controller: feasibility, queue bound, brownout hysteresis
# ---------------------------------------------------------------------------

def _controller(clk, **cfg_kw):
    cfg = AdmissionConfig(**{"adaptive": False, **cfg_kw})
    return AdmissionController(cfg, clock=clk)


def test_admission_always_admits_empty_queue():
    clk = FakeClock()
    ctrl = _controller(clk, max_queue=4, deadline_sec=0.1)
    # even with a painfully slow observed service rate, an empty queue
    # waits ~0 — the structural zero-sheds-below-capacity property
    ctrl.on_complete()
    clk.advance(10.0)
    for _ in range(20):
        decision, retry = ctrl.decide(0)
        assert decision == ADMIT and retry is None
    assert ctrl.rejected == 0


def test_admission_rejects_on_queue_bound_with_fallback_retry_after():
    clk = FakeClock()
    ctrl = _controller(clk, max_queue=4, retry_after_fallback=9)
    decision, retry = ctrl.decide(4)
    assert decision == REJECT
    assert retry == 9  # no rate signal yet → the static fallback
    assert ctrl.rejected == 1


def test_admission_rejects_infeasible_deadline_with_derived_retry_after():
    clk = FakeClock()
    ctrl = _controller(clk, max_queue=1000, deadline_sec=0.5)
    # establish 10/s service rate: 10 completions over 1s
    for _ in range(5):
        ctrl.on_complete()
        clk.advance(0.2)
        ctrl.on_complete()
    # depth 20 at 10/s → 2s predicted wait >> 0.5s deadline → reject,
    # and the client is told how long the queue actually takes to drain
    decision, retry = ctrl.decide(20)
    assert decision == REJECT
    assert retry == 2  # ceil(20 / 10)
    # depth 3 at 10/s → 0.3s wait < deadline → admit
    assert ctrl.decide(3)[0] == ADMIT


def test_brownout_enter_exit_hysteresis_on_fake_clock():
    clk = FakeClock()
    ctrl = _controller(
        clk, max_queue=10, brownout_enter_frac=0.5,
        brownout_enter_sec=1.0, brownout_exit_sec=2.0)
    # pressure 0.6 (depth 6/10, no deadline signal): saturated but the
    # dwell hasn't elapsed — still admitting
    assert ctrl.decide(6)[0] == ADMIT
    clk.advance(0.5)
    assert ctrl.decide(6)[0] == ADMIT
    assert not ctrl.brownout_active
    clk.advance(0.6)  # 1.1s of sustained saturation
    assert ctrl.decide(6)[0] == BROWNOUT
    assert ctrl.brownout_active
    # clear air starts the exit dwell; brownout holds until it elapses
    clk.advance(0.1)
    assert ctrl.decide(0)[0] == BROWNOUT
    clk.advance(1.0)
    assert ctrl.decide(0)[0] == BROWNOUT
    clk.advance(1.1)  # 2.1s clear
    assert ctrl.decide(0)[0] == ADMIT
    assert not ctrl.brownout_active
    # a saturation blip mid-exit-dwell resets the clear timer
    clk.advance(0.1)
    assert ctrl.decide(6)[0] == ADMIT  # dwell restarts, not instant


def test_brownout_exits_on_idle_server_via_health_and_scrapes():
    """Brownout must not latch once traffic stops: state otherwise only
    advances in decide(), and a browned-out server the LB pulled would
    report brownoutActive=1 forever — health probes and metric scrapes
    keep the hysteresis clock moving."""
    clk = FakeClock()
    ctrl = _controller(
        clk, max_queue=10, brownout_enter_frac=0.5,
        brownout_enter_sec=1.0, brownout_exit_sec=2.0)
    ctrl.decide(6)
    clk.advance(1.1)
    assert ctrl.decide(6)[0] == BROWNOUT
    # traffic stops dead; only /health probes arrive from here on
    clk.advance(0.5)
    assert ctrl.snapshot(0)["brownoutActive"]  # clear dwell just started
    clk.advance(2.1)
    assert not ctrl.snapshot(0)["brownoutActive"]
    assert not ctrl.brownout_active


def test_admission_shed_bookkeeping_counts_as_drain_progress():
    clk = FakeClock()
    ctrl = _controller(clk, max_queue=100, deadline_sec=1.0)
    ctrl.on_shed_expired(10)
    assert ctrl.shed_expired == 10
    # sheds leave the queue too: they must feed the service-rate signal
    # or a burst of dead requests reads as a stalled server forever
    # (a lone burst is still "no signal" — the estimator needs two
    # retained events before it reports a rate)
    clk.advance(2.0)
    ctrl.on_shed_expired(10)
    assert ctrl.service_rate() == pytest.approx(10.0)


def test_admission_snapshot_shape():
    clk = FakeClock()
    ctrl = AdmissionController(
        AdmissionConfig(max_queue=8, deadline_sec=0.5, adaptive=True,
                        min_inflight=1, max_inflight=2), clock=clk)
    snap = ctrl.snapshot(3)
    assert snap["queueDepth"] == 3 and snap["queueMax"] == 8
    assert snap["inflightLimit"] == 2
    assert set(snap) >= {"brownoutActive", "admitted", "rejected",
                         "brownoutServed", "shedExpired",
                         "serviceRatePerSec"}


# ---------------------------------------------------------------------------
# micro-batcher: deadline eviction + live resize (the ISSUE 5 satellites)
# ---------------------------------------------------------------------------

class _EchoDeployed:
    """predict_batch stub: records concurrency + dispatched payload ids."""

    def __init__(self, block_s: float = 0.0, gate=None):
        self._lock = threading.Lock()
        self.active = 0
        self.max_active = 0
        self.dispatched: list = []
        self.block_s = block_s
        self.gate = gate

    def predict_batch(self, payloads):
        import time as _t

        with self._lock:
            self.active += 1
            self.max_active = max(self.max_active, self.active)
            self.dispatched.extend(p["id"] for p in payloads)
        if self.gate is not None:
            try:
                self.gate.wait(timeout=5.0)
            except Exception:  # noqa: BLE001 - broken barrier == no overlap
                pass
        if self.block_s:
            _t.sleep(self.block_s)
        with self._lock:
            self.active -= 1
        return [{"echo": p["id"]} for p in payloads]


def test_micro_batcher_evicts_expired_entries_at_assembly():
    """The 504-evict step, deterministically: entries enqueued with an
    already-expired FakeClock deadline resolve ShedExpired and never reach
    predict_batch; live entries in the same assembly dispatch normally."""
    from incubator_predictionio_tpu.server.query_server import MicroBatcher

    clk = FakeClock()
    stub = _EchoDeployed()
    ctrl = _controller(clk, max_queue=100)

    async def t():
        batcher = MicroBatcher(stub, max_batch=8, deadline_sec=0.5,
                               clock=clk, admission=ctrl)
        loop = asyncio.get_running_loop()
        dead_fut, live_fut = loop.create_future(), loop.create_future()
        ctx = contextvars.copy_context()
        # one entry whose deadline will have passed, one with headroom
        await batcher.queue.put(
            ({"id": "dead"}, dead_fut, 0.0, ctx, clk.monotonic() + 0.5))
        await batcher.queue.put(
            ({"id": "live"}, live_fut, 0.0, ctx, clk.monotonic() + 60.0))
        clk.advance(1.0)  # the first deadline expires while queued
        batcher.start()
        dead, live = await dead_fut, await asyncio.wait_for(live_fut, 5.0)
        await batcher.stop()
        return dead, live

    dead, live = asyncio.run(t())
    assert isinstance(dead, ShedExpired)
    assert getattr(live, "result", None) == {"echo": "live"}
    assert stub.dispatched == ["live"]  # the dead entry never dispatched
    assert ctrl.shed_expired == 1


def test_micro_batcher_all_expired_batch_skips_dispatch():
    from incubator_predictionio_tpu.server.query_server import MicroBatcher

    clk = FakeClock()
    stub = _EchoDeployed()

    async def t():
        batcher = MicroBatcher(stub, max_batch=4, deadline_sec=0.1,
                               clock=clk)
        loop = asyncio.get_running_loop()
        futs = [loop.create_future() for _ in range(3)]
        ctx = contextvars.copy_context()
        for i, fut in enumerate(futs):
            await batcher.queue.put(
                ({"id": i}, fut, 0.0, ctx, clk.monotonic() + 0.1))
        clk.advance(1.0)
        batcher.start()
        got = [await f for f in futs]
        # the drainer survived the empty assembly: a live submit after the
        # all-dead batch still dispatches (the slot was handed back)
        result = await batcher.submit({"id": "after"})
        await batcher.stop()
        return got, result

    got, result = asyncio.run(t())
    assert all(isinstance(g, ShedExpired) for g in got)
    assert result == {"echo": "after"}
    assert stub.dispatched == ["after"]
    assert stub.max_active == 1


def test_micro_batcher_resize_shrink_mid_traffic_strands_no_futures():
    """ISSUE 5 satellite: MicroBatcher.resize() under concurrent load —
    a live shrink while dispatches are in flight loses nothing, and the
    drainer honors the new slot count afterwards."""
    from incubator_predictionio_tpu.server.query_server import MicroBatcher

    stub = _EchoDeployed(block_s=0.01)

    async def t():
        batcher = MicroBatcher(stub, max_batch=1, max_in_flight=2)
        wave1 = [asyncio.create_task(batcher.submit({"id": i}))
                 for i in range(12)]
        # shrink WHILE wave1 is mid-flight: resize waits out the excess
        # in-flight dispatch, so from its return the bound is real
        while stub.active == 0:
            await asyncio.sleep(0.001)
        await batcher.resize(1)
        got1 = await asyncio.gather(*wave1)
        stub.max_active = 0
        got2 = await asyncio.gather(
            *(batcher.submit({"id": 100 + i}) for i in range(8)))
        await batcher.stop()
        return got1, got2

    got1, got2 = asyncio.run(t())
    assert [r["echo"] for r in got1] == list(range(12))  # nothing stranded
    assert [r["echo"] for r in got2] == [100 + i for i in range(8)]
    assert stub.max_active == 1  # the shrunk bound held for wave 2


def test_micro_batcher_resize_grow_enables_overlap():
    """Growing mid-traffic genuinely adds slots: after resize(3), three
    dispatches must meet at a 3-party barrier (impossible at the old
    bound of 1)."""
    from incubator_predictionio_tpu.server.query_server import MicroBatcher

    barrier = threading.Barrier(3)
    stub = _EchoDeployed(gate=barrier)

    async def t():
        batcher = MicroBatcher(stub, max_batch=1, max_in_flight=1)
        first = await batcher.submit({"id": 0})  # barrier times out alone
        await batcher.resize(3)
        barrier.reset()
        got = await asyncio.gather(
            *(batcher.submit({"id": 1 + i}) for i in range(3)))
        await batcher.stop()
        return first, got

    first, got = asyncio.run(t())
    assert first == {"echo": 0}
    assert [r["echo"] for r in got] == [1, 2, 3]
    assert stub.max_active == 3  # all three met at the barrier


# ---------------------------------------------------------------------------
# query server integration (stub engine — no training, no device)
# ---------------------------------------------------------------------------

class _StubServing:
    def supplement(self, q):
        return q

    def serve(self, q, preds):
        return preds[0]


class _StubAlgo:
    serving_thread_safe = True

    def __init__(self):
        self.mode = "ok"
        self.gate = None

    def query_class(self):
        return None

    def predict(self, model, query):
        if self.gate is not None:
            self.gate.wait(timeout=10.0)
        return {"label": 1, "source": "live"}

    def batch_predict(self, model, pairs):
        return [(i, self.predict(model, q)) for i, q in pairs]


class _StubEngine:
    def __init__(self, algo):
        self._algo = algo

    def serving_and_algorithms(self, engine_params):
        return [self._algo], _StubServing()


def _mk_server(algo, clk=None, **cfg_kw):
    import datetime as dt

    from incubator_predictionio_tpu.core import EngineParams
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.resilience.clock import SYSTEM_CLOCK
    from incubator_predictionio_tpu.server.query_server import (
        DeployedEngine,
        QueryServer,
        ServerConfig,
    )

    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    config = ServerConfig(**cfg_kw)
    instance = EngineInstance(
        id="inst-1", status="COMPLETED",
        start_time=dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc),
        end_time=None, engine_id="stub", engine_version="1",
        engine_variant="v", engine_factory="stub.Engine")
    deployed = DeployedEngine(
        _StubEngine(algo), EngineParams(), instance, [None], warmup=False)
    server = QueryServer(config, storage=storage, deployed=deployed,
                         clock=clk or SYSTEM_CLOCK)
    return server, storage


def test_query_server_429_at_the_door_when_queue_saturates():
    """Queue at its bound → 429 + Retry-After at the door; queued requests
    complete once the wedged dispatch frees up."""
    algo = _StubAlgo()
    algo.gate = threading.Event()
    # max_in_flight=1: ONE wedged dispatch must back the queue up
    server, storage = _mk_server(algo, admission_max_queue=2,
                                 max_in_flight=1)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            payload = {"features": [1]}
            # wedge ONE dispatch first, THEN fill the queue — posting all
            # at once could coalesce into a single batch and never back up
            tasks = [asyncio.create_task(
                client.post("/queries.json", json=payload))]
            while not server.batcher._inflight:
                await asyncio.sleep(0.005)
            tasks += [asyncio.create_task(
                client.post("/queries.json", json=payload))
                for _ in range(2)]
            while server.batcher.queue.qsize() < 2:
                await asyncio.sleep(0.005)
            resp = await client.post("/queries.json", json=payload)
            assert resp.status == 429
            assert "Retry-After" in resp.headers
            assert "admission" in (await resp.json())["message"]
            algo.gate.set()
            results = await asyncio.gather(*tasks)
            assert [r.status for r in results] == [200, 200, 200]
            health = await (await client.get("/health")).json()
            assert health["admission"]["rejected"] == 1
            assert health["admission"]["queueMax"] == 2
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


class _ServingDown(_StubAlgo):
    def predict(self, model, query):
        from incubator_predictionio_tpu.resilience.policy import (
            ServingUnavailable,
        )
        raise ServingUnavailable("every backend is down")


class _Rejecting(_StubAlgo):
    def predict(self, model, query):
        raise TypeError("binding rejected")


class _Crashing(_StubAlgo):
    def predict(self, model, query):
        raise RuntimeError("engine bug")


@pytest.mark.parametrize("algo_cls, status, degraded, samples", [
    (_StubAlgo, 200, False, 2),    # the control: a clean dispatch IS heard
    (_Rejecting, 400, False, 0),
    (_ServingDown, 200, True, 0),
    (_Crashing, 500, False, 0),
], ids=["clean", "invalid-400", "degraded", "engine-error"])
def test_query_server_every_outcome_feeds_rate_only_clean_dispatch_feeds_limiter(
        algo_cls, status, degraded, samples):
    """400 binding rejections, degraded answers and engine errors drained
    the queue and rode a dispatch like any 200 — they must feed the
    service-rate estimate, or a rate fed only by clean successes
    under-reads the true drain rate and sheds good traffic below capacity
    on mixed workloads. But only a CLEAN dispatch's duration reaches the
    AIMD limiter, one observation a batch: a ~1ms all-400 dispatch adopted
    as the gradient-mode "no-contention" baseline would make every real
    dispatch read as congestion and pin the concurrency limit at 1."""
    server, storage = _mk_server(algo_cls())

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            for _ in range(2):
                resp = await client.post("/queries.json",
                                         json={"features": [1]})
                assert resp.status == status
                if status == 200:
                    body = await resp.json()
                    assert body.get("degraded", False) is degraded
            assert server._admission.service_rate() > 0
            assert len(server._admission.limiter._samples) == samples
            assert server._admission.limiter._baseline is None
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


@pytest.mark.parametrize("failure", ["dispatch-raises", "callers-gave-up"])
def test_query_server_failed_or_abandoned_dispatch_never_feeds_limiter(
        failure, monkeypatch):
    """The other two ways a dispatch ends without a verdict on the slots: it
    raised as a whole, or every caller had already been answered from the
    degraded path (budget blown) when it returned. Both count as drain
    progress; neither is a dispatch time."""
    algo = _StubAlgo()
    algo.gate = threading.Event()
    server, storage = _mk_server(algo, query_timeout_sec=0.05)
    if failure == "dispatch-raises":
        algo.gate.set()

        def boom(payloads):
            raise RuntimeError("device lost")

        monkeypatch.setattr(server.deployed, "predict_batch", boom)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            # two requests: the rate estimate needs two events for a signal
            posts = [asyncio.create_task(client.post(
                "/queries.json", json={"features": [1]})) for _ in range(2)]
            for resp in await asyncio.gather(*posts):
                if failure == "dispatch-raises":
                    assert resp.status == 500
                else:
                    assert (await resp.json())["degraded"] is True
            if failure == "callers-gave-up":
                assert server.batcher._inflight  # still wedged in predict
                algo.gate.set()
            for _ in range(400):
                if not server.batcher._inflight:
                    break
                await asyncio.sleep(0.005)
            assert not server.batcher._inflight
            assert server.batcher.batches_served >= 1
            assert server._admission.service_rate() > 0
            assert server._admission.limiter._samples == []
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


def test_query_server_504_evicts_expired_queued_request():
    """A request whose deadline expires while queued answers 504 (shed),
    never a wasted dispatch — driven by FakeClock, no wall sleeps."""
    algo = _StubAlgo()
    algo.gate = threading.Event()
    clk = FakeClock()
    server, storage = _mk_server(
        algo, clk=clk, query_timeout_sec=30.0, admission_max_queue=100,
        max_in_flight=1)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            payload = {"features": [1]}
            first = asyncio.create_task(
                client.post("/queries.json", json=payload))
            while not server.batcher._inflight:
                await asyncio.sleep(0.005)
            second = asyncio.create_task(
                client.post("/queries.json", json=payload))
            while server.batcher.queue.qsize() < 1:
                await asyncio.sleep(0.005)
            clk.advance(31.0)  # the queued request's budget expires
            algo.gate.set()
            r1, r2 = await asyncio.gather(first, second)
            assert r1.status == 200  # dispatched before expiry
            assert r2.status == 504
            assert "Retry-After" in r2.headers
            assert "shed" in (await r2.json())["message"]
            health = await (await client.get("/health")).json()
            assert health["admission"]["shedExpired"] == 1
            status = await (await client.get("/")).json()
            assert status["shedExpired"] == 1
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


def test_query_server_brownout_serves_degraded_then_recovers():
    """Sustained saturation → brownout: valid degraded 200s from the
    last-good cache without touching the device queue; clear air for the
    exit dwell lifts it. All transitions scripted on FakeClock."""
    algo = _StubAlgo()
    clk = FakeClock()
    server, storage = _mk_server(algo, clk=clk, admission_max_queue=10,
                                 brownout_enter_sec=1.0,
                                 brownout_exit_sec=2.0)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            payload = {"features": [1]}
            resp = await client.post("/queries.json", json=payload)
            assert resp.status == 200  # primes the last-good cache
            # script sustained saturation against the controller (depth
            # 6/10 ≥ enter_frac 0.5 for > enter_sec)
            ctrl = server._admission
            ctrl.decide(6)
            clk.advance(1.1)
            assert ctrl.decide(6)[0] == BROWNOUT
            resp = await client.post("/queries.json", json=payload)
            assert resp.status == 200
            body = await resp.json()
            assert body["degraded"] is True
            assert body["label"] == 1  # replayed from last-good
            health = await (await client.get("/health")).json()
            assert health["admission"]["brownoutActive"] is True
            # exit: the posts themselves see an empty queue (clear air)
            clk.advance(0.1)
            await client.post("/queries.json", json=payload)
            clk.advance(2.1)
            resp = await client.post("/queries.json", json=payload)
            assert resp.status == 200
            assert "degraded" not in (await resp.json())
            assert not server._admission.brownout_active
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


def test_query_server_health_and_metrics_admitted_under_saturation():
    """The always-admitted priority class: with the dispatch wedged and
    the admission queue full, /health and /metrics still answer 200."""
    algo = _StubAlgo()
    algo.gate = threading.Event()
    server, storage = _mk_server(algo, admission_max_queue=1,
                                 max_in_flight=1)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            payload = {"features": [1]}
            tasks = [asyncio.create_task(
                client.post("/queries.json", json=payload))]
            while not server.batcher._inflight:
                await asyncio.sleep(0.005)
            tasks.append(asyncio.create_task(
                client.post("/queries.json", json=payload)))
            while server.batcher.queue.qsize() < 1:
                await asyncio.sleep(0.005)
            resp = await client.post("/queries.json", json=payload)
            assert resp.status == 429  # query traffic IS being rejected
            health = await client.get("/health")
            assert health.status == 200
            metrics = await client.get("/metrics")
            assert metrics.status == 200
            assert "pio_admission_queue_depth" in (await metrics.text())
            algo.gate.set()
            await asyncio.gather(*tasks)
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


def test_query_server_adaptive_limiter_resizes_batcher_live():
    """The AIMD limiter's verdict reaches the running batcher: latency far
    above an explicit target shrinks max_in_flight from 2 to 1."""
    algo = _StubAlgo()
    server, storage = _mk_server(
        algo, admission_target_ms=0.000001, admission_max_queue=1000)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            assert server.batcher.max_in_flight == 2  # thread-safe stub
            payload = {"features": [1]}
            # one AIMD window of completions, each far over the target
            for _ in range(33):
                resp = await client.post("/queries.json", json=payload)
                assert resp.status == 200
            for _ in range(200):  # the resize lands via a background task
                if server.batcher.max_in_flight == 1:
                    break
                await asyncio.sleep(0.005)
            assert server.batcher.max_in_flight == 1
            assert server._admission.current_limit() == 1
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


class _SleepAlgo(_StubAlgo):
    """A device round trip: one sleep a BATCH, the GIL released."""

    def __init__(self, delay):
        super().__init__()
        self.delay = delay

    def batch_predict(self, model, pairs):
        import time as _t

        _t.sleep(self.delay)
        return [(i, {"label": 1, "source": "live"}) for i, _q in pairs]


def _short_window_limiter(server, window=8):
    """The server's own limiter with a window a test can fill quickly (the
    constants under test are the class defaults; only the sample count and
    the cool-down shrink)."""
    lim = AdaptiveConcurrencyLimiter(
        min_limit=1, max_limit=2, window=window, cooldown_sec=0.0)
    server._admission.limiter = lim
    return lim


async def _timed_post(client, payload):
    import time as _t

    t0 = _t.perf_counter()
    resp = await client.post("/queries.json", json=payload)
    assert resp.status == 200
    return _t.perf_counter() - t0


def test_query_server_queue_wait_in_front_of_slots_is_not_congestion():
    """Healthy batching under load: bursts queue in FRONT of the dispatch
    slots, so the median request takes several times the fastest one, while
    every dispatch takes the same time. The limiter sizes the slots and
    hears dispatch times only, so the limit stays at its bound window after
    window. (Fed each request's whole latency, as it once was, it read the
    batcher's own queue as congestion of the slots and cut 2 -> 1 in the
    first window, which lengthened the very wait it was reacting to.)"""
    algo = _SleepAlgo(0.015)
    server, storage = _mk_server(algo, max_batch=2,
                                 admission_max_queue=1000)
    lim = _short_window_limiter(server)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            payload = {"features": [1]}
            assert server.batcher.max_in_flight == 2
            latencies = []
            while server.batcher.batches_served < 10 * lim.window:
                latencies += await asyncio.gather(
                    *(_timed_post(client, payload) for _ in range(32)))
            latencies.sort()
            # the premise: the median request waited behind other batches
            assert latencies[len(latencies) // 2] > 2 * latencies[0]
            assert lim.changes == 0
            assert lim.limit == 2
            assert server.batcher.max_in_flight == 2
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


def test_query_server_limiter_shrinks_when_overlapping_dispatches_slow():
    """What the limiter is for survives the new feed: dispatches that take
    three times the best dispatch while two are in flight (a lock inside
    predict, a host out of cores, a full device queue) shrink the limit;
    once they run at the old pace again it grows back."""
    algo = _SleepAlgo(0.010)
    server, storage = _mk_server(algo, max_batch=2,
                                 admission_max_queue=1000)
    lim = _short_window_limiter(server)

    async def post_until(client, limit, n):
        """Posts ``n`` at a time until the limiter reads ``limit`` (a pair
        may coalesce into one dispatch, so windows fill at their own pace),
        then waits for the resize, which lands via a background task."""
        for _ in range(40):
            if lim.limit == limit:
                break
            await asyncio.gather(
                *(_timed_post(client, payload) for _ in range(n)))
        for _ in range(400):
            if server.batcher.max_in_flight == limit:
                break
            await asyncio.sleep(0.005)
        assert lim.limit == limit
        assert server.batcher.max_in_flight == limit

    payload = {"features": [1]}

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            assert server.batcher.max_in_flight == 2
            # learn the uncontended dispatch time, pairs in flight
            while server.batcher.batches_served < lim.window:
                await asyncio.gather(
                    *(_timed_post(client, payload) for _ in range(2)))
            assert lim.limit == 2 and lim.changes == 0
            algo.delay = 0.030  # overlapping dispatches slow each other
            await post_until(client, 1, 2)
            algo.delay = 0.010  # one at a time they are fast again
            await post_until(client, 2, 1)
            assert lim.changes == 2
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


def test_query_server_batch_slots_counter_reads_the_bound_undisturbed():
    """pio_serving_batch_slots_total adds the slot bound in force at each
    batch's assembly: over pio_serving_batches it is the mean number of
    slots the batches ran under, the bound itself on a server whose limiter
    never engaged."""
    server, storage = _mk_server(_StubAlgo())

    async def scrape(client):
        text = await (await client.get("/metrics")).text()
        out = {}
        for line in text.splitlines():
            name, _, value = line.partition(" ")
            if name in ("pio_serving_batch_slots_total",
                        "pio_serving_batches"):
                out[name] = float(value)
        return out

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            before = await scrape(client)
            for _ in range(5):
                resp = await client.post("/queries.json",
                                         json={"features": [1]})
                assert resp.status == 200
            after = await scrape(client)
            batches = (after["pio_serving_batches"]
                       - before["pio_serving_batches"])
            slots = (after["pio_serving_batch_slots_total"]
                     - before["pio_serving_batch_slots_total"])
            assert batches == 5
            assert slots / batches == server.batcher.max_in_flight == 2
        finally:
            await client.close()
            await server.shutdown()

    asyncio.run(t())
    storage.close()


# ---------------------------------------------------------------------------
# event server: per-client fairness + pressure-derived Retry-After
# ---------------------------------------------------------------------------

def _event_env(client_rate=0.0, client_burst=0.0, clk=None, **cfg_kw):
    from incubator_predictionio_tpu.data.storage import (
        AccessKey,
        App,
        Storage,
    )
    from incubator_predictionio_tpu.resilience.clock import SYSTEM_CLOCK
    from incubator_predictionio_tpu.server.event_server import (
        EventServer,
        EventServerConfig,
    )

    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    app_id = storage.get_meta_data_apps().insert(App(0, "ov-app"))
    storage.get_meta_data_access_keys().insert(
        AccessKey(key="keyA", app_id=app_id, events=()))
    storage.get_meta_data_access_keys().insert(
        AccessKey(key="keyB", app_id=app_id, events=()))
    server = EventServer(
        EventServerConfig(client_rate=client_rate, client_burst=client_burst,
                          **cfg_kw),
        storage, clock=clk or SYSTEM_CLOCK)
    return server, storage, app_id


EVENT = {"event": "rate", "entityType": "user", "entityId": "u1"}


def test_event_server_token_bucket_throttles_one_key_alone():
    clk = FakeClock()
    server, storage, app_id = _event_env(
        client_rate=2.0, client_burst=2.0, clk=clk)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            for _ in range(2):  # keyA's burst
                resp = await client.post("/events.json?accessKey=keyA",
                                         json=EVENT)
                assert resp.status == 201
            resp = await client.post("/events.json?accessKey=keyA",
                                     json=EVENT)
            assert resp.status == 429
            assert int(resp.headers["Retry-After"]) >= 1
            # keyB ingests untouched while keyA is in debt
            resp = await client.post("/events.json?accessKey=keyB",
                                     json=EVENT)
            assert resp.status == 201
            clk.advance(1.0)  # 2 tokens back at 2/s
            resp = await client.post("/events.json?accessKey=keyA",
                                     json=EVENT)
            assert resp.status == 201
            health = await (await client.get("/health")).json()
            fairness = health["admission"]["fairness"]
            assert fairness["enabled"] and fairness["throttled"] == 1
        finally:
            await client.close()
            await server.shutdown(flush_deadline_sec=0.1)

    asyncio.run(t())
    storage.close()


def test_event_server_batch_charged_per_item():
    clk = FakeClock()
    server, storage, app_id = _event_env(
        client_rate=10.0, client_burst=10.0, clk=clk)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            batch = [dict(EVENT, entityId=f"u{i}") for i in range(8)]
            resp = await client.post("/batch/events.json?accessKey=keyA",
                                     json=batch)
            assert resp.status == 200  # 8 of the 10-token burst
            resp = await client.post("/batch/events.json?accessKey=keyA",
                                     json=batch)
            assert resp.status == 429  # 2 tokens left < 8
            clk.advance(1.0)  # +10 tokens
            resp = await client.post("/batch/events.json?accessKey=keyA",
                                     json=batch)
            assert resp.status == 200
        finally:
            await client.close()
            await server.shutdown(flush_deadline_sec=0.1)

    asyncio.run(t())
    storage.close()


def test_event_server_throttled_requests_visible_in_stats():
    """429s must land in /stats.json like the 503 spill path does — a hot
    app's event count dropping with no per-app 429 tally reads as lost
    traffic, not rate enforcement."""
    clk = FakeClock()
    server, storage, app_id = _event_env(
        client_rate=1.0, client_burst=1.0, clk=clk, stats=True)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            resp = await client.post("/events.json?accessKey=keyA",
                                     json=EVENT)
            assert resp.status == 201
            resp = await client.post("/events.json?accessKey=keyA",
                                     json=EVENT)
            assert resp.status == 429
            cur = server.stats.get(app_id)["currentHour"]
            assert cur["status"]["429"] == 1
            assert cur["event"]["<throttled>"] == 1
        finally:
            await client.close()
            await server.shutdown(flush_deadline_sec=0.1)

    asyncio.run(t())
    storage.close()


def test_event_server_retry_after_hint_tracks_drain_rate():
    """The satellite: 503 Retry-After derives from spill depth ÷ observed
    drain throughput, with the static config value only as the no-signal
    fallback."""
    clk = FakeClock()
    server, storage, app_id = _event_env(clk=clk, retry_after_sec=7)
    try:
        assert server._retry_after_hint() == 1  # empty spill queue
        # 100 spilled events, no drain signal yet → static fallback
        import datetime as dt

        from incubator_predictionio_tpu.data.event import Event

        ev = Event(event="rate", entity_type="user", entity_id="u1",
                   creation_time=dt.datetime(2024, 1, 1,
                                             tzinfo=dt.timezone.utc))
        for _ in range(100):
            server._spill.append((ev, app_id, None, None))
        assert server._retry_after_hint() == 7
        # the drainer lands 25 events/sec → the hint becomes 100/25 = 4
        server._drain_rate.record(25)
        clk.advance(1.0)
        server._drain_rate.record(25)
        clk.advance(1.0)
        assert server._retry_after_hint() == 4
    finally:
        storage.close()


def test_event_server_503_carries_derived_retry_after():
    """End-to-end: breaker open + full spill queue → 503 whose Retry-After
    is the pressure-derived hint, not the config constant."""
    clk = FakeClock()
    server, storage, app_id = _event_env(
        clk=clk, spill_max=30, breaker_threshold=1, retry_after_sec=7)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            server._store_breaker.record_failure()  # breaker open
            for _ in range(30):  # spill queue at capacity
                server._spill.append((None, app_id, None, None))
            resp = await client.post("/events.json?accessKey=keyA",
                                     json=EVENT)
            assert resp.status == 503
            assert resp.headers["Retry-After"] == "7"  # fallback (no rate)
            # with a drain-rate signal the hint becomes pressure-derived:
            # depth 30 at an observed 10 events/sec → come back in 3s
            server._drain_rate.record(5)
            clk.advance(1.0)
            server._drain_rate.record(5)
            resp = await client.post("/events.json?accessKey=keyA",
                                     json=EVENT)
            assert resp.status == 503
            assert resp.headers["Retry-After"] == "3"
        finally:
            server._spill.clear()
            await client.close()
            await server.shutdown(flush_deadline_sec=0.1)

    asyncio.run(t())
    storage.close()


# ---------------------------------------------------------------------------
# storage server: per-client in-flight caps
# ---------------------------------------------------------------------------

def test_storage_server_per_client_inflight_cap():
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.server import storage_server as ss_mod
    from incubator_predictionio_tpu.server.storage_server import (
        StorageServer,
        StorageServerConfig,
    )

    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    server = StorageServer(StorageServerConfig(client_inflight=1), storage)
    gate = threading.Event()
    ss_mod._RPC[("test", "block")] = lambda s, a: gate.wait(timeout=10.0)

    async def t():
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            first = asyncio.create_task(
                client.post("/rpc/test/block", json={}))
            while not server._inflight_gate.snapshot()["inFlight"]:
                await asyncio.sleep(0.005)
            # same client, second concurrent RPC → capped
            resp = await client.post("/rpc/test/block", json={})
            assert resp.status == 429
            assert "Retry-After" in resp.headers
            health = await (await client.get("/health")).json()
            assert health["admission"]["throttled"] == 1
            assert health["admission"]["maxInFlightPerClient"] == 1
            gate.set()
            assert (await first).status == 200
            # the slot was released: the next RPC is admitted
            resp = await client.post("/rpc/test/block", json={})
            assert resp.status == 200
        finally:
            await client.close()
            await server.shutdown()

    try:
        asyncio.run(t())
    finally:
        del ss_mod._RPC[("test", "block")]
        storage.close()


def test_storage_server_client_key_separates_nat_sharers():
    """The in-flight cap keys on the client's self-reported process
    identity (``X-PIO-Client``, sent by remote.py), not the peer address
    alone — distinct query servers behind one proxy/NAT must each queue
    behind themselves, not behind each other."""
    from aiohttp.test_utils import make_mocked_request

    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.server.storage_server import (
        StorageServer,
        StorageServerConfig,
    )

    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    server = StorageServer(StorageServerConfig(client_inflight=1), storage)
    try:
        a = make_mocked_request("POST", "/rpc/x/y",
                                headers={"X-PIO-Client": "hostA:1"})
        b = make_mocked_request("POST", "/rpc/x/y",
                                headers={"X-PIO-Client": "hostB:2"})
        assert server._client_key(a) != server._client_key(b)
        # header-less callers (older clients, curl) still get a key
        assert server._client_key(make_mocked_request("POST", "/rpc/x/y"))
    finally:
        storage.close()


def test_storage_server_remote_aggregate_cap_bounds_identity_rotation():
    """X-PIO-Client is self-reported, so a client rotating identities
    per request never trips the per-identity gate — the per-address
    aggregate cap must bound it anyway."""
    from aiohttp.test_utils import make_mocked_request

    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.server.storage_server import (
        StorageServer,
        StorageServerConfig,
    )

    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    server = StorageServer(
        StorageServerConfig(client_inflight=1, remote_inflight=2), storage)
    try:
        reqs = [make_mocked_request("POST", "/rpc/x/y",
                                    headers={"X-PIO-Client": f"minted{i}"})
                for i in range(3)]  # same address, fresh identity each
        keys = [server._admit_rpc(r) for r in reqs]
        assert keys[0] is not None and keys[1] is not None
        assert keys[2] is None  # aggregate cap holds
        server._release_rpc(keys[0])
        assert server._admit_rpc(reqs[2]) is not None  # slot freed
    finally:
        storage.close()


def test_storage_server_inflight_disabled_with_zero():
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.server.storage_server import (
        StorageServer,
        StorageServerConfig,
    )

    storage = Storage({"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    server = StorageServer(StorageServerConfig(client_inflight=0), storage)
    assert not server._inflight_gate.enabled
    storage.close()


# ---------------------------------------------------------------------------
# satellites: re-export + the CLI health verb
# ---------------------------------------------------------------------------

def test_latency_reservoir_reexport_from_query_server():
    """The obs/ move must not break existing imports: the query-server
    name is the SAME class object."""
    from incubator_predictionio_tpu.server.query_server import (
        LatencyReservoir,
    )

    assert LatencyReservoir is ObsLatencyReservoir
    r = LatencyReservoir(capacity=4)
    for v in (0.1, 0.2, 0.3):
        r.record(v)
    assert r.percentiles()["p50"] == 0.2


def test_cli_health_verb_aggregates_and_exits_nonzero_on_red(monkeypatch,
                                                            capsys):
    from incubator_predictionio_tpu.tools import cli

    healths = {
        "http://e:7070": {"status": "ok", "draining": False,
                          "eventStoreBreaker": {"state": "closed"},
                          "spillQueueDepth": 0, "admission": {
                              "fairness": {"throttled": 0}}},
        "http://q:8000": {"status": "degraded", "draining": False,
                          "servingBreaker": {"state": "open"},
                          "algorithmBreakers": {
                              "algorithm:0:X": {"state": "closed"}},
                          "admission": {"brownoutActive": True,
                                        "rejected": 12, "shedExpired": 3}},
        "http://s:7072": {"status": "ok", "draining": False,
                          "backendBreakers": {},
                          "admission": {"throttled": 0}},
    }
    monkeypatch.setattr(cli, "_fetch_health",
                        lambda url, timeout=5.0: healths[url])
    args = cli.build_parser().parse_args(["health", *healths.keys()])
    rc = cli.cmd_health(args, None)
    out = capsys.readouterr().out
    assert rc == 1  # one red row → non-zero
    assert "BROWNOUT" in out and "rejected 12" in out and "shed 3" in out
    assert "servingBreaker" in out  # the open breaker is named
    # all-green fleet → exit 0
    healths["http://q:8000"] = {"status": "ok", "draining": False,
                                "servingBreaker": {"state": "closed"},
                                "admission": {}}
    rc = cli.cmd_health(args, None)
    assert rc == 0
    # an unreachable server is red
    monkeypatch.setattr(cli, "_fetch_health",
                        lambda url, timeout=5.0: (_ for _ in ()).throw(
                            OSError("refused")))
    rc = cli.cmd_health(args, None)
    assert rc == 1
    assert "unreachable" in capsys.readouterr().out
