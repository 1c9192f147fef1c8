"""Engine (query) server — the ``pio deploy`` surface.

Parity target: workflow/CreateServer.scala:106-695. One deployed engine per
server process; routes:

- ``GET /``              — status page (engine info + serving stats, the
                           reference's twirl HTML page becomes JSON/HTML)
- ``POST /queries.json`` — the hot path: bind query → supplement →
                           per-algorithm predict → serve → JSON
- ``POST /reload``       — re-load the latest COMPLETED instance (MasterActor
                           ReloadServer, CreateServer.scala:317-343)
- ``POST /stop``         — graceful shutdown (auth via server access key)
- ``GET /plugins.json``  — engine-server plugin listing

Design notes vs the reference:
- the reference calls algorithms sequentially per query with a "TODO:
  Parallelize" (CreateServer.scala:488); our predict path is a resident
  jit-compiled function per algorithm, and the (tiny) per-query host work is
  done inline — the TPU round-trip dominates, so the fix the reference never
  shipped is batching, which ``batch_predict`` exposes for bulk callers;
- models are made device-resident once at deploy (prepare_for_serving), not
  re-loaded per query;
- the optional feedback loop POSTs a ``predict`` event back to the event
  server asynchronously, with prId generation like CreateServer.scala:508-570.
"""

from __future__ import annotations

import asyncio
import contextvars
import dataclasses
import datetime as _dt
import hashlib
import json
import logging
import os
import time
import uuid
from typing import Any, Optional

from aiohttp import web

from incubator_predictionio_tpu.obs.http import (
    add_observability_routes,
    telemetry_middleware,
)
from incubator_predictionio_tpu.obs import profile as _profile
from incubator_predictionio_tpu.obs import trace as _trace
from incubator_predictionio_tpu.obs import slo as _slo
from incubator_predictionio_tpu.obs.metrics import (
    REGISTRY,
    LatencyReservoir,
)
from incubator_predictionio_tpu.resilience.admission import (
    BROWNOUT,
    REJECT,
    AdmissionConfig,
    AdmissionController,
    ShedExpired,
)
from incubator_predictionio_tpu.resilience.breaker import publish_breaker_metrics
from incubator_predictionio_tpu.streaming.stream_metrics import (
    APPLIED as _STREAM_APPLIED,
    DEDUPED as _STREAM_DEDUPED,
    STALENESS as _STREAM_STALENESS,
)

from incubator_predictionio_tpu.core.controller import (
    Engine,
    EngineParams,
    resolve_engine_factory,
    variant_from_file,
)
from incubator_predictionio_tpu.data.storage.base import EngineInstance
from incubator_predictionio_tpu.data.storage.registry import Storage, get_storage
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.resilience.breaker import (
    BREAKERS,
    CircuitBreaker,
    CircuitOpenError,
)
from incubator_predictionio_tpu.resilience.clock import SYSTEM_CLOCK, Clock
from incubator_predictionio_tpu.resilience.policy import (
    DeadlineExceeded,
    ServingUnavailable,
    run_with_deadline,
)
from incubator_predictionio_tpu.server.lifecycle import (
    DrainState,
    drained_exit_deadline,
    install_signal_drain,
    wait_for,
)
from incubator_predictionio_tpu.utils import jitstats
from incubator_predictionio_tpu.utils.json_util import bind_query, to_jsonable
from incubator_predictionio_tpu.utils.serialization import deserialize_model

logger = logging.getLogger(__name__)

# -- telemetry (obs/, docs/observability.md) --------------------------------
_DEGRADED = REGISTRY.counter(
    "pio_serving_degraded_total",
    "Queries answered from the degradation path (last-good cache / serving "
    "default) instead of a live prediction")
_G_REQUESTS = REGISTRY.gauge(
    "pio_serving_requests", "Successfully served queries (this process)")
_G_BATCHES = REGISTRY.gauge(
    "pio_serving_batches", "Micro-batches dispatched to the device")
_G_MAX_BATCH = REGISTRY.gauge(
    "pio_serving_max_batch_seen", "Largest micro-batch coalesced so far")
_BATCH_SLOTS = REGISTRY.counter(
    "pio_serving_batch_slots_total",
    "Dispatch-slot bound in force at each micro-batch's assembly, summed: "
    "its growth over pio_serving_batches' is the mean number of slots the "
    "batches ran under (docs/resilience.md \"Adaptive concurrency\")")
_G_LATENCY_Q = REGISTRY.gauge(
    "pio_serving_latency_seconds",
    "Serving latency split into its terms (exact reservoir quantiles)",
    labels=("stage", "quantile"))
_G_DEV_MEM = REGISTRY.gauge(
    "pio_device_bytes_in_use",
    "Accelerator memory in use (the device_memory_report fold)",
    labels=("device",))
_ROLLBACKS = REGISTRY.counter(
    "pio_deploy_rollbacks_total",
    "Reloads rejected by the smoke-query gate or auto-rolled back during "
    "the post-swap probation window (docs/resilience.md)")
#: the serve bucket ladder: the batch-size histogram's edges and the
#: ``bucket`` attribute of a batch's spans
_BATCH_EDGES = (1, 2, 4, 8, 16, 32, 64, 128, 256)
_H_TEMPLATE_BATCH = REGISTRY.histogram(
    "pio_serving_template_batch_size",
    "Live queries per coalesced batch_predict dispatch, per algorithm class "
    "— proves the micro-batcher's coalescing reaches the vectorized "
    "template paths (docs/serving.md)",
    labels=("template",), buckets=tuple(map(float, _BATCH_EDGES)))


def _batch_attrs(n: int) -> dict:
    """A batch's span attributes: its live size and the ladder edge it
    falls under."""
    return {"batch": n,
            "bucket": next((e for e in _BATCH_EDGES if n <= e), n)}

#: per-algorithm wall times of the current dispatch, set by ``predict_batch``
#: and read back from the SAME Context object after ``Context.run`` returns
#: (writes inside ``ctx.run`` persist in ``ctx``) — per-dispatch state with
#: no shared attribute, so overlapping dispatches can never swap timings
_DISPATCH_ALGO_TIMES: contextvars.ContextVar[list] = contextvars.ContextVar(
    "pio_dispatch_algo_times")


@dataclasses.dataclass
class ServerConfig:
    """(CreateServer.scala:106-175 flags)"""

    engine_variant: str = "engine.json"
    ip: str = "0.0.0.0"
    port: int = 8000
    feedback: bool = False
    ssl_cert: Optional[str] = None  # TLS (reference SSLConfiguration.scala:30)
    ssl_key: Optional[str] = None
    event_server_ip: str = "127.0.0.1"
    event_server_port: int = 7070
    access_key: Optional[str] = None  # for feedback events
    server_access_key: Optional[str] = None  # guards /stop and /reload
    max_batch: int = 64  # micro-batch cap for /queries.json (1 = no batching)
    # concurrent dispatches (host prep overlaps device time). None = auto:
    # overlap (2) only when every deployed algorithm declares
    # ``serving_thread_safe``; otherwise strict predict_batch serialization
    # (1) — custom engines with non-thread-safe predict code must never race
    # by default. An explicit int overrides in either direction.
    max_in_flight: Optional[int] = None
    log_url: Optional[str] = None  # remote error-log shipping (CreateServer.scala:423-436)
    log_prefix: str = ""  # prepended to shipped log messages
    # -- graceful degradation (resilience/) -------------------------------
    # total per-query budget: a query still unanswered after this many
    # seconds gets a degraded-but-valid response (last-good cache or the
    # serving layer's default), never a 500. Also propagated to storage
    # calls under the predict path via deadline_scope. None disables.
    query_timeout_sec: Optional[float] = None
    # per-algorithm deadline: an algorithm slower than this counts a
    # breaker failure even when it eventually answers. None disables.
    algo_deadline_sec: Optional[float] = None
    # consecutive failures before an algorithm's breaker opens, and how
    # long it stays open before a half-open probe
    algo_breaker_threshold: int = 3
    algo_breaker_reset_sec: float = 10.0
    # -- crash-safe model lifecycle (docs/resilience.md) ------------------
    # smoke queries the /reload health gate runs against the NEW instance
    # before it may serve: any exception keeps the live instance and
    # answers 409. Payload dicts, exactly as POSTed to /queries.json.
    smoke_queries: tuple = ()
    # seconds after a successful swap during which a serving-breaker trip
    # auto-rolls back to the previous (pinned) instance; 0 disables
    reload_probation_sec: float = 30.0
    # -- overload protection (resilience/admission.py) --------------------
    # bounded admission queue: queries beyond this many waiting requests
    # are rejected at the door with 429 + pressure-derived Retry-After
    # (docs/resilience.md "Overload & admission control")
    admission_max_queue: int = dataclasses.field(
        default_factory=lambda: int(
            os.environ.get("PIO_ADMISSION_MAX_QUEUE", "256")))
    # adaptive concurrency limiter: AIMD on the time a batch's dispatch
    # holds its slot, live-resizes the micro-batcher's dispatch slots
    # within [1, effective max]
    admission_adaptive: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "PIO_ADMISSION_ADAPTIVE", "1") != "0")
    # explicit target for a batch's dispatch time (ms); unset = gradient
    # mode (the target tracks a rolling-minimum dispatch-time baseline)
    admission_target_ms: Optional[float] = dataclasses.field(
        default_factory=lambda: (
            float(os.environ["PIO_ADMISSION_TARGET_MS"])
            if os.environ.get("PIO_ADMISSION_TARGET_MS") else None))
    # brownout hysteresis: saturation (predicted wait ≥ enter_frac of the
    # deadline) sustained for enter_sec flips the server to the degraded
    # path; exit needs exit_sec of clear air
    brownout_enter_frac: float = dataclasses.field(
        default_factory=lambda: float(
            os.environ.get("PIO_BROWNOUT_ENTER_FRAC", "0.5")))
    brownout_enter_sec: float = dataclasses.field(
        default_factory=lambda: float(
            os.environ.get("PIO_BROWNOUT_ENTER_SEC", "1.0")))
    brownout_exit_sec: float = dataclasses.field(
        default_factory=lambda: float(
            os.environ.get("PIO_BROWNOUT_EXIT_SEC", "2.0")))
    # -- multi-host shard ownership (docs/sharding.md) --------------------
    # when both are set this process serves only item rows
    # ShardSpec(n_items, shard_count).shard_bounds(shard_id) via
    # POST /shard/queries.json partials, announced through
    # /health.deployment.shardOwner for the fleet router's scatter/gather
    shard_id: Optional[int] = dataclasses.field(
        default_factory=lambda: (
            int(os.environ["PIO_FLEET_SHARD_ID"])
            if os.environ.get("PIO_FLEET_SHARD_ID") else None))
    shard_count: Optional[int] = dataclasses.field(
        default_factory=lambda: (
            int(os.environ["PIO_FLEET_SHARD_COUNT"])
            if os.environ.get("PIO_FLEET_SHARD_COUNT") else None))
    # where the owner's fencing epoch persists (atomic-write discipline);
    # unset = in-memory epoch only (tests, throwaway owners)
    shard_state_dir: Optional[str] = dataclasses.field(
        default_factory=lambda: (
            os.environ.get("PIO_FLEET_SHARD_STATE_DIR") or None))


class DeployedEngine:
    """Holds the live models + stages for one engine instance."""

    def __init__(
        self,
        engine: Engine,
        engine_params: EngineParams,
        instance: EngineInstance,
        models: list[Any],
        max_batch: int = 64,
        warmup: bool = True,
        algo_deadline: Optional[float] = None,
        breaker_threshold: int = 3,
        breaker_reset: float = 10.0,
        clock: Clock = SYSTEM_CLOCK,
    ):
        self.engine = engine
        self.engine_params = engine_params
        self.instance = instance
        algorithms, serving = engine.serving_and_algorithms(engine_params)
        self.algorithms = algorithms
        self.serving = serving
        self.models = [
            self._prepare(a, m) for a, m in zip(algorithms, models)
        ]
        self.query_cls = next(
            (a.query_class() for a in algorithms if a.query_class() is not None), None
        )
        # per-algorithm circuit breakers: a consistently failing (or, with
        # algo_deadline set, consistently slow) algorithm is skipped and the
        # remaining algorithms keep serving — not registered in the global
        # BREAKERS registry because their lifetime is this deployment's
        # (reload/tests build fresh engines; /health composes both views)
        self.algo_deadline = algo_deadline
        self._clock = clock
        self.algo_breakers = [
            CircuitBreaker(f"algorithm:{i}:{type(a).__name__}",
                           failure_threshold=breaker_threshold,
                           reset_timeout=breaker_reset, clock=clock)
            for i, a in enumerate(algorithms)
        ]
        if warmup:
            self.warmup(max_batch)

    @staticmethod
    def _prepare(algorithm, model):
        """Models exposing ``prepare_for_serving()`` become device-resident here."""
        prep = getattr(model, "prepare_for_serving", None)
        return prep() if callable(prep) else model

    def warmup(self, max_batch: int) -> None:
        """Pre-compile every serving batch bucket at deploy time so no live
        query ever pays an XLA compile (the round-2 p50 regression)."""
        for m in self.models:
            w = getattr(m, "warmup", None)
            if callable(w):
                w(max_batch)

    def _record_algo_timing(self, idx: int, took: float) -> None:
        """Success bookkeeping with the per-algorithm deadline: a completed
        call slower than the deadline still counts as a breaker failure —
        an algorithm that keeps blowing its budget should be skipped, not
        waited on."""
        brk = self.algo_breakers[idx]
        if self.algo_deadline is not None and took > self.algo_deadline:
            brk.record_failure()
        else:
            brk.record_success()

    def _record_batch_outcome(self, ai: int, results: dict[int, Any],
                              took: float, single_call: bool) -> None:
        """Breaker verdict for one algorithm's share of a batch: healthy if
        ANY query got a prediction, healthy if every failure is
        query-semantic (bad queries, not a bad algorithm), failing only
        when every query died with an infrastructure-class error."""
        vals = list(results.values())
        if any(not isinstance(v, Exception) for v in vals):
            if single_call:
                self._record_algo_timing(ai, took)
            else:
                self.algo_breakers[ai].record_success()
        elif vals and all(isinstance(v, (TypeError, ValueError, KeyError))
                          for v in vals):
            self.algo_breakers[ai].record_success()
        else:
            self.algo_breakers[ai].record_failure()

    def _live_algorithms(self) -> list[int]:
        live = [i for i in range(len(self.algorithms))
                if self.algo_breakers[i].allow()]
        if not live:
            raise ServingUnavailable(
                "all algorithms have open circuit breakers")
        return live

    def predict(self, payload: dict) -> Any:
        query = bind_query(self.query_cls, payload)
        query = self.serving.supplement(query)
        predictions = []
        live = self._live_algorithms()
        # _live_algorithms admitted a (possibly half-open-probe) slot on
        # EVERY live breaker; if an early algorithm raises, the later ones
        # never get an outcome — hand their slots back or they wedge
        pending = set(live)
        try:
            for i in live:
                t0 = self._clock.monotonic()
                try:
                    predictions.append(
                        self.algorithms[i].predict(self.models[i], query))
                except (TypeError, ValueError, KeyError):
                    # query-semantic rejection (unknown entity, bad shape):
                    # the algorithm is healthy — a run of bad queries must
                    # not trip its breaker and degrade everyone's traffic
                    pending.discard(i)
                    self.algo_breakers[i].record_success()
                    raise
                except Exception:
                    pending.discard(i)
                    self.algo_breakers[i].record_failure()
                    raise
                pending.discard(i)
                self._record_algo_timing(i, self._clock.monotonic() - t0)
        finally:
            for j in pending:
                self.algo_breakers[j].release_probe()
        return self.serving.serve(query, predictions)

    def predict_batch(self, payloads: list[dict]) -> list[Any]:
        """Batched predict: one ``batch_predict`` device dispatch per
        algorithm instead of one per query — the fix for the reference's
        unshipped 'TODO: Parallelize' (CreateServer.scala:488). Returns one
        result OR exception per payload (bad queries don't fail the batch).

        Degradation semantics (resilience/): algorithms whose breaker is
        open are skipped; an algorithm that raises is retried query-by-query
        so a poison query fails alone, and a breaker failure is counted only
        when an algorithm fails every query with an infrastructure-class
        error (backend down, model broken) — all-semantic failures (bad
        queries) leave the breaker alone.
        Queries are served from whichever algorithms survived; a query no
        algorithm could answer carries its first error."""
        # the batch's whole life in the worker thread: serve.batch.dispatch
        # minus this is the thread hand-over and the loop's wake-up
        with _trace.span("serve.batch.predict", **_batch_attrs(len(payloads))):
            return self._predict_batch(payloads)

    def _predict_batch(self, payloads: list[dict]) -> list[Any]:
        out: list[Any] = [None] * len(payloads)
        bound: list[Any] = [None] * len(payloads)
        for i, p in enumerate(payloads):
            try:
                bound[i] = self.serving.supplement(bind_query(self.query_cls, p))
            except (TypeError, ValueError, KeyError) as e:
                out[i] = e
        live = [i for i in range(len(payloads)) if out[i] is None]
        if not live:
            return out
        try:
            algo_live = self._live_algorithms()
        except ServingUnavailable as e:
            for i in live:
                out[i] = e
            return out
        per_algo: dict[int, dict[int, Any]] = {}  # algo idx -> query idx -> pred/exc
        algo_times: list[tuple[str, float]] = []
        for ai in algo_live:
            a, m = self.algorithms[ai], self.models[ai]
            _H_TEMPLATE_BATCH.labels(template=type(a).__name__).observe(
                len(live))
            t0 = self._clock.monotonic()
            healed = False
            try:
                got = dict(a.batch_predict(m, [(i, bound[i]) for i in live]))
                for i in live:
                    if i not in got:
                        # sparse batch result: heal per query (the pre-
                        # resilience code recovered this case through its
                        # KeyError → retry-all path)
                        healed = True
                        try:
                            got[i] = a.predict(m, bound[i])
                        except Exception as e:  # noqa: BLE001
                            got[i] = e
                per_algo[ai] = {i: got[i] for i in live}
            except Exception:  # noqa: BLE001 - isolate the failing query
                # a query may have poisoned the whole batch: retry one by
                # one so only the offender fails
                healed = True
                singles: dict[int, Any] = {}
                for i in live:
                    try:
                        singles[i] = a.predict(m, bound[i])
                    except Exception as e:  # noqa: BLE001
                        singles[i] = e
                per_algo[ai] = singles
            took = self._clock.monotonic() - t0
            algo_times.append((f"algo{ai}.{type(a).__name__}", took))
            self._record_batch_outcome(
                ai, per_algo[ai], took,
                # the per-call deadline is only meaningful when the elapsed
                # time WAS one call: a single-query batch with no heals.
                # Judging it against a coalesced N-query dispatch (or a
                # batch attempt plus N retries) would brand a healthy
                # algorithm slow exactly under peak load
                single_call=(len(live) == 1 and not healed))
        # the per-batch cost is per-dispatch state (a coalesced batch shares
        # one device round trip): publish via the dispatch's own context so
        # overlapping dispatches cannot swap each other's timings
        _DISPATCH_ALGO_TIMES.set(algo_times)
        for i in live:
            preds, first_err = [], None
            for ai in algo_live:
                v = per_algo[ai][i]
                if isinstance(v, Exception):
                    first_err = first_err or v
                else:
                    preds.append(v)
            if not preds:
                out[i] = first_err or ServingUnavailable(
                    "no algorithm produced a prediction")
                continue
            try:
                out[i] = self.serving.serve(bound[i], preds)
            except Exception as e:  # noqa: BLE001
                out[i] = e
        return out


def _run_under(parent: "_trace.SpanContext", fn, *args):
    """``fn(*args)`` with ``parent`` as the ambient span (worker-thread side
    of a span that crossed the executor hop)."""
    with _trace.trace_scope(parent):
        return fn(*args)


class _Delivered:
    """Marker wrapper the dispatcher resolves futures with: the payload's
    result plus the batch's per-algorithm timings. A distinct type (not a
    tuple) so a prediction that happens to BE a tuple can never be mistaken
    for the envelope; error paths deliver bare exceptions."""

    __slots__ = ("result", "algo_times", "resolved_at")

    def __init__(self, result: Any, algo_times: list, resolved_at: float):
        self.result = result
        self.algo_times = algo_times
        #: ``perf_counter`` when the batch's futures were resolved: where
        #: each request's ``serve.request.respond`` starts
        self.resolved_at = resolved_at


class MicroBatcher:
    """Continuous micro-batching for the query hot path.

    Requests enqueue; a single drainer coalesces everything that arrived
    while the previous batch was on the device into ONE ``predict_batch``
    dispatch (capped at ``max_batch``). No artificial wait is added — an idle
    server serves single queries at single-query latency, a loaded server
    amortizes the device round-trip across the whole in-flight window. The
    batch executes in a worker thread so the event loop keeps accepting
    requests mid-dispatch, and up to ``max_in_flight`` batches overlap: the
    next batch's host prep (query binding, padding, bucketing) runs while
    the previous one computes — a burst no longer serializes host work
    behind device work (the round-3 p99 tail, VERDICT r3 #6).

    Tail observability: ``queue_delay`` (submit → batch assembly) and
    ``dispatch`` (assembly → results) reservoirs split the latency into its
    two terms; both are exposed on the status page.

    Occupancy: the batcher counts the requests it holds, from the enqueue
    until the entry leaves it (its batch merged or failed, shed or dropped
    at assembly, drained by ``stop()``; a waiter that gave up is held until
    then too: its entry still rides the queue or the dispatch), and books
    the time it holds none as span ``serve.server.empty`` and the rest as
    ``serve.server.occupied`` (``obs/trace.Track``; the first also lies on
    the profiler's timeline). A request pays one integer add, a batch one
    subtraction. ``occupied ÷ (occupied + empty)`` between two scrapes of
    ``pio_profile_phase_seconds_total`` is the server's utilisation.

    Overload protection (resilience/admission.py): each request is tagged
    with its deadline at enqueue; batch assembly evicts entries whose
    deadline already expired (their futures resolve :class:`ShedExpired`
    → 504) instead of wasting a device dispatch on work nobody is waiting
    for. Deadline decisions run on the injected clock, so they are
    deterministic under ``FakeClock``.
    """

    def __init__(self, deployed: DeployedEngine, max_batch: int = 64,
                 max_in_flight: int = 2,
                 deadline_sec: Optional[float] = None,
                 clock: Clock = SYSTEM_CLOCK,
                 admission: Optional[AdmissionController] = None):
        self.deployed = deployed
        self.max_batch = max_batch
        self.max_in_flight = max_in_flight
        # per-batch budget, propagated into the worker thread as the
        # ambient deadline so storage calls under predict inherit it
        self.deadline_sec = deadline_sec
        self._clock = clock
        # shed bookkeeping, and the adaptive limiter that each clean
        # dispatch's duration feeds (may be None)
        self._admission = admission
        self.queue: asyncio.Queue = asyncio.Queue()
        self.batches_served = 0
        self.max_batch_seen = 0
        self.shed_expired = 0
        self.queue_delay = LatencyReservoir()
        self.dispatch_sec = LatencyReservoir()
        self._task: Optional[asyncio.Task] = None
        self._sem: Optional[asyncio.Semaphore] = None
        self._inflight: set[asyncio.Task] = set()
        self._resizes: set[asyncio.Task] = set()  # strong refs
        self._stopped = False
        #: requests held: enqueued, entry not yet merged, shed or drained
        #: (the loop's thread only)
        self.held = 0
        self._occupancy = _trace.Track("serve.server", timeline=("empty",))

    def start(self) -> None:
        if self._stopped:
            raise RuntimeError("server shutting down")
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._drain())
            self._occupancy.switch("empty")

    async def stop(self) -> None:
        """Cancel the drainer and fail everything still queued so callers
        don't hang until aiohttp force-cancels them."""
        self._stopped = True
        # a shrink could be parked on the dispatch semaphore; nothing will
        # ever need the smaller bound again
        for task in list(self._resizes):
            task.cancel()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        drained = 0
        while True:
            try:
                entry = self.queue.get_nowait()
            except asyncio.QueueEmpty:
                break
            drained += 1
            fut = entry[1]
            if not fut.done():
                fut.set_result(RuntimeError("server shutting down"))
        # (the dispatches in flight released theirs as the drainer ended)
        self._release(drained)
        self._occupancy.switch(None)

    def _release(self, n: int) -> None:
        """``n`` held entries left the batcher (after ``stop()`` closed the
        track, nothing reopens it)."""
        self.held -= n
        if n and not self.held and self._occupancy.phase is not None:
            self._occupancy.switch("empty")

    async def submit(self, payload: dict) -> Any:
        return (await self.submit_timed(payload))[0]

    async def submit_timed(self, payload: dict) -> tuple[Any, list, float]:
        """Submit and also return the dispatch's per-algorithm wall times
        (the X-PIO-Server-Timing source) and the instant the batch resolved
        this request's future — per-call data, never read off shared state,
        so overlapping dispatches can't swap timings."""
        self.start()
        fut = asyncio.get_running_loop().create_future()
        # deadline tagged at enqueue (docs/resilience.md shedding order):
        # batch assembly evicts this entry with ShedExpired once it passes
        deadline_at = (self._clock.monotonic() + self.deadline_sec
                       if self.deadline_sec is not None else None)
        # carry the submitter's contextvars (trace identity from the
        # telemetry middleware) — the dispatch worker thread re-enters the
        # first request's context so storage calls under predict stay on the
        # caller's trace (coalesced followers share that dispatch span)
        await self.queue.put((payload, fut, time.perf_counter(),
                              contextvars.copy_context(), deadline_at))
        self.held += 1
        if self.held == 1:
            self._occupancy.switch("occupied")
        try:
            got = await fut
        except asyncio.CancelledError:
            # the waiter is gone (handler timeout/disconnect): mark the
            # queued entry abandoned so assembly drops it silently instead
            # of counting it as a shed the caller never saw
            fut.cancel()
            raise
        if isinstance(got, _Delivered):
            result, algo_times, resolved_at = (
                got.result, got.algo_times, got.resolved_at)
        else:  # error paths deliver bare exceptions
            result, algo_times, resolved_at = got, [], time.perf_counter()
        if isinstance(result, Exception):
            raise result
        return result, algo_times, resolved_at

    async def resize(self, n: int) -> None:
        """Resize the dispatch-slot semaphore live (reload can swap in an
        engine with a different thread-safety posture; the adaptive
        admission limiter shrinks/grows it under load). Growing releases
        slots immediately; shrinking acquires the excess — waiting out
        in-flight dispatches — so the new bound is real, not advisory."""
        n = max(1, n)
        delta = n - self.max_in_flight
        self.max_in_flight = n
        if self._sem is None or delta == 0:  # drainer not started yet
            return
        if delta > 0:
            for _ in range(delta):
                self._sem.release()
        else:
            for _ in range(-delta):
                await self._sem.acquire()

    async def _drain(self) -> None:
        loop = asyncio.get_running_loop()
        sem = self._sem = asyncio.Semaphore(self.max_in_flight)
        try:
            while True:
                # slot FIRST, assemble SECOND: requests that arrive while we
                # wait for a free dispatch slot coalesce into this batch
                # (assembling first would both under-fill the batch and
                # strand dequeued futures if stop() cancels at the acquire)
                await sem.acquire()
                try:
                    batch = [await self.queue.get()]
                except asyncio.CancelledError:
                    sem.release()
                    raise
                # the batch's spans hang under the first request's trace
                # (coalesced followers share them), each request's queue
                # wait under its own — docs/observability.md "Profiling"
                lead = _trace.context_of(batch[0][3])
                with _trace.trace_scope(lead):
                    with _trace.span("serve.batch.assemble") as sp:
                        while len(batch) < self.max_batch:
                            try:
                                batch.append(self.queue.get_nowait())
                            except asyncio.QueueEmpty:
                                break
                        sp.attrs.update(_batch_attrs(len(batch)))
                        now = time.perf_counter()
                        taken, batch = batch, self._evict_expired(batch)
                    self._release(len(taken) - len(batch))
                    for entry in taken:
                        self.queue_delay.record(now - entry[2])
                        _trace.record_span(
                            "serve.request.queue", entry[2], now - entry[2],
                            context=_trace.context_of(entry[3]))
                if not batch:
                    # the whole assembly was dead on arrival: no dispatch,
                    # hand the slot back and keep draining
                    sem.release()
                    continue
                self.batches_served += 1
                _BATCH_SLOTS.inc(self.max_in_flight)
                self.max_batch_seen = max(self.max_batch_seen, len(batch))
                task = loop.create_task(self._dispatch(loop, batch))
                self._inflight.add(task)
                task.add_done_callback(self._inflight.discard)
                task.add_done_callback(lambda _t: sem.release())
        except asyncio.CancelledError:
            # stop() cancelled the drainer; in-flight dispatch tasks must
            # still resolve their futures — cancel and await them
            for task in list(self._inflight):
                task.cancel()
            for task in list(self._inflight):
                try:
                    await task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            raise

    def _evict_expired(self, batch: list) -> list:
        """Deadline-aware shedding at batch-assembly time (the 504-evict
        step of the shedding order): entries whose deadline passed while
        they queued resolve ShedExpired instead of riding the dispatch —
        the caller already timed out, and dead work on the device would
        only inflate every live request's tail."""
        now = self._clock.monotonic()
        live = []
        shed = 0
        for entry in batch:
            # entries are (payload, fut, t_enq, ctx, deadline_at); tests
            # that inject raw 4-tuples simply have no deadline
            if entry[1].done():
                # abandoned (waiter cancelled/answered already): drop
                # without dispatching AND without shed bookkeeping — the
                # caller never saw a 504, and phantom counts would inflate
                # the service-rate estimate the 429 gate trusts
                continue
            deadline_at = entry[4] if len(entry) > 4 else None
            if deadline_at is not None and now >= deadline_at:
                shed += 1
                entry[1].set_result(ShedExpired(
                    "deadline expired before dispatch"))
            else:
                live.append(entry)
        if shed:
            self.shed_expired += shed
            if self._admission is not None:
                self._admission.on_shed_expired(shed)
        return live

    async def _dispatch(self, loop, batch) -> None:
        payloads = [entry[0] for entry in batch]
        # run_in_executor does not copy contextvars — run_with_deadline
        # re-establishes the deadline scope inside the worker thread, and
        # entering the first request's captured context carries its trace
        # identity across the thread hop (each request's context is captured
        # once at submit, so it is never entered twice)
        ctx = batch[0][3]
        lead = _trace.context_of(ctx)
        # crosses the await: ring and aggregate only. The worker re-enters
        # the span's identity, so what predict_batch opens hangs under it
        attrs = _batch_attrs(len(batch))
        dispatch = _trace.span("serve.batch.dispatch", thread_scoped=False,
                               **attrs)
        try:
            with _trace.trace_scope(lead), dispatch as sp:
                results = await loop.run_in_executor(
                    None, ctx.run, _run_under, sp.context,
                    run_with_deadline, self.deadline_sec,
                    self.deployed.predict_batch, payloads
                )
        except asyncio.CancelledError:
            # cancelled mid-dispatch: these futures are already dequeued, so
            # the queue-drain in stop() can't see them — fail them here or
            # their callers hang forever
            for entry in batch:
                if not entry[1].done():
                    entry[1].set_result(RuntimeError("server shutting down"))
            self._release(len(batch))
            raise
        except Exception as e:  # noqa: BLE001 - keep serving
            results = [e] * len(batch)
        self.dispatch_sec.record(sp.duration)
        # predict_batch published its per-algorithm times inside ctx; writes
        # made under Context.run persist in the Context object
        algo_times = ctx.get(_DISPATCH_ALGO_TIMES, [])
        delivered = False
        with _trace.trace_scope(lead), \
                _trace.span("serve.batch.merge", **attrs):
            resolved_at = time.perf_counter()
            for entry, r in zip(batch, results):
                if not entry[1].done():
                    delivered = True
                    entry[1].set_result(
                        _Delivered(r, algo_times, resolved_at))
        # (after merge's block: the empty interval's annotation must not
        # open inside another span's)
        self._release(len(batch))
        # the limiter sizes the dispatches in flight, so what it observes is
        # how long this one held its slot. Only a clean dispatch somebody
        # waited out says that: a failed one, one that rejected or healed a
        # query, or one whose callers all gave up first feeds nothing
        if (self._admission is not None and delivered
                and not any(isinstance(r, Exception) for r in results)):
            limit = self._admission.on_dispatch(sp.duration)
            if limit is not None and limit != self.max_in_flight:
                # off the dispatch's own path: a shrink waits out whichever
                # dispatch holds the slot it takes away
                task = loop.create_task(self.resize(limit))
                self._resizes.add(task)
                task.add_done_callback(self._resizes.discard)


# LatencyReservoir moved to obs/metrics.py (it is a general primitive the
# admission limiter needs too); imported above and re-exported here so
# existing ``from ...query_server import LatencyReservoir`` keeps working.


def load_deployed_engine(
    config: ServerConfig,
    storage: Optional[Storage] = None,
    ctx: Optional[MeshContext] = None,
    warmup: bool = True,
) -> DeployedEngine:
    """variant → engine factory → latest COMPLETED instance → live models
    (createServerActorWithEngine, CreateServer.scala:187-246)."""
    storage = storage or get_storage()
    ctx = ctx or MeshContext.create()
    variant = variant_from_file(config.engine_variant)
    factory_path = variant["engineFactory"]
    engine = resolve_engine_factory(factory_path)()
    engine_params = engine.engine_params_from_variant(variant)
    import os

    # deploy.* spans (docs/observability.md "Profiling"): load here and at
    # the model's sidecar, then restore | quantize | ensure_host | index |
    # warmup inside the model's own load / prepare_for_serving / warmup
    with _trace.span("deploy.load", part="instance"):
        instances = storage.get_meta_data_engine_instances()
        instance = instances.get_latest_completed(
            variant.get("id", "default"), variant.get("version", "1"),
            os.path.abspath(config.engine_variant),
        )
        if instance is None:
            raise RuntimeError(
                f"No COMPLETED engine instance for variant {config.engine_variant}; "
                "run train first (reference: CreateServer.scala:199 'Invalid engine instance')"
            )
        blob = storage.get_model_data_models().get(instance.id)
        if blob is None:
            raise RuntimeError(f"model blob missing for instance {instance.id}")
        persisted = deserialize_model(blob.models)
    models = engine.prepare_deploy(ctx, engine_params, persisted, instance.id)
    logger.info("deployed engine instance %s (trained %s)", instance.id,
                instance.start_time)
    return DeployedEngine(engine, engine_params, instance, models,
                          max_batch=config.max_batch, warmup=warmup,
                          algo_deadline=config.algo_deadline_sec,
                          breaker_threshold=config.algo_breaker_threshold,
                          breaker_reset=config.algo_breaker_reset_sec)


def effective_max_in_flight(config: ServerConfig, deployed: DeployedEngine) -> int:
    """Resolve ``ServerConfig.max_in_flight``'s auto (None) mode.

    max_batch=1 means "no batching" and keeps its historical strict
    serialization of user predict code regardless; otherwise overlap is only
    enabled automatically when every deployed algorithm opted in via
    ``serving_thread_safe`` (BaseAlgorithm)."""
    if config.max_batch == 1:
        return 1
    if config.max_in_flight is not None:
        return max(1, config.max_in_flight)
    safe = all(getattr(a, "serving_thread_safe", False)
               for a in deployed.algorithms)
    return 2 if safe else 1


class QueryServer:
    def __init__(
        self,
        config: ServerConfig,
        storage: Optional[Storage] = None,
        ctx: Optional[MeshContext] = None,
        deployed: Optional[DeployedEngine] = None,
        clock: Clock = SYSTEM_CLOCK,
        name: str = "query_server",
    ):
        self.config = config
        self.name = name
        self._clock = clock
        self.storage = storage or get_storage()
        self.ctx = ctx or MeshContext.create()
        # durable span export + sampling (obs/spool.py): applies the
        # PIO_TRACE_* env state; a no-op unless the spool dir is set.
        # Only the process front (the default name) configures the
        # process-wide planes — per-tenant cores hosted by a
        # TenantRegistry (server/tenancy.py) must not re-arm them on
        # every cold load
        if name == "query_server":
            from incubator_predictionio_tpu.obs import spool as trace_spool
            from incubator_predictionio_tpu.obs.plane import (
                configure_perf_plane_from_env,
            )

            trace_spool.configure_export_from_env("query_server")
            # continuous performance plane: procstats + profiler + metrics
            # history + SLO burn-rate engine (obs/plane.py)
            configure_perf_plane_from_env("query_server")
        # an explicit DeployedEngine skips storage loading (tests inject
        # hand-built engines to script failure modes)
        self.deployed = deployed or load_deployed_engine(
            config, self.storage, self.ctx)
        # -- overload protection (resilience/admission.py) ----------------
        # the door policy for sheddable query traffic: bounded queue +
        # deadline feasibility (429), brownout (degraded 200s), and the
        # adaptive concurrency limiter that live-resizes dispatch slots.
        # Health/metrics/reload are separate always-admitted routes.
        self._admission = AdmissionController(
            AdmissionConfig(
                max_queue=config.admission_max_queue,
                deadline_sec=config.query_timeout_sec,
                adaptive=config.admission_adaptive,
                max_inflight=effective_max_in_flight(config, self.deployed),
                target_latency_sec=(
                    config.admission_target_ms / 1e3
                    if config.admission_target_ms is not None else None),
                brownout_enter_frac=config.brownout_enter_frac,
                brownout_enter_sec=config.brownout_enter_sec,
                brownout_exit_sec=config.brownout_exit_sec,
            ), clock=clock, server=name)
        self.batcher = MicroBatcher(
            self.deployed, max_batch=config.max_batch,
            max_in_flight=effective_max_in_flight(config, self.deployed),
            deadline_sec=config.query_timeout_sec,
            clock=clock, admission=self._admission,
        )
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        self.latency = LatencyReservoir()
        # -- graceful degradation state (resilience/) ---------------------
        # server-level breaker over the whole predict path: opens after
        # repeated timeouts/unavailability so a dead engine answers
        # degraded responses instantly instead of waiting out every budget
        self._serving_breaker = CircuitBreaker(
            "serving", failure_threshold=config.algo_breaker_threshold,
            reset_timeout=config.algo_breaker_reset_sec)
        # last-good predictions keyed by canonical query JSON (bounded
        # LRU); guarded by a lock — _degraded_result runs in executor
        # threads while _remember_good mutates on the loop thread
        import threading

        self._last_good: "dict[str, Any]" = {}
        self._last_good_lock = threading.Lock()
        self._LAST_GOOD_MAX = 1024
        self.degraded_count = 0
        # -- crash-safe model lifecycle (docs/resilience.md) --------------
        # the previous DeployedEngine stays pinned through the probation
        # window after a successful /reload so a breaker-trip burst from
        # the new instance can atomically roll back
        self._previous: Optional[DeployedEngine] = None
        self._probation_until: Optional[float] = None
        self._rollback_count = 0
        self._last_reload: dict = {"status": "initial",
                                   "instanceId": self.deployed.instance.id}
        # -- streaming delta state (docs/streaming.md) --------------------
        # which [from_seq, to_seq) ranges of the updater's chain this
        # replica has applied; None until the first delta lands (or after
        # a full /reload resets the base). Snapshotted with the probation
        # pin so a rollback restores the matching chain position.
        self._delta_state: Optional[dict] = None
        self._previous_delta_state: Optional[dict] = None
        # -- multi-host shard ownership (docs/sharding.md) ----------------
        # fenced claim on a contiguous item-row range; None when this
        # process serves the whole catalog (the single-host default)
        self.shard_owner = None
        if config.shard_id is not None and config.shard_count is not None:
            from incubator_predictionio_tpu.server.shard_owner import (
                ShardOwner,
            )

            self.shard_owner = ShardOwner(
                config.shard_id, config.shard_count, config.shard_state_dir)
            self.shard_owner.bind_rows(self._catalog_rows())
        # -- graceful drain (server/lifecycle.py) -------------------------
        self._drain_state = DrainState(name)
        self._start_time = self._clock.monotonic()
        self._runner: Optional[web.AppRunner] = None
        self._stop_event = asyncio.Event()
        self._feedback_tasks: set[asyncio.Task] = set()  # strong refs (GC pitfall)
        # fold this server's signals into /metrics at scrape time (keyed:
        # a re-constructed server replaces its predecessor's collector;
        # per-tenant cores each get their own key so an eviction removes
        # exactly one collector)
        REGISTRY.add_collector(name, self._collect_metrics)

    def _collect_metrics(self) -> None:
        """Exposition-time fold: standalone breakers (per-algorithm +
        serving), the serving reservoirs, and device memory."""
        breakers = {b.name: b.snapshot() for b in self.deployed.algo_breakers}
        breakers["serving"] = self._serving_breaker.snapshot()
        publish_breaker_metrics(breakers)
        _G_REQUESTS.set(self.request_count)
        _G_BATCHES.set(self.batcher.batches_served)
        _G_MAX_BATCH.set(self.batcher.max_batch_seen)
        self._admission.publish(self.batcher.queue.qsize())
        for stage, res in (("total", self.latency),
                           ("queue_delay", self.batcher.queue_delay),
                           ("dispatch", self.batcher.dispatch_sec)):
            for q, v in res.percentiles().items():
                _G_LATENCY_Q.labels(stage=stage, quantile=q).set(v)
        stream = self._streaming_health()
        if stream is not None and stream.get("stalenessSeconds") is not None:
            _STREAM_STALENESS.set(stream["stalenessSeconds"])
        import sys

        if "jax" in sys.modules:  # never the import that drags jax in
            try:
                for row in _profile.device_memory_report():
                    if row["bytes_in_use"] is not None:
                        _G_DEV_MEM.labels(device=row["device"]).set(
                            row["bytes_in_use"])
            except Exception:  # noqa: BLE001 - stats are best-effort
                pass

    # -- routes -----------------------------------------------------------
    def make_app(self) -> web.Application:
        app = web.Application(
            middlewares=[telemetry_middleware("query_server")])
        app.router.add_get("/", self.handle_status)
        app.router.add_get("/health", self.handle_health)
        add_observability_routes(app)
        app.router.add_post("/queries.json", self.handle_query)
        app.router.add_post("/shard/queries.json", self.handle_shard_query)
        app.router.add_post("/shard/promote", self.handle_shard_promote)
        app.router.add_post("/reload", self.handle_reload)
        app.router.add_post("/delta", self.handle_delta)
        app.router.add_post("/rollback", self.handle_rollback)
        app.router.add_post("/stop", self.handle_stop)
        app.router.add_get("/plugins.json", self.handle_plugins)
        return app

    async def handle_health(self, request: web.Request) -> web.Response:
        """Liveness + breaker state: per-algorithm, the serving path, and
        every storage backend registered in the process-wide registry."""
        algo = {
            b.name: b.snapshot()
            for b in self.deployed.algo_breakers
        }
        serving = self._serving_breaker.snapshot()
        backends = BREAKERS.snapshot()
        degraded = any(
            s["state"] != "closed"
            for s in (serving, *algo.values(), *backends.values()))
        return web.json_response({
            "status": self._drain_state.health_status(degraded),
            "draining": self._drain_state.draining,
            # SLO burn-rate verdicts (obs/slo.py; None when no PIO_SLO_CONFIG)
            # — pio-tpu health paints breaching objectives red
            "slo": _slo.health_block(),
            "servingBreaker": serving,
            "algorithmBreakers": algo,
            "backendBreakers": backends,
            "degradedResponses": self.degraded_count,
            # overload surface (docs/resilience.md "Overload & admission
            # control"): queue bound, brownout, limiter, shed tallies
            "admission": self._admission.snapshot(
                self.batcher.queue.qsize()),
            # crash-safe lifecycle surface (docs/resilience.md): which
            # instance serves, whether a previous one is pinned for
            # rollback, and what the last reload did. engineVersion is
            # what the fleet tier keys experiment arms and rollouts on
            # (docs/serving.md "Fleet serving")
            "deployment": {
                "instanceId": self.deployed.instance.id,
                "engineId": self.deployed.instance.engine_id,
                "engineVersion": self.deployed.instance.engine_version,
                "previousInstanceId": (
                    self._previous.instance.id
                    if self._previous is not None else None),
                "probationActive": self._probation_active(),
                "rollbacks": self._rollback_count,
                "lastReload": self._last_reload,
                # streaming update lag: lastDeltaSeq is what the updater's
                # ship-resync keys on; stalenessSeconds is the freshness
                # SLO pio-tpu health and the fleet balancer read
                "streaming": self._streaming_health(),
                # sharded serving (docs/sharding.md): per-model shard count
                # + mode + explicit [lo, hi) row bounds, None for
                # single-host models — what `pio-tpu shards` and fleet
                # tooling read without a full status page
                "sharding": self._sharding_summary(),
                # multi-host shard ownership: the fenced row-range claim
                # the fleet router's scatter/gather routes on
                "shardOwner": (self.shard_owner.announce()
                               if self.shard_owner is not None else None),
            },
        })

    def _sharding_summary(self) -> list:
        from incubator_predictionio_tpu.sharding.table import ShardSpec

        out = []
        for m in self.deployed.models:
            info = m.serving_info() if hasattr(m, "serving_info") else None
            sh = (info or {}).get("sharding")
            if not sh:
                out.append(None)
                continue
            entry = {"nShards": sh["n_shards"], "mode": sh["mode"],
                     "mergeFanin": sh["merge_fanin"]}
            items = sh.get("items") or None
            if items:
                # explicit per-shard [lo, hi) item-row bounds — routers and
                # `pio-tpu shards` need ranges, not just counts
                spec = ShardSpec(items["name"], items["n_rows"],
                                 items["width"], items["n_shards"])
                entry["shardIds"] = list(range(spec.n_shards))
                entry["rows"] = [list(spec.shard_bounds(s))
                                 for s in range(spec.n_shards)]
            out.append(entry)
        return out

    def _catalog_rows(self) -> int:
        """Item-catalog row count of the deployed model — what the shard
        owner's ``[lo, hi)`` bounds derive from."""
        for m in self.deployed.models:
            info = m.serving_info() if hasattr(m, "serving_info") else None
            if info and info.get("catalog_rows"):
                return int(info["catalog_rows"])
        return 0

    async def handle_shard_query(self, request: web.Request) -> web.Response:
        """One shard owner's PARTIAL answer (docs/sharding.md "Multi-host
        shard owners"): block-local top-k candidates over the owned item
        rows only, plus the owner's fenced epoch so the router can discard
        partials from a deposed owner. Only the fleet router should call
        this — clients keep using /queries.json."""
        if self._drain_state.draining:
            return self._drain_state.reject_response()
        so = self.shard_owner
        if so is None or so.bounds() is None:
            return web.json_response(
                {"message": "this server is not a shard owner (deploy with "
                            "--shard-id/--shard-count)"}, status=409)
        lo, hi = so.bounds()
        body = await request.read()
        try:
            payload = json.loads(body.decode("utf-8"))
            if not isinstance(payload, dict):
                raise ValueError("query must be a JSON object")
        except (ValueError, UnicodeDecodeError) as e:
            return web.json_response(
                {"message": f"bad query: {e}"}, status=400)
        from incubator_predictionio_tpu.server import shard_owner as so_mod

        deployed = self.deployed  # swap-safe snapshot
        loop = asyncio.get_running_loop()
        try:
            part = await loop.run_in_executor(
                None, so_mod.partial_predict, deployed, payload, lo, hi)
        except (TypeError, ValueError, KeyError) as e:
            # query-semantic rejection, same class split as /queries.json
            return web.json_response(
                {"message": f"bad query: {e}"}, status=400)
        except so_mod.ShardOwnerError as e:
            return web.json_response({"message": str(e)}, status=409)
        return web.json_response({
            "candidates": {"ids": part["ids"], "scores": part["scores"],
                           "items": part["items"]},
            "num": part["num"],
            "shard": {**so.announce(),
                      "instanceId": deployed.instance.id},
        })

    async def handle_shard_promote(self, request: web.Request) -> web.Response:
        """Failover promotion: durably bump this owner's fencing epoch
        (persist-then-announce, the replication/manager.py invariant) so
        its partials supersede the deposed owner's. The caller may pass
        ``{"epoch": N}`` — the highest epoch it has observed for the range
        — to guarantee the promoted owner exceeds it."""
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        if self.shard_owner is None:
            return web.json_response(
                {"message": "this server is not a shard owner"}, status=409)
        try:
            body = json.loads((await request.read()) or b"{}")
        except ValueError:
            body = {}
        requested = body.get("epoch") if isinstance(body, dict) else None
        epoch = self.shard_owner.promote(
            int(requested) if requested is not None else None)
        logger.warning("shard owner %d/%d PROMOTED to epoch %d",
                       self.shard_owner.shard_id,
                       self.shard_owner.shard_count, epoch)
        return web.json_response({
            "status": "promoted", "epoch": epoch,
            "shard": self.shard_owner.announce(),
        })

    async def handle_status(self, request: web.Request) -> web.Response:
        inst = self.deployed.instance
        if "text/html" in request.headers.get("Accept", ""):
            return web.Response(
                text=self._status_html(), content_type="text/html")
        devices = self.ctx.mesh.devices.flat
        return web.json_response({
            "status": "alive",
            # the devices THIS process holds, as JAX reports them
            "platform": devices[0].platform,
            "deviceKind": devices[0].device_kind,
            "deviceCount": self.ctx.n_devices,
            "engineInstance": {
                "id": inst.id,
                "engineId": inst.engine_id,
                "engineVersion": inst.engine_version,
                "startTime": inst.start_time.isoformat(),
            },
            "algorithms": [type(a).__name__ for a in self.deployed.algorithms],
            # which execution path each model serves from (host numpy for
            # small catalogs, device bf16 / int8-pallas for large ones)
            "servingPaths": [
                m.serving_info() if hasattr(m, "serving_info") else None
                for m in self.deployed.models
            ],
            "requestCount": self.request_count,
            "avgServingSec": self.avg_serving_sec,
            "lastServingSec": self.last_serving_sec,
            "servingSecPercentiles": self.latency.percentiles(),
            # tail split (VERDICT r3 #6): time spent WAITING for a batch
            # slot vs time the dispatch itself took
            "queueDelaySecPercentiles": self.batcher.queue_delay.percentiles(),
            "dispatchSecPercentiles": self.batcher.dispatch_sec.percentiles(),
            "batchesServed": self.batcher.batches_served,
            "maxBatchSeen": self.batcher.max_batch_seen,
            # overload tallies (docs/resilience.md): queued-past-deadline
            # evictions and the live dispatch-slot bound
            "shedExpired": self.batcher.shed_expired,
            "maxInFlight": self.batcher.max_in_flight,
            # compile-churn gauge: distinct serving executables built in this
            # process; must stay flat under load once warmup has run
            "jitCompileKeys": jitstats.count(),
            "uptimeSec": self._clock.monotonic() - self._start_time,
        })

    def _status_html(self) -> str:
        """Human status page on ``/`` — the twirl template counterpart
        (core/src/main/twirl/.../workflow/index.scala.html, served by
        CreateServer.scala:437-462). Same sections: engine info, server info,
        per-stage params, algorithms+models, feedback loop. Self-contained
        CSS (no CDN — serving hosts may have no egress)."""
        import html as _html

        inst = self.deployed.instance
        cfg = self.config

        def esc(v) -> str:
            return _html.escape(str(v))

        def table(rows: list[tuple[str, object]]) -> str:
            return "<table>" + "".join(
                f"<tr><th>{esc(k)}</th><td>{esc(v)}</td></tr>"
                for k, v in rows) + "</table>"

        algo_rows = "".join(
            f"<tr><th rowspan=\"3\">{i + 1}</th>"
            f"<th>Class</th><td>{esc(type(a).__name__)}</td></tr>"
            f"<tr><th>Parameters</th><td>{esc(p)}</td></tr>"
            f"<tr><th>Model</th><td>{esc(m)}</td></tr>"
            for i, (a, p, m) in enumerate(zip(
                self.deployed.algorithms,
                json.loads(inst.algorithms_params or "[]")
                + [""] * len(self.deployed.algorithms),
                [type(m).__name__ for m in self.deployed.models]))
        )
        title = (f"{inst.engine_factory} ({inst.engine_variant}) - "
                 f"Engine Server at {cfg.ip}:{cfg.port}")
        return f"""<!DOCTYPE html>
<html lang="en">
<head><title>{esc(title)}</title>
<style>
 body {{ font-family: sans-serif; margin: 2em; }}
 table {{ border-collapse: collapse; margin-bottom: 1.5em; }}
 th, td {{ border: 1px solid #ccc; padding: 4px 10px; text-align: left; }}
 td {{ font-family: Menlo, Monaco, Consolas, monospace; }}
</style></head>
<body>
<h1>Engine Server at {esc(cfg.ip)}:{esc(cfg.port)}</h1>
<p>{esc(inst.engine_factory)} ({esc(inst.engine_variant)})</p>
<h2>Engine Information</h2>
{table([
    ("Training Start Time", inst.start_time),
    ("Training End Time", inst.end_time),
    ("Variant ID", inst.engine_variant),
    ("Instance ID", inst.id),
])}
<h2>Server Information</h2>
{table([
    ("Start Time", _dt.datetime.fromtimestamp(self._start_time)),
    ("Request Count", self.request_count),
    ("Average Serving Time", f"{self.avg_serving_sec:.4f} seconds"),
    ("Last Serving Time", f"{self.last_serving_sec:.4f} seconds"),
    ("Engine Factory Class", inst.engine_factory),
])}
<h2>Data Source</h2>
{table([("Parameters", inst.data_source_params)])}
<h2>Data Preparator</h2>
{table([("Parameters", inst.preparator_params)])}
<h2>Algorithms and Models</h2>
<table><tr><th>#</th><th colspan="2">Information</th></tr>{algo_rows}</table>
<h2>Serving</h2>
{table([("Parameters", inst.serving_params)])}
<h2>Feedback Loop Information</h2>
{table([
    ("Feedback Loop Enabled?", cfg.feedback),
    ("Event Server IP", cfg.event_server_ip),
    ("Event Server Port", cfg.event_server_port),
])}
</body>
</html>"""

    async def handle_query(self, request: web.Request) -> web.Response:
        t_entry = time.perf_counter()
        if self._drain_state.draining:
            return self._drain_state.reject_response()
        status, result, headers = await self._serve_payload(
            await request.read(), t_entry)
        return web.json_response(result, status=status, headers=headers)

    @staticmethod
    def _server_timing(total_sec: float,
                       algo_times: list[tuple[str, float]]) -> str:
        """``X-PIO-Server-Timing`` value: total µs plus this request's
        dispatch's per-algorithm µs (``<name>;us=<int>`` entries) — clients
        see server-side cost without scraping /metrics."""
        parts = [f"total;us={int(total_sec * 1e6)}"]
        parts.extend(f"{name};us={int(sec * 1e6)}"
                     for name, sec in algo_times)
        return ", ".join(parts)

    def _feed_admission(self) -> None:
        """Every request that consumed a batcher queue slot counts as drain
        progress — 400 binding rejections, timeout-degraded answers, and
        engine exceptions all drained the queue (and usually a dispatch)
        just like clean 200s, and a service-rate estimate fed only by
        successes under-reads the true drain rate, shedding good traffic
        below capacity on mixed workloads. Brownout answers and abandoned
        entries never enter the queue, so they stay out; assembly-time
        504-evictions are recorded by ``on_shed_expired`` instead. No
        request's latency goes to the adaptive limiter: that one sizes the
        dispatch slots and hears from ``MicroBatcher._dispatch``, once a
        batch."""
        self._admission.on_complete()

    async def _serve_payload(
            self, body: bytes, t_entry: Optional[float] = None,
    ) -> tuple[int, Any, Optional[dict]]:
        """The whole query lifecycle from raw body bytes — ONE code path
        shared by the aiohttp route and the native front, so their behavior
        cannot drift. Returns (status, jsonable body, response headers or
        None) — headers carry X-PIO-Server-Timing on predictions and
        Retry-After on overload rejections. ``t_entry``: the
        ``perf_counter`` reading at the handler's entry, when the body was
        still to be read (``serve.request.parse`` starts there)."""
        if t_entry is None:
            t_entry = time.perf_counter()
        t0 = self._clock.monotonic()
        try:
            payload = json.loads(body)
        except json.JSONDecodeError:
            return 400, {"message": "Invalid JSON query"}, None
        loop = asyncio.get_running_loop()
        # -- admission door (resilience/admission.py) ---------------------
        # shedding order (docs/resilience.md): brownout (degraded 200)
        # before 429-reject before the batcher's 504-evict. Health,
        # /metrics, and /reload never pass this door.
        decision, retry_after = self._admission.decide(
            self.batcher.queue.qsize())
        if decision == REJECT:
            return 429, {
                "message": "server overloaded; rejected by admission "
                           "control (docs/resilience.md)",
            }, {"Retry-After": str(retry_after)}
        if decision == BROWNOUT:
            # sustained saturation: answer from the degraded path (last-
            # good cache / serving default) without touching the device
            # queue — valid 200s for everyone beats shedding for some
            return 200, await loop.run_in_executor(
                None, self._degraded_result, payload,
                "brownout (admission control)"), None
        if not self._serving_breaker.allow():
            # the predict path has been failing hard: degrade instantly
            # instead of waiting out another budget (half-open probes are
            # admitted by allow() once the reset window elapses). User code
            # (default_result, plugins) runs in the executor — under outage
            # EVERY request takes this path, and it must not block the loop
            return 200, await loop.run_in_executor(
                None, self._degraded_result, payload,
                "serving breaker open"), None
        # handler entry → enqueue: body, JSON, admission, breaker (timed by
        # hand: the body read is an await)
        _trace.record_span("serve.request.parse", t_entry,
                           time.perf_counter() - t_entry)
        try:
            submitted = self.batcher.submit_timed(payload)
            if self.config.query_timeout_sec is not None:
                # the degraded-200 backstop waits a small GRACE past the
                # budget: the batcher's 504-evict (assembly-time shed of
                # queued-expired requests) fires AT the budget, so under
                # overload the orderly shed wins; the backstop only
                # catches a wedged dispatch that produced no assembly at
                # all — firing both at the same instant would make the
                # shed path unreachable and charge the serving breaker
                # (and probation rollback) for pure overload
                budget = self.config.query_timeout_sec
                prediction, algo_times, resolved_at = await asyncio.wait_for(
                    submitted, budget + max(0.05, 0.1 * budget))
            else:
                prediction, algo_times, resolved_at = await submitted
        except asyncio.CancelledError:
            # client disconnected mid-await (aiohttp cancels the handler):
            # no verdict on the engine's health — hand back the admitted
            # half-open probe slot or the breaker wedges half-open forever
            self._serving_breaker.release_probe()
            raise
        except ShedExpired:
            # evicted at batch assembly: the deadline passed while queued.
            # Overload, not an engine verdict — the probe slot goes back
            # untouched and the caller gets a fail-fast 504 with the same
            # pressure-derived hint the 429 path sends
            self._serving_breaker.release_probe()
            return 504, {
                "message": "deadline expired before dispatch; request "
                           "shed (docs/resilience.md)",
            }, {"Retry-After": str(
                self._admission.retry_after(self.batcher.queue.qsize()))}
        except (TypeError, ValueError, KeyError) as e:
            # the engine answered (binding rejected the query): health-wise
            # a success — a half-open probe slot must never leak
            self._serving_breaker.record_success()
            self._feed_admission()
            return 400, {"message": f"Invalid query: {e}"}, None
        except (asyncio.TimeoutError, ServingUnavailable, DeadlineExceeded,
                CircuitOpenError) as e:
            # deadline blown or every algorithm/backend breaker open:
            # degraded-but-valid beats a 500 (ISSUE 1 acceptance)
            self._serving_breaker.record_failure()
            # a breaker trip inside a reload's probation window indicts the
            # freshly swapped instance — restore the pinned previous one
            await self._maybe_probation_rollback(repr(e))
            self._ship_remote_log(f"query degraded: {e!r}")
            self._feed_admission()
            return 200, await loop.run_in_executor(
                None, self._degraded_result, payload, repr(e)), None
        except Exception as e:  # noqa: BLE001 - ship serving errors remotely
            # a per-query engine exception is the ENGINE answering (with an
            # error) — not a serving outage. One client's poison query must
            # not trip this breaker and degrade everyone; a genuinely
            # broken engine opens the per-algorithm breakers instead, which
            # surfaces here as ServingUnavailable (counted above).
            self._serving_breaker.record_success()
            self._ship_remote_log(f"query failed: {e!r}")
            self._feed_admission()
            raise
        # future resolved → answer built: the wait for the loop to reach
        # this request behind its batch-mates' answers, then the block (the
        # response object's own JSON encoding, tens of µs, is left to the
        # route span)
        with _trace.span("serve.request.respond", start=resolved_at):
            return self._respond(payload, prediction, algo_times, t0)

    def _respond(self, payload: dict, prediction: Any, algo_times: list,
                 t0: float) -> tuple[int, Any, Optional[dict]]:
        """A clean prediction's way out: bookkeeping, ``to_jsonable``,
        output plugins, the last-good cache, the timing header."""
        self._serving_breaker.record_success()
        dt = self._clock.monotonic() - t0
        self.request_count += 1
        self.last_serving_sec = dt
        self.avg_serving_sec += (dt - self.avg_serving_sec) / self.request_count
        self.latency.record(dt)
        self._feed_admission()
        # camelCase field names: the reference's response shape
        # (CreateServer.scala:494's json4s serialization of e.g. ItemScore)
        result = to_jsonable(prediction, camelize_fields=True)
        from incubator_predictionio_tpu.server.plugins import apply_output_plugins

        result = apply_output_plugins(self.deployed.instance, payload, result)
        # cache POST-plugin: a degraded replay must never leak fields an
        # output plugin (redaction, enrichment) would have removed
        self._remember_good(payload, result)
        if self.config.feedback:
            task = asyncio.create_task(self._send_feedback(payload, result))
            self._feedback_tasks.add(task)
            task.add_done_callback(self._feedback_tasks.discard)
        return 200, result, {
            "X-PIO-Server-Timing": self._server_timing(dt, algo_times)}

    # -- graceful degradation (resilience/) -------------------------------
    @staticmethod
    def _cache_key(payload: dict) -> str:
        try:
            canon = json.dumps(payload, sort_keys=True, default=str)
        except (TypeError, ValueError):
            canon = repr(payload)
        # digest, not the canonical string: 1024 cached entries must not
        # also pin 1024 full query bodies as dict keys
        return hashlib.sha1(canon.encode()).hexdigest()

    def _remember_good(self, payload: dict, result: Any) -> None:
        key = self._cache_key(payload)
        with self._last_good_lock:
            self._last_good.pop(key, None)  # re-insert = move to MRU end
            self._last_good[key] = result
            while len(self._last_good) > self._LAST_GOOD_MAX:
                self._last_good.pop(next(iter(self._last_good)))

    def _degraded_result(self, payload: dict, reason: str) -> Any:
        """Fallback when the engine cannot answer in time: the last good
        prediction for this exact query, else the serving layer's declared
        default (``serving.default_result(query)``), else a minimal valid
        body — always 200, never a 500 (the engine being slow is our
        problem, not the caller's)."""
        with self._last_good_lock:
            # += from concurrent executor threads is a lost-update hazard
            self.degraded_count += 1
            cached = self._last_good.get(self._cache_key(payload))
        _DEGRADED.inc()
        if cached is not None:
            if isinstance(cached, dict):
                return {**cached, "degraded": True}
            return cached
        default_fn = getattr(self.deployed.serving, "default_result", None)
        if callable(default_fn):
            try:
                from incubator_predictionio_tpu.server.plugins import (
                    apply_output_plugins,
                )

                # the documented contract passes the BOUND query (like
                # supplement/serve), not the raw JSON dict
                query = bind_query(self.deployed.query_cls, payload)
                result = to_jsonable(default_fn(query), camelize_fields=True)
                result = apply_output_plugins(
                    self.deployed.instance, payload, result)
                if isinstance(result, dict):
                    return {**result, "degraded": True}
                return result
            except Exception:  # noqa: BLE001 - the default must never throw
                logger.exception("serving default_result failed")
        return {"degraded": True, "message": f"serving degraded: {reason}"}

    @staticmethod
    async def _post_json(url: str, body: dict, what: str) -> None:
        """Fire-and-forget POST; failures are logged, never raised (feedback
        and log shipping must never break serving)."""
        import aiohttp

        try:
            async with aiohttp.ClientSession() as session:
                async with session.post(url, json=body,
                                        timeout=aiohttp.ClientTimeout(total=5)) as resp:
                    if resp.status >= 300:
                        logger.warning("%s rejected: %s", what, resp.status)
        except Exception as e:  # noqa: BLE001
            logger.warning("%s failed: %s", what, e)

    async def _send_feedback(self, query: dict, prediction: Any) -> None:
        """POST a `predict` event to the event server (CreateServer.scala:508-570)."""
        pr_id = prediction.get("prId") if isinstance(prediction, dict) else None
        pr_id = pr_id or uuid.uuid4().hex
        event = {
            "event": "predict",
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": {"query": query, "prediction": prediction},
        }
        url = (
            f"http://{self.config.event_server_ip}:{self.config.event_server_port}"
            f"/events.json?accessKey={self.config.access_key or ''}"
        )
        await self._post_json(url, event, "feedback event")

    def _ship_remote_log(self, message: str) -> None:
        """Fire-and-forget POST of a serving error to ``--log-url``
        (reference ``remoteLog``, CreateServer.scala:423-436)."""
        if not self.config.log_url:
            return

        body = {"level": "ERROR",
                "message": f"{self.config.log_prefix}{message}",
                "engineInstanceId": self.deployed.instance.id}
        task = asyncio.create_task(
            self._post_json(self.config.log_url, body, "remote log"))
        self._feedback_tasks.add(task)
        task.add_done_callback(self._feedback_tasks.discard)

    def _authorized(self, request: web.Request) -> bool:
        import hmac

        key = self.config.server_access_key
        if not key:
            return True
        # bytes operands: compare_digest rejects non-ASCII str
        return hmac.compare_digest(
            request.query.get("accessKey", "").encode(), key.encode())

    async def handle_reload(self, request: web.Request) -> web.Response:
        """Versioned hot-swap (docs/resilience.md crash-safe lifecycle):

        1. load + warm the new instance BESIDE the live one (the live
           engine keeps serving throughout — a crash anywhere in here
           leaves it untouched);
        2. run the configured smoke queries against the new instance; any
           failure keeps the live instance and answers 409 (the new
           instance never serves a query);
        3. atomically swap the ``DeployedEngine`` reference and pin the
           previous instance for ``reload_probation_sec`` — a
           serving-breaker trip inside that window auto-rolls back.
        """
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        if self._drain_state.draining:
            return self._drain_state.reject_response()
        loop = asyncio.get_running_loop()
        try:
            # executor: loading deserializes blobs and warms compile caches
            # — seconds of work that must not stall live queries
            new = await loop.run_in_executor(
                None, load_deployed_engine, self.config, self.storage,
                self.ctx)
        except RuntimeError as e:
            return web.json_response({"message": str(e)}, status=400)
        failure = await self._smoke_gate(new)
        if failure is not None:
            self._rollback_count += 1
            _ROLLBACKS.inc()
            self._last_reload = {
                "status": "rejected", "instanceId": new.instance.id,
                "reason": failure,
            }
            logger.error("reload: smoke gate rejected instance %s (%s); "
                         "instance %s keeps serving", new.instance.id,
                         failure, self.deployed.instance.id)
            return web.json_response({
                "message": "Reload rejected by smoke-query gate; previous "
                           "instance keeps serving",
                "error": failure,
                "engineInstanceId": self.deployed.instance.id,
            }, status=409)
        old = await self._swap_in(new)
        # a full reload resets the streaming chain: deltas were built for
        # the previous base instance and the updater starts a fresh chain
        # against this one (the snapshot _swap_in took still restores the
        # old chain position if probation rolls this reload back)
        self._delta_state = None
        self._last_reload = {"status": "ok", "instanceId": new.instance.id,
                             "previousInstanceId": old.instance.id}
        return web.json_response({"message": "Reloaded",
                                  "engineInstanceId": new.instance.id})

    async def _swap_in(self, new: DeployedEngine) -> DeployedEngine:
        """Atomic engine swap + probation pin, shared by /reload (full
        model) and /delta (streaming delta deploy): in-flight dispatches
        hold their own reference to the old engine and complete against
        it; everything after the assignment serves the new one. The old
        engine — and the delta-chain position that matched it — is pinned
        for the probation window so a breaker trip rolls BOTH back."""
        loop = asyncio.get_running_loop()
        old = self.deployed
        self.deployed = new
        # The batcher captured the old DeployedEngine at construction;
        # repoint it or the swap would silently keep serving the stale
        # model.
        self.batcher.deployed = new
        # the swapped engine may have a different thread-safety posture —
        # re-resolve the overlap bound (and re-bound the adaptive limiter,
        # which also resets its latency baseline: new engine, new floor)
        # or auto mode's no-race guarantee breaks across the swap
        bound = effective_max_in_flight(self.config, new)
        limit = self._admission.set_max_inflight(bound)
        await self.batcher.resize(limit if limit is not None else bound)
        if self.shard_owner is not None:
            # a swapped-in instance may carry a different catalog size —
            # re-derive the owned [lo, hi) from the same ShardSpec math
            self.shard_owner.bind_rows(self._catalog_rows())
        self._previous = old
        self._previous_delta_state = (
            dict(self._delta_state) if self._delta_state else None)
        self._probation_until = (
            self._clock.monotonic() + self.config.reload_probation_sec
            if self.config.reload_probation_sec > 0 else None)
        if self._probation_until is not None:
            # release the pin proactively when the window ends: without a
            # /health prober nothing else reads _probation_active(), and
            # the old instance's device arrays would stay resident for the
            # process lifetime (doubling memory per reload cycle). The
            # callback is a no-op if a rollback already consumed the pin
            # or an injected test clock says probation is still running.
            loop.call_later(self.config.reload_probation_sec + 0.5,
                            self._probation_active)
        else:
            self._previous = None  # probation disabled: nothing to pin
        return old

    async def _smoke_gate(self, new: DeployedEngine) -> Optional[str]:
        """Run ``config.smoke_queries`` against the not-yet-live instance.
        Returns an error description, or None when the gate passes (no
        queries configured = pass: warmup already exercised the models)."""
        loop = asyncio.get_running_loop()
        for payload in self.config.smoke_queries:
            try:
                await loop.run_in_executor(None, new.predict, dict(payload))
            except Exception as e:  # noqa: BLE001 - any failure gates
                return f"smoke query {payload!r} failed: {e!r}"
        return None

    def _probation_active(self) -> bool:
        if self._previous is None or self._probation_until is None:
            return False
        if self._clock.monotonic() >= self._probation_until:
            # probation survived: release the pinned previous instance so
            # its device arrays can be reclaimed
            self._previous = None
            self._probation_until = None
            return False
        return True

    async def _restore_previous(self, reason: str) -> DeployedEngine:
        """Swap the pinned previous instance back in (probation rollback
        and the fleet orchestrator's POST /rollback share this): atomic
        engine swap, limiter re-bound, serving breaker closed so the
        restored instance serves immediately."""
        prev, self._previous = self._previous, None
        self._probation_until = None
        rolled_from = self.deployed.instance.id
        self.deployed = prev
        self.batcher.deployed = prev
        # the restored engine's tables predate the swapped-in deploy —
        # restore the delta-chain position that matched them, so the
        # updater's ship-resync re-sends exactly what was rolled back
        self._delta_state = self._previous_delta_state
        self._previous_delta_state = None
        bound = effective_max_in_flight(self.config, prev)
        limit = self._admission.set_max_inflight(bound)
        await self.batcher.resize(limit if limit is not None else bound)
        if self.shard_owner is not None:
            self.shard_owner.bind_rows(self._catalog_rows())
        self._serving_breaker.record_success()  # clean slate for the restore
        self._rollback_count += 1
        _ROLLBACKS.inc()
        self._last_reload = {"status": "rolled_back",
                             "instanceId": prev.instance.id,
                             "rolledBackFrom": rolled_from,
                             "reason": reason}
        logger.error("reload: rolled back from instance %s to %s "
                     "(%s)", rolled_from, prev.instance.id, reason)
        return prev

    async def _maybe_probation_rollback(self, reason: str) -> None:
        """Called after a serving-breaker failure: if the breaker tripped
        OPEN inside a reload's probation window, the new instance is
        broken under real traffic — swap the pinned previous instance back
        in and close the breaker so it serves immediately."""
        if self._serving_breaker.state != "open" or not self._probation_active():
            return
        await self._restore_previous(reason)

    def _streaming_health(self) -> Optional[dict]:
        """Delta-chain position + freshness for /health.deployment (None
        until a streaming delta has been applied to this base)."""
        st = self._delta_state
        if not st:
            return None
        staleness = None
        if st.get("maxEventTimeUs"):
            # pio-lint: disable=R2 (maxEventTimeUs is an EPOCH stamp from the event log; staleness vs wall time is the semantic — the monotonic Clock seam cannot express it)
            staleness = max(0.0, time.time() - st["maxEventTimeUs"] / 1e6)
        return {
            "lastDeltaSeq": st["lastDeltaSeq"],
            "chainBase": st["chainBase"],
            "applied": st["applied"],
            "deduped": st["deduped"],
            "stalenessSeconds": staleness,
        }

    async def handle_delta(self, request: web.Request) -> web.Response:
        """Streaming delta deploy (docs/streaming.md): apply a versioned
        embedding-row delta through the SAME discipline as a full /reload
        — build the delta-applied engine BESIDE the live one, run the
        smoke-query gate, swap atomically, pin the previous engine for
        probation (a breaker trip rolls the delta back to last-good).

        Exactly-once enforcement: every delta names its ``[from_seq,
        to_seq)`` event range and the base instance it applies to.
        Out-of-order or wrong-base deltas are rejected 409 (with this
        replica's position, so the updater resyncs the chain); an
        already-applied range answers 200 "duplicate" — the crash-replay
        dedup — and is counted, never re-applied."""
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        if self._drain_state.draining:
            return self._drain_state.reject_response()
        from incubator_predictionio_tpu.streaming.delta import decode_delta

        body = await request.read()
        try:
            delta = decode_delta(body)
        except Exception as e:  # noqa: BLE001 - bad/foreign artifact
            return web.json_response(
                {"status": "rejected", "message": f"bad delta: {e}"},
                status=400)
        inst_id = self.deployed.instance.id
        st = self._delta_state
        last = st["lastDeltaSeq"] if st else None
        if delta.base_instance != inst_id:
            return web.json_response({
                "status": "rejected", "reason": "base-mismatch",
                "message": f"delta targets instance {delta.base_instance}, "
                           f"this replica serves {inst_id}",
                "instanceId": inst_id, "lastDeltaSeq": last,
            }, status=409)
        if last is not None and delta.to_seq <= last:
            # already applied (the updater crashed between ship and cursor
            # commit and is replaying): idempotent ack, counted
            self._delta_state["deduped"] += 1
            _STREAM_DEDUPED.inc()
            return web.json_response({
                "status": "duplicate", "lastDeltaSeq": last})
        expected = last if last is not None else delta.chain_base
        if delta.from_seq != expected:
            return web.json_response({
                "status": "rejected", "reason": "out-of-order",
                "message": f"expected from_seq {expected}, got "
                           f"{delta.from_seq} — resync the chain",
                "lastDeltaSeq": last, "instanceId": inst_id,
            }, status=409)
        if not delta.finite():
            return web.json_response({
                "status": "rejected", "reason": "non-finite",
                "message": "delta carries non-finite rows; quarantine the "
                           "stream (docs/streaming.md)",
                "lastDeltaSeq": last,
            }, status=409)
        # shard owners apply only THEIR slice of the chain's item rows —
        # the full chain still ships to every owner (seq bookkeeping must
        # stay contiguous for the range checks above), the restriction
        # happens at apply time so a foreign owner's rows never land here
        apply_delta_obj = delta
        if self.shard_owner is not None:
            bounds = self.shard_owner.bounds()
            if bounds is not None:
                from incubator_predictionio_tpu.streaming.delta import (
                    restrict_to_item_rows,
                )

                apply_delta_obj = restrict_to_item_rows(delta, *bounds)
        loop = asyncio.get_running_loop()

        def build() -> DeployedEngine:
            import signal as _signal

            models = []
            applied = False
            for m in self.deployed.models:
                if hasattr(m, "apply_delta"):
                    m = m.apply_delta(apply_delta_obj)
                    applied = True
                models.append(m)
            if not applied:
                raise LookupError("no deployed model supports streaming "
                                  "deltas (apply_delta)")
            if os.environ.get("PIO_DELTA_FAULT") == "kill:mid_apply":
                # chaos hook: die with the new tables built but NOT
                # swapped — serving must still hold the old engine after
                # restart, with nothing half-applied
                logger.error("PIO_DELTA_FAULT tripping mid_apply — SIGKILL")
                os.kill(os.getpid(), _signal.SIGKILL)
            return DeployedEngine(
                self.deployed.engine, self.deployed.engine_params,
                self.deployed.instance, models,
                max_batch=self.config.max_batch, warmup=False,
                algo_deadline=self.config.algo_deadline_sec,
                breaker_threshold=self.config.algo_breaker_threshold,
                breaker_reset=self.config.algo_breaker_reset_sec,
                clock=self._clock)

        try:
            new = await loop.run_in_executor(None, build)
        except LookupError as e:
            return web.json_response(
                {"status": "rejected", "message": str(e)}, status=409)
        except (ValueError, RuntimeError) as e:
            return web.json_response({
                "status": "rejected", "reason": "apply-failed",
                "message": str(e), "lastDeltaSeq": last,
            }, status=409)
        failure = await self._smoke_gate(new)
        if failure is not None:
            self._rollback_count += 1
            _ROLLBACKS.inc()
            self._last_reload = {
                "status": "delta_rejected", "instanceId": inst_id,
                "deltaRange": [delta.from_seq, delta.to_seq],
                "reason": failure,
            }
            logger.error("delta [%d, %d): smoke gate rejected (%s); "
                         "previous state keeps serving",
                         delta.from_seq, delta.to_seq, failure)
            return web.json_response({
                "status": "rejected", "reason": "smoke-gate",
                "error": failure, "lastDeltaSeq": last,
            }, status=409)
        await self._swap_in(new)
        prev_max_t = st["maxEventTimeUs"] if st else 0
        self._delta_state = {
            "lastDeltaSeq": delta.to_seq,
            "chainBase": delta.chain_base,
            "maxEventTimeUs": max(prev_max_t, delta.max_event_time_us),
            "applied": (st["applied"] if st else 0) + 1,
            "deduped": st["deduped"] if st else 0,
        }
        _STREAM_APPLIED.inc()
        self._last_reload = {
            "status": "delta", "instanceId": inst_id,
            "deltaRange": [delta.from_seq, delta.to_seq],
        }
        return web.json_response({
            "status": "applied",
            "lastDeltaSeq": delta.to_seq,
            "rows": delta.n_rows,
            "engineInstanceId": inst_id,
        })

    async def handle_rollback(self, request: web.Request) -> web.Response:
        """Operator/orchestrator-driven rollback to the pinned previous
        instance — the fleet rollout's halt path (``pio-tpu fleet
        rollout``, docs/serving.md "Fleet serving"): when a LATER replica
        trips its smoke gate or probation, the already-updated replicas
        are restored to last-good through this endpoint while their own
        probation pins still hold. 409 once the pin is gone (probation
        elapsed or rollback already consumed it)."""
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        if not self._probation_active():
            return web.json_response({
                "message": "no pinned previous instance (probation "
                           "inactive); nothing to roll back to",
            }, status=409)
        prev = await self._restore_previous("operator rollback "
                                            "(POST /rollback)")
        return web.json_response({"message": "Rolled back",
                                  "engineInstanceId": prev.instance.id})

    async def handle_stop(self, request: web.Request) -> web.Response:
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        self._stop_event.set()
        return web.json_response({"message": "Shutting down"})

    async def handle_plugins(self, request: web.Request) -> web.Response:
        from incubator_predictionio_tpu.server.plugins import (
            ENGINE_SERVER_PLUGINS,
            EngineServerPlugin,
        )

        def listing(output_type):
            return {
                p.name: {"description": p.description, "class": type(p).__name__}
                for p in ENGINE_SERVER_PLUGINS.values()
                if p.output_type == output_type
            }

        return web.json_response({"plugins": {
            "outputblockers": listing(EngineServerPlugin.OUTPUTBLOCKER),
            "outputsniffers": listing(EngineServerPlugin.OUTPUTSNIFFER),
        }})

    # -- lifecycle --------------------------------------------------------
    async def start(self) -> None:
        import os

        from incubator_predictionio_tpu.obs import procstats
        from incubator_predictionio_tpu.server.event_server import _ssl_context

        # loop-lag gauge rides this server's loop (pio_process_loop_lag_*)
        self._loop_lag = procstats.start_loop_lag("query_server")
        self._runner = web.AppRunner(self.make_app())
        await self._runner.setup()
        # OPT-IN for serving (measured a wash on single-core CPU: the
        # cross-thread completion hops cost what the aiohttp cycle saved —
        # PERF.md round-5; multi-core / TPU hosts may differ, hence the knob)
        if (os.environ.get("PIO_NATIVE_HTTP_SERVING", "0") == "1"
                and os.environ.get("PIO_NATIVE_HTTP", "1") != "0"
                and self.config.ssl_cert is None):
            from incubator_predictionio_tpu.server.front_boot import (
                start_with_native_front,
            )

            self._loop = asyncio.get_running_loop()
            self._front = await start_with_native_front(
                self._runner, self.config.ip, self.config.port,
                self._native_http_handler, "POST /queries.json",
                "engine server")
            if self._front is not None:
                return
            self._runner = web.AppRunner(self.make_app())
            await self._runner.setup()
        site = web.TCPSite(self._runner, self.config.ip, self.config.port,
                           ssl_context=_ssl_context(self.config))
        await site.start()
        logger.info("engine server listening on %s:%d", self.config.ip, self.config.port)

    def _native_http_handler(self, token: int, method: str, path_qs: str,
                             body: bytes):
        """Runs on the native front's epoll thread: schedule the query on
        the event loop (the SAME _serve_payload path aiohttp uses) and
        answer later via the completion token — so micro-batching keeps
        coalescing concurrent queries across connections."""
        from incubator_predictionio_tpu import native

        if self._drain_state.draining:
            # tunnel: the aiohttp handler owns the 503 + Retry-After
            # draining answer — accepting here would re-enter the
            # micro-batcher and keep the drain's queue-empty wait from
            # ever becoming true
            return None
        loop = getattr(self, "_loop", None)
        if loop is None or loop.is_closed():
            return None  # tunnel
        asyncio.run_coroutine_threadsafe(
            self._native_serve(token, body), loop)
        return native.HTTP_PENDING

    async def _native_serve(self, token: int, body: bytes) -> None:
        from incubator_predictionio_tpu import native

        try:
            status, result, headers = await self._serve_payload(body)
            payload = json.dumps(result).encode()
            reason = {200: "OK", 400: "Bad Request",
                      429: "Too Many Requests",
                      504: "Gateway Timeout"}.get(status, "Error")
            extra = "".join(f"{k}: {v}\r\n"
                            for k, v in (headers or {}).items())
            resp = (f"HTTP/1.1 {status} {reason}\r\n"
                    f"Content-Type: application/json; charset=utf-8\r\n"
                    f"Content-Length: {len(payload)}\r\n"
                    f"{extra}"
                    f"Connection: keep-alive\r\n\r\n").encode() + payload
        except Exception:  # noqa: BLE001 - aiohttp would 500 here
            logger.exception("native serving handler error")
            body_b = b"500 Internal Server Error"
            resp = (b"HTTP/1.1 500 Internal Server Error\r\n"
                    b"Content-Type: text/plain; charset=utf-8\r\n"
                    b"Content-Length: " + str(len(body_b)).encode() +
                    b"\r\nConnection: close\r\n\r\n" + body_b)
        native.http_front_complete(getattr(self, "_front", None), token, resp)

    async def wait_stopped(self) -> None:
        await self._stop_event.wait()
        await self.drain_and_shutdown()

    async def drain_and_shutdown(
            self, deadline_sec: Optional[float] = None) -> None:
        """Graceful exit (docs/resilience.md): stop accepting queries
        (503 + Retry-After, /health → 'draining'), let every queued and
        in-flight micro-batch complete, then shut down — all within the
        deadline so a wedged dispatch can't hold the process hostage."""
        self._drain_state.begin()
        deadline = (drained_exit_deadline()
                    if deadline_sec is None else deadline_sec)
        drained = await wait_for(
            lambda: (self.batcher.queue.qsize() == 0
                     and not self.batcher._inflight),
            deadline)
        if not drained:
            logger.warning("drain: in-flight queries still running after "
                           "%.1fs — shutting down anyway", deadline)
        await self.shutdown()

    async def shutdown(self) -> None:
        # stop the native front first (no new pending queries), then stop
        # accepting backend connections BEFORE stopping the batcher — a
        # query in the gap would otherwise resurrect the drainer task
        front = getattr(self, "_front", None)
        if front is not None:
            from incubator_predictionio_tpu import native

            native.http_front_stop(front)
            self._front = None
        if self._runner is not None:
            await self._runner.cleanup()
        lag = getattr(self, "_loop_lag", None)
        if lag is not None:
            lag.cancel()
        await self.batcher.stop()
        # the registry must not keep a stopped server (and through it the
        # deployed models' device arrays) alive
        REGISTRY.remove_collector(self.name, self._collect_metrics)
        # lifecycle flush for the trace spool: the drain's last spans (the
        # 503s it answered, the final dispatches) must reach disk before
        # the process exits
        from incubator_predictionio_tpu.obs import spool as trace_spool

        trace_spool.flush_export()


def serve_forever(config: ServerConfig, storage: Optional[Storage] = None) -> None:
    """Blocking entry used by the CLI `deploy` verb."""

    async def main():
        server = QueryServer(config, storage)
        await server.start()
        # SIGTERM/SIGINT drain exactly like POST /stop: finish in-flight
        # micro-batches, then exit (second signal force-exits)
        install_signal_drain(asyncio.get_running_loop(), server._stop_event,
                             "engine server")
        await server.wait_stopped()

    asyncio.run(main())
