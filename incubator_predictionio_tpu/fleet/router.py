"""The fleet router server — ``pio-tpu fleet route``.

An async front that spreads ``/queries.json`` across N query-server
replicas (docs/serving.md "Fleet serving"). Same server conventions as
the other three servers (server/lifecycle.py drain, obs/ telemetry
middleware + ``/metrics`` + ``/traces.json``); pure asyncio — the native
front is a per-replica optimization, the router is I/O-bound fan-out.

Routing policy per request:

1. the experiment (if any) assigns an arm — control or candidate — by
   entity hash or weighted rotation (fleet/experiments.py);
2. the arm's balancer picks the least-loaded *available* replica
   (healthy, not draining, not inside a Retry-After backoff window);
3. the query is forwarded with ``X-PIO-Trace`` and ``X-PIO-Client``
   propagated (client → router → replica → storage is ONE trace, and the
   storage tier's in-flight caps see the true originating identity);
4. transport errors and replica-side 429/503 are retried on a *different*
   replica while the request deadline allows — queries are idempotent
   reads, so a retry is safe where the event-ingest path's would not be;
5. shadow experiments mirror the query to the candidate fire-and-forget
   and compare (never serve) the response.

Replica health state is fed by the concurrent health watcher
(fleet/health.py) plus the passive per-request signals; a replica that
dies mid-storm is ejected after consecutive transport errors and
re-admitted by the probe cycle.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import os
import time
from typing import Optional

from aiohttp import web

from incubator_predictionio_tpu.fleet.balancer import Balancer, Replica
from incubator_predictionio_tpu.fleet.experiments import (
    CANDIDATE,
    CONTROL,
    Experiment,
)
from incubator_predictionio_tpu.fleet.health import HealthWatcher
from incubator_predictionio_tpu.obs import trace
from incubator_predictionio_tpu.obs.http import (
    add_observability_routes,
    telemetry_middleware,
)
from incubator_predictionio_tpu.obs.metrics import REGISTRY, LatencyReservoir
from incubator_predictionio_tpu.resilience.clock import SYSTEM_CLOCK, Clock
from incubator_predictionio_tpu.server.lifecycle import (
    DrainState,
    drained_exit_deadline,
    install_signal_drain,
    wait_for,
)

logger = logging.getLogger(__name__)

_REQUESTS = REGISTRY.counter(
    "pio_fleet_requests_total",
    "Queries forwarded by the fleet router, by replica and status "
    "('error' = transport failure)", labels=("replica", "status"))
_RETRIES = REGISTRY.counter(
    "pio_fleet_retries_total",
    "Forwarding attempts retried on a different replica, by reason "
    "(error = transport failure, overload = replica 429/503)",
    labels=("reason",))
_UNROUTABLE = REGISTRY.counter(
    "pio_fleet_unroutable_total",
    "Queries the router could not place on any replica (all ejected, "
    "draining, or backing off) — answered 503 + Retry-After")
_G_AVAILABLE = REGISTRY.gauge(
    "pio_fleet_replicas_available",
    "Replicas currently routable, by experiment arm", labels=("arm",))
_PARTIAL = REGISTRY.counter(
    "pio_fleet_partial_answers_total",
    "Degraded scatter/gather answers served with one or more shard ranges "
    "missing (flagged X-PIO-Partial; docs/sharding.md \"Multi-host shard "
    "owners\")")

#: statuses that mean "this replica cannot take the query right now, but
#: another one might": the idempotent-retry set. 504 is excluded — the
#: replica spent the request's deadline; there is nothing left to retry
#: with. 4xx/5xx engine answers pass through untouched.
_RETRYABLE_STATUSES = (429, 503)


@dataclasses.dataclass
class RouterConfig:
    """``pio-tpu fleet route`` flags over ``PIO_FLEET_*`` env defaults
    (docs/configuration.md)."""

    replicas: tuple = ()
    #: candidate-arm pool (a different engine version, deployed beside the
    #: control fleet); empty = no experiment routing possible
    candidates: tuple = ()
    ip: str = "0.0.0.0"
    port: int = 8200
    #: total per-query budget across every forwarding attempt; the hard
    #: wall the retry loop respects
    deadline_sec: float = dataclasses.field(
        default_factory=lambda: float(
            os.environ.get("PIO_FLEET_DEADLINE", "3.0")))
    #: forwarding attempts per query (distinct replicas)
    max_attempts: int = dataclasses.field(
        default_factory=lambda: int(
            os.environ.get("PIO_FLEET_MAX_ATTEMPTS", "2")))
    #: consecutive transport errors before a replica is ejected
    eject_threshold: int = dataclasses.field(
        default_factory=lambda: int(
            os.environ.get("PIO_FLEET_EJECT_THRESHOLD", "3")))
    #: health-watcher probe cadence / per-probe timeout
    health_interval_sec: float = dataclasses.field(
        default_factory=lambda: float(
            os.environ.get("PIO_FLEET_HEALTH_INTERVAL", "2.0")))
    probe_timeout_sec: float = dataclasses.field(
        default_factory=lambda: float(
            os.environ.get("PIO_FLEET_PROBE_TIMEOUT", "2.0")))
    #: outbound connection-pool cap across all replicas; 0 = unbounded.
    #: aiohttp's default pool of 100 is an invisible throughput ceiling at
    #: fleet scale (offered_qps x replica latency in-flight connections);
    #: the replicas' own admission control is the real backpressure, so
    #: the router does not queue at an arbitrary pool size by default
    max_outbound: int = dataclasses.field(
        default_factory=lambda: int(
            os.environ.get("PIO_FLEET_MAX_OUTBOUND", "0")))
    #: what a scatter/gather answer does when a shard range stays missing
    #: after retries within the deadline: "degrade" = serve the merged
    #: answer from the live ranges, flagged ``X-PIO-Partial`` and counted
    #: in pio_fleet_partial_answers_total; "fail" = 504. Never an
    #: unflagged short answer (docs/sharding.md).
    partial_policy: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "PIO_FLEET_PARTIAL_POLICY", "degrade"))
    #: guards POST /experiment; also presented as ``accessKey`` when the
    #: router drives a shard owner's /shard/promote during failover
    server_access_key: Optional[str] = None
    experiment: Optional[Experiment] = None

    def __post_init__(self):
        if self.partial_policy not in ("degrade", "fail"):
            raise ValueError(
                f"PIO_FLEET_PARTIAL_POLICY must be 'degrade' or 'fail', "
                f"got {self.partial_policy!r}")


class RouterServer:
    def __init__(self, config: RouterConfig, clock: Clock = SYSTEM_CLOCK,
                 fetch_health=None):
        if not config.replicas:
            raise ValueError("fleet router needs at least one --replica")
        self.config = config
        self._clock = clock
        # the router is the fleet's EDGE: it roots each query's trace, so
        # the head sampling decision (PIO_TRACE_SAMPLE) is minted here and
        # rides X-PIO-Trace as `:s=` to every downstream hop; the spool
        # (PIO_TRACE_SPOOL_DIR) makes this process's fragment durable
        from incubator_predictionio_tpu.obs import spool as trace_spool
        from incubator_predictionio_tpu.obs.plane import (
            configure_perf_plane_from_env,
        )

        trace_spool.configure_export_from_env("fleet_router")
        # continuous performance plane (obs/plane.py): procstats +
        # profiler + metrics history + SLO burn-rate engine
        configure_perf_plane_from_env("fleet_router")
        self.balancer = Balancer(config.replicas, clock=clock,
                                 eject_threshold=config.eject_threshold)
        self.candidate_balancer = Balancer(
            config.candidates, clock=clock,
            eject_threshold=config.eject_threshold)
        self.experiment = config.experiment
        self.watcher = HealthWatcher(
            [*self.balancer.replicas, *self.candidate_balancer.replicas],
            interval_sec=config.health_interval_sec,
            timeout=config.probe_timeout_sec,
            fetch=fetch_health, clock=clock)
        self.request_count = 0
        self.retry_count = 0
        self.unroutable_count = 0
        self.latency = LatencyReservoir()
        self._inflight = 0
        self._drain_state = DrainState("fleet_router")
        self._session = None  # lazy: needs the running loop
        self._runner: Optional[web.AppRunner] = None
        self._stop_event = asyncio.Event()
        self._shadow_tasks: set[asyncio.Task] = set()  # strong refs
        self._start_time = self._clock.monotonic()
        REGISTRY.add_collector("fleet_router", self._collect_metrics)

    def _collect_metrics(self) -> None:
        now = self._clock.monotonic()
        _G_AVAILABLE.labels(arm=CONTROL).set(sum(
            1 for r in self.balancer.replicas if r.available(now)))
        _G_AVAILABLE.labels(arm=CANDIDATE).set(sum(
            1 for r in self.candidate_balancer.replicas if r.available(now)))
        topo = self._topology()
        if topo.is_sharded:
            topo.down_ranges(now)  # publishes pio_fleet_shard_ranges_down
        else:
            from incubator_predictionio_tpu.fleet import topology as _topo

            _topo._G_RANGES_DOWN.set(0)

    # -- routes -------------------------------------------------------
    def make_app(self) -> web.Application:
        app = web.Application(
            middlewares=[telemetry_middleware("fleet_router")])
        app.router.add_get("/", self.handle_status)
        app.router.add_get("/health", self.handle_health)
        add_observability_routes(app)
        app.router.add_post("/queries.json", self.handle_query)
        # tenant-addressed queries (docs/tenancy.md): same handler — the
        # path names the engine, the pick filters on (tenant, load)
        app.router.add_post(
            "/engines/{tenant}/queries.json", self.handle_query)
        app.router.add_get("/experiment.json", self.handle_experiment_get)
        app.router.add_post("/experiment", self.handle_experiment_set)
        return app

    async def handle_status(self, request: web.Request) -> web.Response:
        topo = self._topology()
        return web.json_response({
            "status": "alive",
            "requestCount": self.request_count,
            "retries": self.retry_count,
            "unroutable": self.unroutable_count,
            "latencySecPercentiles": self.latency.percentiles(),
            "replicas": self.balancer.snapshot(),
            "candidates": self.candidate_balancer.snapshot(),
            "sharding": topo.snapshot() if topo.is_sharded else None,
            "experiment": (self.experiment.summary()
                           if self.experiment else None),
            "uptimeSec": self._clock.monotonic() - self._start_time,
        })

    async def handle_health(self, request: web.Request) -> web.Response:
        now = self._clock.monotonic()
        available = [r for r in self.balancer.replicas if r.available(now)]
        degraded = len(available) < len(self.balancer.replicas)
        status = self._drain_state.health_status(degraded)
        if not available and not self._drain_state.draining:
            status = "unroutable"
        topo = self._topology()
        sharding = None
        if topo.is_sharded:
            sharding = topo.snapshot()
            if sharding["downRanges"] and not self._drain_state.draining:
                # a shard range with zero live owners means partial (or
                # failed) answers — red, even while other replicas are up
                status = "shard-down"
        from incubator_predictionio_tpu.obs import slo as _slo

        return web.json_response({
            "status": status,
            "draining": self._drain_state.draining,
            # SLO burn-rate verdicts (obs/slo.py; None when no PIO_SLO_CONFIG)
            "slo": _slo.health_block(),
            "availableReplicas": len(available),
            "replicas": self.balancer.snapshot(),
            "candidates": self.candidate_balancer.snapshot(),
            "sharding": sharding,
            "experiment": (self.experiment.summary()
                           if self.experiment else None),
            "retries": self.retry_count,
            "unroutable": self.unroutable_count,
        }, status=200)

    # -- experiment control (pio-tpu fleet experiment) -----------------
    def _authorized(self, request: web.Request) -> bool:
        import hmac

        key = self.config.server_access_key
        if not key:
            return True
        return hmac.compare_digest(
            request.query.get("accessKey", "").encode(), key.encode())

    async def handle_experiment_get(
            self, request: web.Request) -> web.Response:
        return web.json_response({
            "experiment": (self.experiment.summary()
                           if self.experiment else None),
            "candidates": self.candidate_balancer.snapshot(),
        })

    async def handle_experiment_set(
            self, request: web.Request) -> web.Response:
        """Start (JSON body: name/mode/weight/hashField) or stop
        (``{"stop": true}``) the experiment at runtime — a promotion or
        abort must not need a router restart."""
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        try:
            body = json.loads(await request.read())
        except ValueError:
            return web.json_response(
                {"message": "invalid JSON"}, status=400)
        if body.get("stop"):
            self.experiment = None
            return web.json_response({"message": "experiment stopped"})
        if not self.candidate_balancer.replicas:
            return web.json_response(
                {"message": "no candidate replicas configured "
                            "(--candidate)"}, status=409)
        try:
            self.experiment = Experiment(
                name=body.get("name", "candidate"),
                mode=body.get("mode", "ab"),
                weight=float(body.get("weight", 0.1)),
                hash_field=body.get("hashField"))
        except (TypeError, ValueError) as e:
            return web.json_response({"message": str(e)}, status=400)
        return web.json_response(
            {"message": "experiment started",
             "experiment": self.experiment.summary()})

    # -- the hot path ---------------------------------------------------
    def _forward_headers(self, request: web.Request) -> dict:
        """Headers every hop (serve, retry, shadow mirror) carries: the
        current trace identity (the middleware adopted the client's or
        rooted one) and the ORIGINATING client identity — the storage
        tier's per-client in-flight caps must meter the real caller, not
        collapse the whole fleet's traffic into the router's identity."""
        headers = {"Content-Type": "application/json"}
        trace.inject(headers)
        client = request.headers.get("X-PIO-Client") or request.remote
        if client:
            headers["X-PIO-Client"] = client
        return headers

    async def _session_or_start(self):
        if self._session is None:
            import aiohttp

            self._session = aiohttp.ClientSession(
                connector=aiohttp.TCPConnector(
                    limit=max(self.config.max_outbound, 0)))
        return self._session

    @staticmethod
    def _retry_after_sec(headers) -> Optional[float]:
        try:
            return float(headers.get("Retry-After", ""))
        except ValueError:
            return None

    async def _post_replica(self, replica: Replica, body: bytes,
                            headers: dict, timeout_sec: float,
                            path: str = "/queries.json"):
        """One forwarding attempt → (status, body, headers). Transport
        errors propagate to the retry loop; the passive balancer signals
        (EWMAs, backoff, ejection) are recorded here either way. Each
        attempt gets its own span (child of the route span) with the trace
        header re-injected under it — a replica that dies mid-request
        leaves THIS span, status `error:<Type>`, in the router's spool:
        the forensic record the chaos suite assembles."""
        import aiohttp

        session = await self._session_or_start()
        replica.inflight += 1
        t0 = self._clock.monotonic()
        try:
            with trace.span("forward", service="fleet_router",
                            thread_scoped=False,
                            replica=replica.url) as fsp:
                headers = dict(headers)
                trace.inject(headers)
                async with session.post(
                        replica.url + path, data=body,
                        headers=headers,
                        timeout=aiohttp.ClientTimeout(
                            total=timeout_sec)) as resp:
                    payload = await resp.read()
                    status, resp_headers = resp.status, resp.headers
                fsp.set_attr("status", status)
                if status >= 500:
                    # keep the edge in tail-kept traces: the replica's 5xx
                    # span is kept, and without this its parent (THIS
                    # span) would be head-dropped at s=0, orphaning the
                    # replica subtree in the assembled tree
                    fsp.status = f"error:http{status}"
        except asyncio.CancelledError:
            raise
        except Exception:
            _REQUESTS.labels(replica=replica.url, status="error").inc()
            replica.on_error()
            raise
        finally:
            replica.inflight -= 1
        _REQUESTS.labels(replica=replica.url, status=str(status)).inc()
        if status in _RETRYABLE_STATUSES:
            replica.on_overload(self._retry_after_sec(resp_headers))
        elif status >= 500:
            replica.on_failure_status()
        else:
            replica.on_success(self._clock.monotonic() - t0)
        return status, payload, resp_headers

    def _passthrough(self, status: int, payload: bytes,
                     resp_headers, replica: Replica) -> web.Response:
        headers = {"X-PIO-Fleet-Replica": replica.url}
        for h in ("X-PIO-Server-Timing", "Retry-After"):
            if h in resp_headers:
                headers[h] = resp_headers[h]
        return web.Response(
            body=payload, status=status,
            content_type="application/json", headers=headers)

    def _shadow_mirror(self, body: bytes, headers: dict,
                       served_status: int, served_body: bytes) -> None:
        """Fire-and-forget candidate mirror: the response is compared,
        never served, and a candidate outage costs nothing but a counter."""
        replica = self.candidate_balancer.pick()
        if replica is None:
            from incubator_predictionio_tpu.fleet.experiments import (
                SHADOW_MIRRORS,
            )

            SHADOW_MIRRORS.labels(outcome="error").inc()
            return

        async def mirror():
            from incubator_predictionio_tpu.fleet.experiments import (
                SHADOW_MIRRORS,
            )

            t0 = self._clock.monotonic()
            try:
                status, payload, _ = await self._post_replica(
                    replica, body, headers, self.config.deadline_sec)
            except Exception:  # noqa: BLE001 - shadow must never surface
                SHADOW_MIRRORS.labels(outcome="error").inc()
                return
            Experiment.observe(CANDIDATE, status,
                               self._clock.monotonic() - t0)
            Experiment.compare_shadow(served_status, served_body,
                                      status, payload)

        task = asyncio.get_running_loop().create_task(mirror())
        self._shadow_tasks.add(task)
        task.add_done_callback(self._shadow_tasks.discard)

    # -- shard-owner scatter/gather (docs/sharding.md) -------------------
    def _topology(self):
        from incubator_predictionio_tpu.fleet.topology import ShardTopology

        return ShardTopology(self.balancer.replicas, self._clock)

    async def _promote_owner(self, owner: Replica, rng) -> None:
        """Failover promotion: durably bump a standby's fencing epoch past
        the highest this router has observed for the range, so the deposed
        owner's rows can never re-enter a merged answer. Best-effort — a
        failed promote only delays fencing, never the query."""
        import aiohttp

        session = await self._session_or_start()
        key = self.config.server_access_key or ""
        try:
            async with session.post(
                    f"{owner.url}/shard/promote?accessKey={key}",
                    json={"epoch": rng.max_epoch},
                    timeout=aiohttp.ClientTimeout(
                        total=self.config.probe_timeout_sec)) as resp:
                if resp.status != 200:
                    return
                payload = await resp.json()
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 - best-effort
            return
        epoch = int(payload.get("epoch") or 0)
        if epoch > rng.max_epoch:
            rng.max_epoch = epoch
        if isinstance(owner.shard_owner, dict):
            owner.shard_owner["epoch"] = max(
                epoch, int(owner.shard_owner.get("epoch") or 0))
        owner.fenced = False
        logger.warning("fleet: promoted shard owner %s for rows "
                       "[%d, %d) to epoch %d", owner.url, rng.lo, rng.hi,
                       epoch)

    async def _fetch_shard(self, topo, rng, body: bytes, headers: dict,
                           deadline_at: float):
        """One shard range's partial → ``(partial dict | None,
        passthrough-response | None)``. Retries on the range's OTHER
        owners (the failover path) within the deadline; a failed-over-to
        standby is promoted first so the deposed owner is fenced. Partials
        carrying a stale epoch are discarded, never merged."""
        tried: set[str] = set()
        retry_reason: Optional[str] = None
        promote_next = False
        for _attempt in range(max(self.config.max_attempts,
                                  len(rng.owners))):
            owner = topo.pick(rng, exclude=tried)
            if owner is None:
                break
            tried.add(owner.url)
            remaining = deadline_at - self._clock.monotonic()
            if remaining <= 0:
                break
            if retry_reason is not None:
                _RETRIES.labels(reason=retry_reason).inc()
                self.retry_count += 1
                retry_reason = None
            if promote_next:
                promote_next = False
                await self._promote_owner(owner, rng)
            try:
                status, payload, resp_headers = await self._post_replica(
                    owner, body, headers, remaining,
                    path="/shard/queries.json")
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - transport failure
                # the owner is gone (SIGKILL, reset, timeout): the next
                # pick is a failover — promote it past the dead owner
                retry_reason = "error"
                promote_next = True
                continue
            if status == 200:
                try:
                    part = json.loads(payload)
                    shard = part.get("shard") or {}
                    epoch = int(shard.get("epoch") or 0)
                    part["candidates"]["ids"]  # shape check
                except (ValueError, TypeError, KeyError):
                    retry_reason = "error"
                    continue
                if epoch < rng.max_epoch:
                    # a deposed owner answered with stale rows — discard
                    # the partial outright and fence it
                    topo.fence(owner, rng.max_epoch)
                    retry_reason = "fenced"
                    continue
                if epoch > rng.max_epoch:
                    rng.max_epoch = epoch
                    if isinstance(owner.shard_owner, dict):
                        owner.shard_owner["epoch"] = epoch
                return part, None
            if status == 400:
                # query-semantic rejection: identical on every owner, the
                # client's error — pass the first one through
                return None, (status, payload, resp_headers, owner)
            retry_reason = ("overload" if status in _RETRYABLE_STATUSES
                            else "error")
        return None, None

    async def _serve_sharded(self, body: bytes, headers: dict,
                             topo) -> web.Response:
        """Scatter a query to one live owner per shard range, merge the
        partials with ``merge_topk`` (ranges ascending by lo — the
        shard-major tie discipline), assemble the /queries.json response
        shape. Missing ranges follow the declared partial policy: degrade
        (flagged + counted) or fail (504) — never an unflagged short
        answer."""
        import numpy as np

        from incubator_predictionio_tpu.serving.topk import merge_topk

        try:
            query = json.loads(body)
            if not isinstance(query, dict):
                raise ValueError("query must be a JSON object")
        except ValueError as e:
            return web.json_response(
                {"message": f"bad query: {e}"}, status=400)
        self._inflight += 1
        t0 = self._clock.monotonic()
        deadline_at = t0 + self.config.deadline_sec
        try:
            results = await asyncio.gather(*[
                self._fetch_shard(topo, rng, body, headers, deadline_at)
                for rng in topo.ranges])
            for _part, err in results:
                if err is not None:
                    status, payload, resp_headers, owner = err
                    return self._passthrough(status, payload, resp_headers,
                                             owner)
            missing = [rng for rng, (part, _e) in zip(topo.ranges, results)
                       if part is None]
            parts = [part for part, _e in results if part is not None]
            if not parts:
                self.unroutable_count += 1
                _UNROUTABLE.inc()
                return web.json_response(
                    {"message": "fleet router: no shard owner available "
                                "for any range (docs/sharding.md)"},
                    status=503, headers={"Retry-After": "1"})
            missing_rows = [[rng.lo, rng.hi] for rng in missing]
            if missing and self.config.partial_policy == "fail":
                _PARTIAL.inc()
                return web.json_response({
                    "message": "fleet router: shard range(s) unavailable "
                               "and PIO_FLEET_PARTIAL_POLICY=fail",
                    "missingRows": missing_rows,
                }, status=504)
            # merge: candidates arrive ordered by the owners' block-local
            # chains; ranges are ascending by lo, so the concatenation is
            # exactly _search_host's shard-major candidate layout. Scores
            # round-tripped f32→JSON→f64 are cast back to f32 (exact), so
            # the merge sees the owners' tie structure bit-for-bit.
            cand_ids = np.concatenate([
                np.asarray(p["candidates"]["ids"], np.int64)
                for p in parts])
            cand_sc = np.concatenate([
                np.asarray(p["candidates"]["scores"], np.float64)
                for p in parts]).astype(np.float32)
            names: dict[int, str] = {}
            for p in parts:
                names.update(zip((int(i) for i in p["candidates"]["ids"]),
                                 p["candidates"]["items"]))
            num = max(int(p["num"]) for p in parts)
            if len(cand_ids) and num > 0:
                ids, sc = merge_topk(cand_ids[None, :], cand_sc[None, :],
                                     num)
                item_scores = [
                    {"item": names[int(i)], "score": float(s)}
                    for i, s in zip(ids[0], sc[0])]
            else:
                item_scores = []
            out: dict = {"itemScores": item_scores}
            resp_headers = {"X-PIO-Fleet-Sharded": str(len(parts))}
            if missing:
                _PARTIAL.inc()
                out["partial"] = {"missingRows": missing_rows}
                resp_headers["X-PIO-Partial"] = ",".join(
                    f"rows={lo}-{hi}" for lo, hi in missing_rows)
            dt = self._clock.monotonic() - t0
            self.request_count += 1
            self.latency.record(dt)
            return web.json_response(out, headers=resp_headers)
        finally:
            self._inflight -= 1

    async def handle_query(self, request: web.Request) -> web.Response:
        if self._drain_state.draining:
            return self._drain_state.reject_response()
        body = await request.read()
        headers = self._forward_headers(request)
        # (tenant, load) routing (docs/tenancy.md): the engine id from the
        # path or the X-PIO-Engine header narrows the pick to replicas
        # that serve it; the id forwards as the header so both multi-
        # tenant and classic single-engine replicas accept the request
        tenant = (request.match_info.get("tenant")
                  or request.headers.get("X-PIO-Engine"))
        if tenant is not None:
            headers["X-PIO-Engine"] = tenant
        # shard-owner fleets route by range, not by interchangeable pick
        topo = self._topology()
        if topo.is_sharded:
            return await self._serve_sharded(body, headers, topo)
        exp = self.experiment
        arm = CONTROL
        if exp is not None:
            payload = None
            if exp.hash_field:
                try:
                    payload = json.loads(body)
                except ValueError:
                    payload = None  # replica answers the 400; control arm
            arm = exp.assign(payload)
        serve_candidate = (arm == CANDIDATE and exp is not None
                           and exp.mode == "ab"
                           and self.candidate_balancer.replicas)
        balancer = self.candidate_balancer if serve_candidate \
            else self.balancer
        self._inflight += 1
        t0 = self._clock.monotonic()
        deadline_at = t0 + self.config.deadline_sec
        tried: set[str] = set()
        last_unroutable = False
        #: why the PREVIOUS attempt failed; counted as a retry only once a
        #: new attempt actually starts (a failed final attempt is not a
        #: retry — during a full outage nothing retries, and the metric
        #: must say so)
        retry_reason: Optional[str] = None
        #: the last orderly 429/503 a replica DID answer; if the planned
        #: retry finds no alternate replica, this passes through instead
        #: of a router-fabricated 503 (the replica's pressure-derived
        #: Retry-After is real signal; "no replica available" is not)
        last_retryable = None
        try:
            for attempt in range(self.config.max_attempts):
                replica = balancer.pick(exclude=tried, tenant=tenant)
                if replica is None and serve_candidate:
                    # candidate pool exhausted: the experiment must not
                    # cost a user their answer — fall back to control
                    balancer, arm = self.balancer, CONTROL
                    replica = balancer.pick(exclude=tried, tenant=tenant)
                if replica is None:
                    last_unroutable = True
                    break
                tried.add(replica.url)
                remaining = deadline_at - self._clock.monotonic()
                if remaining <= 0:
                    break
                if retry_reason is not None:
                    _RETRIES.labels(reason=retry_reason).inc()
                    self.retry_count += 1
                    retry_reason = None
                try:
                    status, payload, resp_headers = await self._post_replica(
                        replica, body, headers, remaining)
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 - transport failure
                    retry_reason = "error"
                    continue
                if (status in _RETRYABLE_STATUSES
                        and attempt + 1 < self.config.max_attempts
                        and self._clock.monotonic() < deadline_at):
                    retry_reason = "overload"
                    last_retryable = (status, payload, resp_headers,
                                      replica)
                    continue
                dt = self._clock.monotonic() - t0
                self.request_count += 1
                self.latency.record(dt)
                if exp is not None:
                    if exp.mode == "shadow" and arm == CANDIDATE:
                        # served from control; candidate gets the mirror
                        Experiment.observe(CONTROL, status, dt)
                        self._shadow_mirror(body, headers, status, payload)
                    else:
                        Experiment.observe(arm, status, dt)
                return self._passthrough(status, payload, resp_headers,
                                         replica)
            if last_retryable is not None:
                # a replica answered an orderly 429/503 and the planned
                # retry had nowhere to go — its answer (with the real
                # pressure-derived Retry-After) beats fabricating a 503
                status, payload, resp_headers, replica = last_retryable
                dt = self._clock.monotonic() - t0
                self.request_count += 1
                self.latency.record(dt)
                if exp is not None:
                    Experiment.observe(arm, status, dt)
                return self._passthrough(status, payload, resp_headers,
                                         replica)
            # every attempt failed or nothing was routable
            self.unroutable_count += 1
            _UNROUTABLE.inc()
            reason = ("no replica available"
                      if last_unroutable else "all replicas failed")
            return web.json_response(
                {"message": f"fleet router: {reason} "
                            "(docs/serving.md \"Fleet serving\")"},
                status=503, headers={"Retry-After": "1"})
        finally:
            self._inflight -= 1

    # -- lifecycle ------------------------------------------------------
    async def start(self) -> None:
        from incubator_predictionio_tpu.obs import procstats

        # loop-lag gauge rides this server's loop (pio_process_loop_lag_*)
        self._loop_lag = procstats.start_loop_lag("fleet_router")
        self._runner = web.AppRunner(self.make_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.config.ip, self.config.port)
        await site.start()
        self.watcher.start()
        logger.info("fleet router listening on %s:%d over %d replica(s)",
                    self.config.ip, self.config.port,
                    len(self.balancer.replicas))

    async def wait_stopped(self) -> None:
        await self._stop_event.wait()
        await self.drain_and_shutdown()

    async def drain_and_shutdown(
            self, deadline_sec: Optional[float] = None) -> None:
        """New queries 503, in-flight forwards (and shadow mirrors)
        complete, then shut down within the drain deadline."""
        self._drain_state.begin()
        deadline = (drained_exit_deadline()
                    if deadline_sec is None else deadline_sec)
        await wait_for(
            lambda: self._inflight == 0 and not self._shadow_tasks,
            deadline)
        await self.shutdown()

    async def shutdown(self) -> None:
        # unregister from the process-wide registry: a later exposition
        # must not re-publish this dead router's gauges (or retain its
        # whole object graph) — bench_fleet builds several routers in one
        # process
        REGISTRY.remove_collector("fleet_router")
        lag = getattr(self, "_loop_lag", None)
        if lag is not None:
            lag.cancel()
        await self.watcher.stop()
        for task in list(self._shadow_tasks):
            task.cancel()
        if self._runner is not None:
            await self._runner.cleanup()
        if self._session is not None:
            await self._session.close()
            self._session = None
        from incubator_predictionio_tpu.obs import spool as trace_spool

        trace_spool.flush_export()


def serve_forever(config: RouterConfig) -> None:
    """Blocking entry for the CLI ``fleet route`` verb."""

    async def main():
        server = RouterServer(config)
        await server.start()
        install_signal_drain(asyncio.get_running_loop(), server._stop_event,
                             "fleet router")
        await server.wait_stopped()

    asyncio.run(main())


__all__ = ["RouterConfig", "RouterServer", "serve_forever"]
