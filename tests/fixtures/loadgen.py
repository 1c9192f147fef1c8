"""Shared raw-socket HTTP/1.1 load generator — ONE implementation behind
both the ``drills.py overload`` drill (spawned as a subprocess via
``bench_main``) and the chaos storm test (imported in-process).

Raw keep-alive sockets, not aiohttp: the client shares the host's cores
with the server under test, and an aiohttp client costs more per request
than the server's whole handler — measuring through it reports the
client, not the server (same rationale as the ingestion drill's
driver).

Load shapes:

- :func:`closed_loop` — N connections, each fires its next request when
  the previous answers: self-throttling, the capacity-measurement shape.
- :func:`open_loop` — request slots are scheduled at the offered rate
  whether or not earlier requests finished — the closed-loop client's
  implicit self-throttling is exactly what real overload does NOT do.

Error statuses (429/504) are counted, not raised, and connections stay
keep-alive across them — shed traffic must keep offering load.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import time
import urllib.parse


def request_bytes(host: str, port: int, body: bytes,
                  path: str = "/queries.json") -> bytes:
    return (f"POST {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


async def post(r, w, req: bytes):
    """One request/response on a kept-alive connection →
    ``(status, degraded, latency_ms)``."""
    t0 = time.perf_counter()
    w.write(req)
    await w.drain()
    status = int((await r.readline()).split()[1])
    length = None
    while True:
        line = await r.readline()
        if line in (b"\r\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":")[1])
    body = await r.readexactly(length)
    return status, b'"degraded"' in body, (time.perf_counter() - t0) * 1e3


def pct(vals, q: float) -> float:
    a = sorted(vals)
    return a[min(len(a) - 1, int(q * (len(a) - 1)))] if a else 0.0


def attempted_qps(counts: dict, duration: float) -> float:
    """Requests actually put on the wire per second — the *achieved*
    offered rate (int keys only: 'degraded' shadows a 200 already
    counted). Under heavy backend slowness this falls below the nominal
    open-loop target; reporting it keeps the bench honest."""
    n = sum(v for k, v in counts.items() if isinstance(k, int))
    return n / duration


def _track(counts: dict, lat_ms: list, status: int, degraded: bool,
           ms: float) -> None:
    counts[status] = counts.get(status, 0) + 1
    if status == 200:
        lat_ms.append(ms)
        if degraded:
            counts["degraded"] = counts.get("degraded", 0) + 1


async def closed_loop(host: str, port: int, n_conns: int, duration: float,
                      req_fn) -> tuple[dict, list]:
    """``req_fn() -> bytes`` supplies each request (stateful closures give
    per-request variety). Returns ``(status counts, 200-latencies ms)``."""
    conns = [await asyncio.open_connection(host, port)
             for _ in range(n_conns)]
    stop_at = time.perf_counter() + duration
    counts: dict = {}
    lat_ms: list = []

    async def worker(conn):
        while time.perf_counter() < stop_at:
            _track(counts, lat_ms, *(await post(*conn, req_fn())))

    await asyncio.gather(*(worker(c) for c in conns))
    for _, w in conns:
        w.close()
    return counts, lat_ms


async def open_loop(host: str, port: int, n_conns: int, duration: float,
                    target_qps: float, req_fn) -> tuple[dict, list]:
    conns = [await asyncio.open_connection(host, port)
             for _ in range(n_conns)]
    t0 = time.perf_counter()
    slots = itertools.count()
    counts: dict = {}
    lat_ms: list = []

    async def worker(conn):
        while True:
            t_sched = t0 + next(slots) / target_qps
            if t_sched - t0 >= duration:
                return
            now = time.perf_counter()
            # WALL-time cutoff, not just scheduled-time: when the backend
            # answers slower than the offered rate, workers fall behind
            # their slots — without this, every scheduled slot still fires
            # long after the window closed, the phase stretches to
            # slots/served_rate seconds, and counts/duration inflates
            # goodput by the overrun factor (a slow fleet would *measure*
            # faster). Slots the client could not offer in the window are
            # dropped; the achieved rate is in the returned counts.
            if now - t0 >= duration:
                return
            if t_sched > now:
                await asyncio.sleep(t_sched - now)
            _track(counts, lat_ms, *(await post(*conn, req_fn())))

    await asyncio.gather(*(worker(c) for c in conns))
    for _, w in conns:
        w.close()
    return counts, lat_ms


def three_phase(base_url: str, warm_s: float, cap_s: float, over_s: float,
                req_fn, overload_factor: float = 3.0) -> dict:
    """The ``drills.py overload`` protocol: serial warm (strictly below
    capacity, where zero sheds are allowed) → 16-conn closed-loop capacity
    → open-loop at ``overload_factor``× the measured capacity."""
    host = urllib.parse.urlsplit(base_url).hostname
    port = urllib.parse.urlsplit(base_url).port

    async def main() -> dict:
        r, w = await asyncio.open_connection(host, port)
        await post(r, w, req_fn())  # warmup round trip
        w.close()
        warm_counts, warm_lat = await closed_loop(
            host, port, 1, warm_s, req_fn)
        cap_counts, cap_lat = await closed_loop(
            host, port, 16, cap_s, req_fn)
        cap_qps = cap_counts.get(200, 0) / cap_s
        over_counts, over_lat = await open_loop(
            host, port, 48, over_s, overload_factor * max(cap_qps, 1.0),
            req_fn)
        return {
            "warm": {"counts": {str(k): v for k, v in warm_counts.items()},
                     "p99_ms": round(pct(warm_lat, 0.99), 2)},
            "capacity": {
                "qps": round(cap_qps, 1),
                "p50_ms": round(pct(cap_lat, 0.5), 2),
                "p99_ms": round(pct(cap_lat, 0.99), 2),
                "counts": {str(k): v for k, v in cap_counts.items()}},
            "overload": {
                "offered_qps": round(overload_factor * cap_qps, 1),
                "achieved_qps": round(attempted_qps(over_counts, over_s), 1),
                "goodput_qps": round(over_counts.get(200, 0) / over_s, 1),
                "p50_ms": round(pct(over_lat, 0.5), 2),
                "p99_ms": round(pct(over_lat, 0.99), 2),
                "counts": {str(k): v for k, v in over_counts.items()}},
        }

    return asyncio.run(main())


def fixed_load(base_url: str, warm_s: float, over_s: float,
               offered_qps: float, req_fn, n_conns: int = 48) -> dict:
    """Warm (single closed-loop connection) then open-loop at a FIXED
    offered rate — the ``drills.py fleet`` comparison shape: the same
    absolute load offered to different fleet topologies, so goodput/p99
    deltas are the topology's, not the load's."""
    host = urllib.parse.urlsplit(base_url).hostname
    port = urllib.parse.urlsplit(base_url).port

    async def main() -> dict:
        r, w = await asyncio.open_connection(host, port)
        await post(r, w, req_fn())  # warmup round trip
        w.close()
        warm_counts, warm_lat = await closed_loop(
            host, port, 1, warm_s, req_fn)
        over_counts, over_lat = await open_loop(
            host, port, n_conns, over_s, offered_qps, req_fn)
        return {
            "warm": {"counts": {str(k): v for k, v in warm_counts.items()},
                     "p99_ms": round(pct(warm_lat, 0.99), 2)},
            "overload": {
                "offered_qps": round(offered_qps, 1),
                "achieved_qps": round(attempted_qps(over_counts, over_s), 1),
                "goodput_qps": round(over_counts.get(200, 0) / over_s, 1),
                "p50_ms": round(pct(over_lat, 0.5), 2),
                "p99_ms": round(pct(over_lat, 0.99), 2),
                "counts": {str(k): v for k, v in over_counts.items()}},
        }

    return asyncio.run(main())


def _rotating_user_req_fn(base: str, n_users: int):
    host = urllib.parse.urlsplit(base).hostname
    port = urllib.parse.urlsplit(base).port
    seq = itertools.count()

    def req_fn() -> bytes:
        # rotating user ids: enough variety to exercise the real
        # recommendation path without an RNG dependency in the client
        body = json.dumps({"user": f"u{next(seq) % n_users}",
                           "num": 10}).encode()
        return request_bytes(host, port, body)

    return req_fn


def bench_main(argv: list[str]) -> None:
    """Subprocess entry for ``drills.py overload``:
    ``argv = [base_url, warm_s, cap_s, over_s, n_users]``. Prints one JSON
    line of the three-phase results."""
    base, warm_s, cap_s, over_s, n_users = (
        argv[0], float(argv[1]), float(argv[2]), float(argv[3]),
        int(argv[4]))
    print(json.dumps(three_phase(
        base, warm_s, cap_s, over_s, _rotating_user_req_fn(base, n_users))))


def tenant_main(argv: list[str]) -> None:
    """Subprocess entry for per-tenant drivers (the multi-tenant chaos
    test and ``drills.py multi_tenant``): drive ONE tenant's path at a
    fixed open-loop rate from its own process, so concurrent tenant
    drivers cannot pollute each other's latency measurements through
    client-side GIL/scheduler contention.

    ``argv = [host, port, path, duration_s, target_qps, n_conns, body]``.
    Prints one JSON line: status counts + p50/p99 of the 200s."""
    host, port, path, duration, qps, n_conns, body = (
        argv[0], int(argv[1]), argv[2], float(argv[3]), float(argv[4]),
        int(argv[5]), argv[6].encode())
    req = request_bytes(host, port, body, path=path)
    counts, lat = asyncio.run(
        open_loop(host, port, n_conns, duration, qps, lambda: req))
    print(json.dumps({
        "counts": {str(k): v for k, v in counts.items()},
        "goodput_qps": round(counts.get(200, 0) / duration, 1),
        "p50_ms": round(pct(lat, 0.5), 2),
        "p99_ms": round(pct(lat, 0.99), 2),
    }))


def fleet_main(argv: list[str]) -> None:
    """Subprocess entry for ``drills.py fleet``:
    ``argv = [base_url, warm_s, cap_s, over_s, n_users, offered_qps]``.
    ``offered_qps <= 0`` runs the full three-phase protocol (measuring
    capacity, overload at 3×); ``> 0`` skips capacity measurement and
    drives the open loop at that absolute rate (``cap_s`` is unused) —
    the fixed-offered-load topology comparison."""
    base, warm_s, cap_s, over_s, n_users, offered = (
        argv[0], float(argv[1]), float(argv[2]), float(argv[3]),
        int(argv[4]), float(argv[5]))
    req_fn = _rotating_user_req_fn(base, n_users)
    if offered > 0:
        # each keep-alive connection awaits its response before taking the
        # next slot, so achievable rate is capped at n_conns / latency —
        # at saturation (latency ~= the 1s-scale micro-batch drain) 48
        # conns silently under-offer and the comparison measures the
        # CLIENT. Size the pool to sustain ~1s latency at the target rate.
        n_conns = min(max(48, int(offered)), 512)
        print(json.dumps(fixed_load(base, warm_s, over_s, offered, req_fn,
                                    n_conns=n_conns)))
    else:
        print(json.dumps(three_phase(base, warm_s, cap_s, over_s, req_fn)))
