"""Open-loop load generator: a process of its own that never imports jax.

    python3 benchmarks/loadgen.py <spec.json>

Arrivals are fixed before the first request is sent and do not wait for
replies: each request is due at its instant, goes out on the first free of
``connections`` keep-alive sockets, and its latency runs from the instant it
was DUE to the last byte of the reply, so a stall is paid by every request it
delayed. How late requests left (``lag``) is reported, so a starved generator
is not read as a fast server.

The schedule is a fixed multiset drawn from ``schedule_seed`` (the same gaps
in every run of a cell) whose ORDER, and the users asked for, come from the
run's seed: seeds change the order of the work, not its amount.
"""

from __future__ import annotations

import asyncio
import json
import math
import sys
import time

import numpy as np

USER_STRIDE = 2_654_435_761  # odd, so rank -> id is a bijection mod n_users


def schedule(rate: float, seconds: float, seed: int, schedule_seed: int = 0):
    """Due instants (seconds from window start) of ``round(rate*seconds)``
    requests: exponential gaps drawn from ``schedule_seed``, rescaled to fill
    the window exactly, permuted by ``seed``."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(schedule_seed).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due


def zipf_users(n: int, n_users: int, s: float, seed: int) -> np.ndarray:
    """``n`` user indices, Zipf(``s``) over ``n_users`` popularity ranks
    (inverse CDF; ``s`` < 1 is fine), rank r being user ``r*STRIDE mod n``."""
    w = np.arange(1, n_users + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(w)
    u = np.random.default_rng(seed + 1).random(n) * cdf[-1]
    rank = np.minimum(np.searchsorted(cdf, u), n_users - 1)
    return (rank * USER_STRIDE) % n_users


def _request(host: str, port: int, user: int, num: int) -> bytes:
    body = json.dumps({"user": f"u{user}", "num": num}).encode()
    return (f"POST /queries.json HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


async def _exchange(reader, writer, payload: bytes):
    writer.write(payload)
    await writer.drain()
    status = await reader.readline()
    length = None
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":")[1])
    body = await reader.readexactly(length) if length else b""
    return status, body


def judge(status: bytes, body: bytes, num: int):
    """(ok, items, scores): a reply counts only as 200, not degraded, ``num``
    itemScores, every score finite."""
    if b" 200 " not in status:
        return False, None, None
    try:
        obj = json.loads(body)
        rows = obj["itemScores"]
        if "degraded" in obj or len(rows) != num:
            return False, None, None
        items = [int(r["item"][1:]) for r in rows]
        scores = [float(r["score"]) for r in rows]
    except (ValueError, KeyError, TypeError):
        return False, None, None
    if not all(math.isfinite(s) for s in scores):
        return False, None, None
    return True, items, scores


async def drive(spec: dict) -> dict:
    host, port = spec["host"], int(spec["port"])
    num, n_conn = int(spec["num"]), int(spec["connections"])
    seconds, warm = float(spec["seconds"]), float(spec["warmup_seconds"])
    timeout = float(spec["timeout_s"])
    due = schedule(spec["rate_qps"], seconds, spec["seed"],
                   spec.get("schedule_seed", 0))
    users = zipf_users(len(due), spec["n_users"], spec["zipf_s"], spec["seed"])
    n_warm = int(round(spec["rate_qps"] * warm))
    warm_due = np.arange(n_warm) / max(spec["rate_qps"], 1e-9)
    warm_users = zipf_users(max(n_warm, 1), spec["n_users"], spec["zipf_s"],
                            spec["seed"] + 7919)[:n_warm]

    conns = [await asyncio.open_connection(host, port) for _ in range(n_conn)]
    free: asyncio.Queue = asyncio.Queue()
    for c in conns:
        free.put_nowait(c)

    n = len(due)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    items = np.full((n, num), -1, np.int64)
    scores = np.full((n, num), np.nan, np.float64)

    async def one(i, user, t0, record):
        conn = await free.get()
        t_send = time.perf_counter()
        good = False
        try:
            status, body = await asyncio.wait_for(
                _exchange(*conn, _request(host, port, int(user), num)),
                timeout)
            good, it, sc = judge(status, body, num)
            free.put_nowait(conn)
        except (asyncio.TimeoutError, OSError, asyncio.IncompleteReadError,
                ValueError):
            # the socket's state is unknown: replace it, count a failure
            conn[1].close()
            try:
                free.put_nowait(await asyncio.open_connection(host, port))
            except OSError:
                pass
        if record:
            sent[i] = t_send - t0
            done[i] = time.perf_counter() - t0
            ok[i] = good
            if good:
                items[i], scores[i] = it, sc

    async def phase(dues, who, record):
        t0 = time.perf_counter()
        tasks = []
        for i in range(len(dues)):
            delay = t0 + dues[i] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(one(i, who[i], t0, record)))
        if tasks:
            await asyncio.wait(tasks)
        return t0

    await phase(warm_due, warm_users, False)
    print(json.dumps({"event": "window_start", "wall": time.time()}),
          flush=True)
    await phase(due, users, True)
    print(json.dumps({"event": "window_end", "wall": time.time()}),
          flush=True)
    while not free.empty():
        free.get_nowait()[1].close()
    np.savez(spec["out"], due=due, sent=sent, done=done, ok=ok, users=users,
             items=items, scores=scores)
    return {"event": "done", "requests": int(n), "ok": int(ok.sum())}


def summarize(due, sent, done, ok, seconds: float,
              limit_ms: float | None = None) -> dict:
    """What the client saw, over ALL requests of the window: latency from the
    due instant in ms (a failed request counts as the window length), the
    share of them answered inside the cell's ``limit_ms`` (the limit its knee
    is defined by), the generator's own lag, and the answers completed INSIDE
    the window per
    second of window: an answer that comes after the window has closed is
    work the window did not finish, so a stall near its end, a queue that
    grows, or a failure each lower the rate."""
    lat = np.where(ok, (done - due) * 1e3, seconds * 1e3)
    lag = (sent - due)[np.isfinite(sent)] * 1e3
    inside = ok & (done <= seconds)
    out = {
        "attempted": int(len(due)),
        "failed": int((~ok).sum()),
        "mean_ms": float(lat.mean()),
        "qps": float(inside.sum() / seconds),
        "lag_p99_ms": float(np.percentile(lag, 99)) if len(lag) else None,
    }
    for q in (50, 90, 95, 99):
        out[f"p{q}_ms"] = float(np.percentile(lat, q))
    if limit_ms is not None:
        out["within_limit_pct"] = float(100.0 * (lat <= limit_ms).mean())
    return out


def stalls(due, done, ok, over_ms: float, gap_s: float = 0.05,
           top: int = 8) -> list:
    """Runs of requests slower than ``over_ms``, grouped where their due
    instants lie within ``gap_s`` of each other: ``[first due s, requests,
    slowest ms]``, the ``top`` slowest. A pause of the server shows as one run
    whose slowest request waited about the pause's length."""
    lat = np.where(ok, (done - due) * 1e3, np.inf)
    slow = np.flatnonzero(lat > over_ms)
    if not len(slow):
        return []
    cuts = np.flatnonzero(np.diff(due[slow]) > gap_s) + 1
    runs = [[round(float(due[r[0]]), 3), int(len(r)),
             round(float(lat[r].max()), 1)] for r in np.split(slow, cuts)]
    return sorted(runs, key=lambda r: -r[2])[:top]


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        _spec = json.load(f)
    print(json.dumps(asyncio.run(drive(_spec))), flush=True)
