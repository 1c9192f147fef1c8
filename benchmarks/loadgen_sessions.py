"""Open-loop generator of growing sessions: a process of its own that never
imports jax.

    python3 benchmarks/loadgen_sessions.py <spec.json>

Who sends this: an application that posts the visitor's whole session to the
deployed engine at every page view, ``{"user": key, "recent_items": [...],
"num": n}``. A pool of live sessions; each request picks one by Zipf. Most
requests are **turns** (the session has gained a few items since it was last
asked and sends its whole list again); the rest are **misses** (a session the
server has never seen replaces the pool's least recently asked one). A
session that would pass ``retire_at`` items is retired the same way.

Everything is fixed before the first send. The amount of work comes from
``schedule_seed`` alone and is the same in every run of a cell: the pool's
starting lengths, the arrival gaps, how many turns and misses, every growth
and every miss's length. The run's seed orders them, picks the sessions and
draws the item ids (Zipf over the items, popularity rank permuted by the
seed). Arrivals, sockets and latency accounting are ``loadgen.py``'s: due
instants fixed up front, latency from the due instant.

Phases: every pool session is asked once (set-up, so its cache exists when
the window opens), a short unmeasured warm-up at the cell's rate, then the
window. What the schedule implies for the server's cache (tokens it can reuse
and tokens it has to compute, request by request, if it answers in order) is
written out beside the results.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.loadgen import _exchange, judge, schedule  # noqa: E402

TURN, MISS = 0, 1


def lengths(rng, n: int, t: dict) -> np.ndarray:
    """Session lengths: lognormal, clipped."""
    raw = rng.lognormal(np.log(t["length_median"]), t["length_sigma"], n)
    return np.clip(np.round(raw), t["length_min"], t["length_max"]).astype(
        np.int64)


def work(n: int, t: dict, salt: int) -> dict:
    """The multiset of one phase: kinds, growths and miss lengths, from
    ``schedule_seed`` (``salt`` keeps the phases' draws apart)."""
    rng = np.random.default_rng([int(t["schedule_seed"]), salt])
    n_miss = int(round(n * t["miss_share"]))
    growth = np.clip(rng.geometric(1.0 / t["growth_mean"], n - n_miss), 1,
                     t["growth_max"])
    kinds = np.concatenate([np.full(n - n_miss, TURN), np.full(n_miss, MISS)])
    return {"kinds": kinds, "growth": growth,
            "miss_len": lengths(rng, n_miss, t),
            "retire_len": lengths(rng, n, t)}  # spares for retirements


def plan(spec: dict) -> dict:
    """The whole run, simulated: which session each request names and how
    long its list is then."""
    t, seed = spec, int(spec["seed"])
    rng = np.random.default_rng([seed, 1])
    n_items = int(t["vocab_size"]) - 1
    pool_n = int(t["pool"])
    item_w = np.arange(1, n_items + 1, dtype=np.float64) ** -t["item_zipf_s"]
    item_cdf = np.cumsum(item_w)
    item_of_rank = rng.permutation(n_items) + 1       # ids 1 .. n_items
    slot_w = np.arange(1, pool_n + 1, dtype=np.float64) ** -t["session_zipf_s"]
    slot_cdf = np.cumsum(slot_w)

    sessions: list = []   # final item lists, grown in place
    length: list = []     # how much of each the application has so far
    asked: list = []      # times each was asked

    def new_session(n0: int) -> int:
        total = int(t["retire_at"])
        u = rng.random(total) * item_cdf[-1]
        ranks = np.minimum(np.searchsorted(item_cdf, u), n_items - 1)
        sessions.append(item_of_rank[ranks].astype(np.int32))
        length.append(int(n0))
        asked.append(0)
        return len(sessions) - 1

    pool_len = lengths(np.random.default_rng([int(t["schedule_seed"]), 0]),
                       pool_n, t)
    pool = [new_session(n0) for n0 in rng.permutation(pool_len)]
    last_asked = list(range(pool_n))   # per slot: the clock it was asked at
    clock = pool_n
    rows = {"sid": [], "length": [], "kind": [], "extended": [],
            "reused": [], "computed": [], "phase": []}

    def ask(sid: int, kind: int, phase: int, known: int) -> None:
        rows["sid"].append(sid)
        rows["length"].append(length[sid])
        rows["kind"].append(kind)
        rows["extended"].append(asked[sid])
        rows["reused"].append(known)
        rows["computed"].append(length[sid] - known)
        rows["phase"].append(phase)
        asked[sid] += 1

    for sid in pool:                       # phase 0: the pool, once each
        ask(sid, MISS, 0, 0)

    def run_phase(n: int, phase: int) -> None:
        nonlocal clock
        w = work(n, t, phase)
        order = rng.permutation(n)
        growth, miss_len = list(w["growth"]), list(w["miss_len"])
        spare = list(w["retire_len"])
        for i in order:
            clock += 1
            kind = int(w["kinds"][i])
            if kind == TURN:
                u = rng.random() * slot_cdf[-1]
                slot = min(int(np.searchsorted(slot_cdf, u)), pool_n - 1)
                sid, g = pool[slot], int(growth.pop())
                if length[sid] + g > t["retire_at"]:
                    pool[slot] = sid = new_session(spare.pop())
                    kind, known = MISS, 0
                else:
                    known = length[sid]
                    length[sid] += g
            else:
                slot = int(np.argmin(last_asked))
                pool[slot] = sid = new_session(miss_len.pop())
                known = 0
            last_asked[slot] = clock
            ask(sid, kind, phase, known)

    n_warm = int(round(t["rate_qps"] * t["warmup_seconds"]))
    n_win = max(1, int(round(t["rate_qps"] * t["seconds"])))
    run_phase(n_warm, 1)
    run_phase(n_win, 2)
    out = {k: np.asarray(v, np.int64) for k, v in rows.items()}
    out["sessions"] = sessions
    return out


def _payload(host, port, sid, items, num) -> bytes:
    body = ('{"user": "s%d", "num": %d, "recent_items": [%s]}' % (
        sid, num, ",".join('"i%d"' % i for i in items))).encode()
    return (f"POST /queries.json HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


async def drive(spec: dict) -> dict:
    host, port, num = spec["host"], int(spec["port"]), int(spec["num"])
    timeout = float(spec["timeout_s"])
    p = plan(spec)
    phase = p["phase"]
    idx = {ph: np.flatnonzero(phase == ph) for ph in (0, 1, 2)}
    seconds = float(spec["seconds"])
    due = schedule(spec["rate_qps"], seconds, int(spec["seed"]),
                   spec.get("schedule_seed", 0))[:len(idx[2])]

    conns = [await asyncio.open_connection(host, port)
             for _ in range(int(spec["connections"]))]
    free: asyncio.Queue = asyncio.Queue()
    for c in conns:
        free.put_nowait(c)

    n = len(idx[2])
    sent, done = np.full(n, np.nan), np.full(n, np.nan)
    ok = np.zeros(n, bool)
    items = np.full((n, num), -1, np.int64)
    scores = np.full((n, num), np.nan, np.float64)
    setup_failed = 0

    async def one(row: int, slot, t0: float):
        nonlocal setup_failed
        sid = int(p["sid"][row])
        payload = _payload(host, port, sid,
                           p["sessions"][sid][:int(p["length"][row])], num)
        conn = await free.get()
        t_send = time.perf_counter()
        good = False
        try:
            status, body = await asyncio.wait_for(
                _exchange(*conn, payload), timeout)
            good, it, sc = judge(status, body, num)
            free.put_nowait(conn)
        except (asyncio.TimeoutError, OSError, asyncio.IncompleteReadError,
                ValueError):
            conn[1].close()
            try:
                free.put_nowait(await asyncio.open_connection(host, port))
            except OSError:
                pass
        if slot is None:
            setup_failed += not good
            return
        sent[slot] = t_send - t0
        done[slot] = time.perf_counter() - t0
        ok[slot] = good
        if good:
            items[slot], scores[slot] = it, sc

    # the pool, once each, a few at a time and in order
    gate = asyncio.Semaphore(int(spec["prefill_connections"]))

    async def gated(row):
        async with gate:
            await one(int(row), None, 0.0)

    await asyncio.gather(*(gated(r) for r in idx[0]))

    async def timed(rows, dues, record):
        t0 = time.perf_counter()
        tasks = []
        for j, row in enumerate(rows):
            delay = t0 + dues[j] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(
                one(int(row), j if record else None, t0)))
        if tasks:
            await asyncio.wait(tasks)

    await timed(idx[1], np.arange(len(idx[1])) / max(spec["rate_qps"], 1e-9),
                False)
    print(json.dumps({"event": "window_start", "wall": time.time(),
                      "setup_failed": int(setup_failed)}), flush=True)
    await timed(idx[2], due, True)
    print(json.dumps({"event": "window_end", "wall": time.time()}),
          flush=True)
    while not free.empty():
        free.get_nowait()[1].close()
    win = idx[2]
    starts = np.cumsum([0] + [len(s) for s in p["sessions"]])
    np.savez(
        spec["out"], due=due, sent=sent, done=done, ok=ok, items=items,
        scores=scores, sid=p["sid"][win], length=p["length"][win],
        kind=p["kind"][win], extended=p["extended"][win],
        reused=p["reused"][win], computed=p["computed"][win],
        sess_flat=np.concatenate(p["sessions"]), sess_start=starts)
    return {"event": "done", "requests": int(n), "ok": int(ok.sum()),
            "setup_failed": int(setup_failed)}


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        _spec = json.load(f)
    print(json.dumps(asyncio.run(drive(_spec))), flush=True)
