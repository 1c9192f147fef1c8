"""The open-loop generator on loopback: the schedule is fixed before the
first send and the same multiset for every seed, latency runs from the due
instant (so a stall is paid by the requests it delayed), lag is reported, and
a bad reply is a failure."""

import asyncio
import json

import numpy as np

from benchmarks import loadgen


def test_schedule_is_one_multiset_in_seeded_order():
    a, b = loadgen.schedule(200, 3.0, seed=1), loadgen.schedule(200, 3.0, seed=2)
    assert len(a) == len(b) == 600 and a[0] == 0.0
    assert a[-1] < 3.0 and np.all(np.diff(a) >= 0)
    ga, gb = np.diff(a), np.diff(b)
    assert not np.allclose(ga, gb)
    # the same gaps, in another order (the first gap anchors at 0)
    full = np.random.default_rng(0).exponential(1.0, 600)
    full *= 3.0 / full.sum()
    for due, seed in ((a, 1), (b, 2)):
        gaps = np.random.default_rng(seed).permutation(full)
        assert np.allclose(np.diff(due), gaps[1:])
    assert np.array_equal(a, loadgen.schedule(200, 3.0, seed=1))


def test_zipf_users_are_skewed_known_ids():
    u = loadgen.zipf_users(20000, 5000, 0.8, seed=3)
    assert u.min() >= 0 and u.max() < 5000
    counts = np.sort(np.bincount(u, minlength=5000))[::-1]
    assert counts[:50].sum() > 5 * counts[-50:].sum() + 50
    assert np.array_equal(u, loadgen.zipf_users(20000, 5000, 0.8, seed=3))
    assert len(np.unique(loadgen.zipf_users(5000, 5000, 0.0, seed=1))) > 2500


def test_judge_counts_only_whole_finite_answers():
    rows = [{"item": f"i{n}", "score": 1.0 - n / 10} for n in range(10)]
    ok = lambda obj, status=b"HTTP/1.1 200 OK\r\n": loadgen.judge(
        status, json.dumps(obj).encode(), 10)[0]
    assert ok({"itemScores": rows})
    assert not ok({"itemScores": rows[:9]})
    assert not ok({"itemScores": rows, "degraded": True})
    assert not ok({"itemScores": rows}, b"HTTP/1.1 503 Busy\r\n")
    assert not ok({"message": "no"})
    assert not loadgen.judge(b"HTTP/1.1 200 OK\r\n", json.dumps(
        {"itemScores": rows[:9] + [{"item": "i9", "score": float("nan")}]}
    ).encode(), 10)[0]


class _Server:
    """Answers every query after ``delay`` seconds; one stall of ``stall``
    seconds holds the (single-threaded) server while request ``stall_at``
    is answered."""

    def __init__(self, delay, stall_at=None, stall=0.0):
        self.delay, self.stall_at, self.stall, self.n = delay, stall_at, stall, 0
        self.lock = asyncio.Lock()

    async def handle(self, reader, writer):
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                length = int([l for l in head.split(b"\r\n")
                              if l.lower().startswith(b"content-length")][0]
                             .split(b":")[1])
                await reader.readexactly(length)
                async with self.lock:  # one batch at a time, like a device
                    self.n += 1
                    await asyncio.sleep(
                        self.stall if self.n == self.stall_at else self.delay)
                body = json.dumps({"itemScores": [
                    {"item": f"i{k}", "score": 2.0 - k / 10}
                    for k in range(10)]}).encode()
                writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: "
                             + str(len(body)).encode() + b"\r\n\r\n" + body)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()  # or Server.wait_closed() waits for ever (3.12)


def _drive(tmp_path, rate, seconds, **server_kw):
    out = str(tmp_path / "out.npz")

    async def main():
        fake = _Server(**server_kw)
        srv = await asyncio.start_server(fake.handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        spec = {"host": "127.0.0.1", "port": port, "seed": 5,
                "seconds": seconds, "rate_qps": rate, "out": out,
                "n_users": 1000, "zipf_s": 0.8, "num": 10, "connections": 8,
                "warmup_seconds": 0.2, "timeout_s": 5.0, "schedule_seed": 0}
        async with srv:
            return await loadgen.drive(spec)

    done = asyncio.run(main())
    r = dict(np.load(out))
    return done, r, loadgen.summarize(r["due"], r["sent"], r["done"], r["ok"],
                                      seconds)


def test_open_loop_latency_runs_from_the_due_instant(tmp_path):
    done, r, s = _drive(tmp_path, rate=100, seconds=1.5, delay=0.002)
    assert done["requests"] == 150 == s["attempted"] and s["failed"] == 0
    # answers completed inside the window: all but the one or two in flight
    # when it closed
    assert 90.0 <= s["qps"] <= 100.0
    assert 2.0 <= s["p50_ms"] < 20.0 and s["lag_p99_ms"] < 20.0
    assert np.all(r["sent"] >= r["due"] - 1e-4)
    assert (r["items"][0] == np.arange(10)).all()


def test_a_stall_is_paid_by_every_request_it_delayed(tmp_path):
    _, r, s = _drive(tmp_path, rate=100, seconds=1.5, delay=0.001,
                     stall_at=40, stall=0.3)
    lat = (r["done"] - r["due"]) * 1e3
    # sends went on during the stall (open loop), and those requests waited
    assert (lat > 100).sum() >= 10
    assert s["p99_ms"] > 150 and s["p50_ms"] < 100
    assert s["p50_ms"] <= s["p90_ms"] <= s["p95_ms"] <= s["p99_ms"]
    assert s["failed"] == 0
    # the stall shows as one run of slow requests, about as long as it was
    runs = loadgen.stalls(r["due"], r["done"], r["ok"], over_ms=100.0)
    assert len(runs) == 1 and runs[0][1] >= 10 and 200 < runs[0][2] < 450
    assert loadgen.stalls(r["due"], r["done"], r["ok"], over_ms=2000.0) == []


def test_a_failed_request_counts_as_the_window_length():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    s = loadgen.summarize(due, due + 0.001, due + 0.004,
                          np.array([True, True, True, False]), seconds=2.0)
    assert s["failed"] == 1 and s["qps"] == 1.5 and s["mean_ms"] > 500.0
    assert abs(s["p99_ms"] - 2000.0) < 100.0 and abs(s["p50_ms"] - 4.0) < 1.0


def test_an_answer_after_the_window_is_not_work_the_window_did():
    """serve_qps is answers completed inside [0, seconds] per second of
    window: a stall at the end lowers it though no request failed."""
    due = np.linspace(0.0, 1.99, 200)
    done = due + 0.004
    ok = np.ones(200, bool)
    steady = loadgen.summarize(due, due, done, ok, seconds=2.0)
    assert steady["qps"] == 100.0 and steady["failed"] == 0
    done[-40:] = 2.0 + 0.3  # the last 40 wait for a stall that outlasts it
    late = loadgen.summarize(due, due, done, ok, seconds=2.0)
    assert late["failed"] == 0 and late["qps"] == 80.0
    assert late["p95_ms"] > 300.0 and late["p50_ms"] < 5.0
