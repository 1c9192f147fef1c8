"""The program's one span primitive (obs/trace.span, ISSUE 24): what every
span feeds (ring, per-phase aggregate, /metrics, the profiler's timeline),
the spans the two measured paths open — a whole ``run_train`` and one
``POST /queries.json`` — and the device-side names the benchmark matches.

Everything runs on the CPU backend; the timeline cases capture a real
``jax.profiler`` trace there (a ``TraceAnnotation`` lands on ``/host:CPU``
whatever the device is).
"""

from __future__ import annotations

import asyncio
import dataclasses
import datetime as dt
import glob
import hashlib
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from incubator_predictionio_tpu.core import (
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
    Params,
    PDataSource,
)
from incubator_predictionio_tpu.core.workflow import run_train
from incubator_predictionio_tpu.data.storage import Storage
from incubator_predictionio_tpu.data.storage.base import EngineInstance
from incubator_predictionio_tpu.obs import profile as prof
from incubator_predictionio_tpu.obs import trace
from incubator_predictionio_tpu.obs.metrics import REGISTRY, parse_prometheus_text
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.templates.recommendation import (
    ALSAlgorithm,
    TrainingData,
)

FACTORY = "tests.test_program_spans.SpanEngine"


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def _occupancy(spans: list) -> list:
    return [s for s in spans if s["name"].startswith("serve.server.")]


def _phase_row(family: str, scope: str, phase: str) -> float:
    """One row of the aggregate as a scrape of ``/metrics`` reads it."""
    samples = parse_prometheus_text(REGISTRY.expose())[family]["samples"]
    return next((value for _, labels, value in samples
                 if labels == {"scope": scope, "phase": phase}), 0.0)


def test_span_feeds_ring_aggregate_and_metrics():
    prof.reset_phases()
    buf = trace.TraceBuffer()
    before = _phase_row("pio_profile_phases_total", "t.unit", "work")
    with trace.span("t.unit.work", buffer=buf, size=3) as sp:
        time.sleep(0.002)
    (row,) = buf.spans()
    assert row["name"] == "t.unit.work" and row["attrs"] == {"size": 3}
    assert row["durationSec"] == sp.duration >= 0.002
    snap = prof.phase_snapshot()["t.unit"]
    assert snap["phases"]["work"] == {"seconds": sp.duration, "count": 1}
    # no span encloses the scope: its wall is the sum of its phases
    assert snap["count"] == 0 and snap["wall_seconds"] == sp.duration
    assert _phase_row("pio_profile_phases_total", "t.unit", "work") \
        == before + 1
    assert _phase_row("pio_profile_phase_seconds_total", "t.unit", "work") > 0
    # a bare name or a route's is a span like any other, and no phase
    with trace.span("forward", buffer=buf):
        with trace.span("POST /queries.json", buffer=buf):
            pass
    assert set(prof.phase_snapshot()) == {"t.unit"}
    assert [s["name"] for s in buf.spans()] == [
        "t.unit.work", "POST /queries.json", "forward"]


def test_enclosing_span_books_its_scopes_wall():
    """A span whose own name is a scope with phases (children exit first)
    adds the scope's wall: the unattributed remainder shows."""
    prof.reset_phases()
    buf = trace.TraceBuffer()
    with trace.span("t.verb", buffer=buf) as root:
        with trace.span("t.verb.read", buffer=buf) as a:
            time.sleep(0.001)
        time.sleep(0.002)  # under no child
        with trace.span("t.verb.fit", buffer=buf) as b:
            time.sleep(0.001)
    snap = prof.phase_snapshot()["t.verb"]
    assert snap["count"] == 1 and snap["wall_seconds"] == root.duration
    assert set(snap["phases"]) == {"read", "fit"}
    assert root.duration - a.duration - b.duration >= 0.002
    # the root is itself a phase of the scope above it
    assert prof.phase_snapshot()["t"]["phases"]["verb"]["count"] == 1
    rows = buf.spans()
    assert {r["parentId"] for r in rows if r["name"] != "t.verb"} \
        == {root.span_id}
    assert len({r["traceId"] for r in rows}) == 1


def _pio_events(log_dir: str) -> tuple[list, list]:
    """(``pio.*`` events, all other events) of the capture's ``/host:CPU``
    plane, as ``(name, start_ns, end_ns)``."""
    (path,) = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    ours, rest = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                row = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                (ours if e.name.startswith("pio.") else rest).append(row)
    return ours, rest


def _capture(log_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def test_span_lies_on_the_profilers_timeline_around_a_jitted_call(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    _capture(str(tmp_path))
    try:
        with trace.span("t.timeline.outer", buffer=trace.TraceBuffer()):
            with trace.span("t.timeline.inner",
                            buffer=trace.TraceBuffer()):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ours, rest = _pio_events(str(tmp_path))
    by_name = {n: (s, e) for n, s, e in ours}
    assert set(by_name) == {"pio.t.timeline.outer", "pio.t.timeline.inner"}
    o, i = by_name["pio.t.timeline.outer"], by_name["pio.t.timeline.inner"]
    assert o[0] <= i[0] and i[1] <= o[1]
    runs = [(s, e) for n, s, e in rest if n == "PjRtCpuExecutable::Execute"]
    assert runs and all(i[0] <= s and e <= i[1] for s, e in runs)


def test_explicit_and_await_crossing_spans_stay_off_the_timeline(tmp_path):
    prof.reset_phases()
    buf = trace.TraceBuffer()
    _capture(str(tmp_path))
    try:
        with trace.span("t.loop.route", buffer=buf, thread_scoped=False) as r:
            t0 = time.perf_counter()
            time.sleep(0.002)
            q = trace.record_span("t.loop.queue", t0,
                                  time.perf_counter() - t0, buffer=buf,
                                  depth=2)
            with trace.span("t.loop.sync", buffer=buf):
                pass
    finally:
        jax.profiler.stop_trace()
    ours, _ = _pio_events(str(tmp_path))
    assert [n for n, _, _ in ours] == ["pio.t.loop.sync"]
    rows = {s["name"]: s for s in buf.spans()}
    assert set(rows) == {"t.loop.route", "t.loop.queue", "t.loop.sync"}
    # the explicit span is a child of the context it was recorded in, keeps
    # the interval it was given, and feeds the aggregate like any other
    assert rows["t.loop.queue"]["parentId"] == r.span_id
    assert rows["t.loop.queue"]["traceId"] == r.trace_id
    assert rows["t.loop.queue"]["attrs"] == {"depth": 2}
    assert q.duration >= 0.002
    assert rows["t.loop.queue"]["startUnix"] == pytest.approx(
        rows["t.loop.route"]["startUnix"], abs=1e-3)
    phases = prof.phase_snapshot()["t.loop"]["phases"]
    assert phases["queue"] == {"seconds": q.duration, "count": 1}
    assert set(phases) == {"route", "queue", "sync"}


def test_span_backdated_to_where_its_interval_began():
    buf = trace.TraceBuffer()
    t0 = time.perf_counter()
    time.sleep(0.003)  # the wait before the block could be entered
    with trace.span("t.req.respond", buffer=buf, start=t0) as sp:
        pass
    (row,) = buf.spans()
    assert row["durationSec"] == sp.duration >= 0.003
    assert row["startUnix"] == pytest.approx(time.time() - sp.duration,
                                             abs=1e-3)


def test_explicit_span_under_a_captured_context():
    """The batcher's case: the interval began on another task, whose
    ``contextvars.Context`` was kept beside the queue entry."""
    import contextvars

    buf = trace.TraceBuffer()
    with trace.span("t.req.route", buffer=buf) as route:
        kept = contextvars.copy_context()
    assert trace.current_context() is None
    got = trace.record_span("t.req.queue", time.perf_counter() - 0.5, 0.25,
                            context=trace.context_of(kept), buffer=buf)
    assert (got.trace_id, got.parent_id) == (route.trace_id, route.span_id)
    assert got.duration == 0.25
    assert got.start_unix == pytest.approx(time.time() - 0.5, abs=0.05)
    assert trace.context_of(contextvars.copy_context()) is None


def test_self_time_is_duration_minus_what_children_cover():
    """Replaces the phase-conservation pair: for a nested tree, each span's
    self time is its duration less the union of its children's intervals —
    overlapping children are not subtracted twice, a child reaching past
    its parent is clipped — and the self times sum to the root."""
    buf = trace.TraceBuffer()
    t = time.perf_counter() - 10.0

    def rec(name, start, dur, parent=None):
        return trace.record_span(
            name, t + start, dur, buffer=buf,
            context=None if parent is None else parent.context)

    root = rec("t.tree.root", 0.0, 1.0)
    a = rec("t.tree.a", 0.1, 0.3, root)         # 0.1 .. 0.4
    b = rec("t.tree.b", 0.3, 0.3, root)         # 0.3 .. 0.6 overlaps a
    c = rec("t.tree.c", 0.9, 0.3, root)         # 0.9 .. 1.2 past the root
    a1 = rec("t.tree.a1", 0.15, 0.1, a)
    rows = buf.spans(root.trace_id)
    own = trace.self_seconds(rows)
    approx = lambda v: pytest.approx(v, abs=1e-5)  # noqa: E731
    assert own[root.span_id] == approx(1.0 - 0.5 - 0.1)
    assert own[a.span_id] == approx(0.2)
    assert own[a1.span_id] == approx(0.1) and own[b.span_id] == approx(0.3)
    assert own[c.span_id] == approx(0.3)
    # a sequential tree conserves: self times sum to the root's duration
    seq = trace.TraceBuffer()
    with trace.span("t.seq.root", buffer=seq) as r:
        for phase in ("h2d", "compute", "gather"):
            with trace.span(f"t.seq.{phase}", buffer=seq):
                time.sleep(0.001)
    own = trace.self_seconds(seq.spans())
    assert sum(own.values()) == pytest.approx(r.duration, abs=1e-4)
    # served with every trace
    (entry,) = seq.traces()
    assert {s["spanId"]: s["selfSec"] for s in entry["spans"]} == own


def test_spans_from_many_threads_lose_no_update():
    """Worker threads and the event loop feed one aggregate: more threads
    than cores, a shortened switch interval, and every span counted."""
    import sys
    import threading

    prof.reset_phases()
    buf = trace.TraceBuffer(capacity=16)
    before = _phase_row("pio_profile_phases_total", "t.stress", "work")
    n_threads, per_thread = 4 * (os.cpu_count() or 2), 300

    def work():
        for _ in range(per_thread):
            with trace.span("t.stress.work", buffer=buf):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    total = n_threads * per_thread
    assert prof.phase_snapshot()["t.stress"]["phases"]["work"]["count"] \
        == total
    assert _phase_row("pio_profile_phases_total", "t.stress", "work") \
        == before + total
    assert len(buf.spans()) == 16  # the ring stays bounded
    assert trace.current_context() is None


def test_span_marks_errors_and_respects_a_classified_status():
    buf = trace.TraceBuffer()
    with pytest.raises(KeyError):
        with trace.span("t.err.raised", buffer=buf):
            raise KeyError("x")
    with pytest.raises(ValueError):
        with trace.span("t.err.classified", buffer=buf) as sp:
            sp.status = "http404"
            raise ValueError("orderly")
    assert [s["status"] for s in buf.spans()] == ["error:KeyError", "http404"]
    assert trace.current_context() is None


# ---------------------------------------------------------------------------
# the server's occupancy (MicroBatcher, ISSUE 36)
# ---------------------------------------------------------------------------

class _Echo:
    """``predict_batch`` stub: sleeps, then echoes or raises."""

    def __init__(self, block_s: float = 0.0, fail: bool = False):
        self.block_s, self.fail = block_s, fail

    def predict_batch(self, payloads):
        time.sleep(self.block_s)
        if self.fail:
            raise ValueError("the engine failed")
        return [{"echo": p["id"]} for p in payloads]


def _assert_alternates_and_ends_empty(batcher, spans: list) -> None:
    """No hold leaked: the count is back at 0 and the booked intervals
    alternate from ``empty`` to ``empty`` (the one ``stop()`` closed)."""
    names = [s["name"].rsplit(".", 1)[1] for s in _occupancy(spans)]
    assert batcher.held == 0
    assert len(names) >= 3 and len(names) % 2 == 1, names
    assert names == ["empty", "occupied"] * (len(names) // 2) + ["empty"]


def test_two_spaced_requests_book_alternating_occupancy():
    from incubator_predictionio_tpu.server.query_server import MicroBatcher

    async def drive():
        batcher = MicroBatcher(_Echo(block_s=0.02), max_batch=4)
        t0 = time.perf_counter()
        batcher.start()
        await asyncio.sleep(0.03)
        first = await batcher.submit({"id": 1})
        assert batcher.held == 0
        await asyncio.sleep(0.04)
        second = await batcher.submit({"id": 2})
        await asyncio.sleep(0.01)
        await batcher.stop()
        return batcher, time.perf_counter() - t0, (first, second)

    trace.TRACES.clear()
    before = prof.phase_snapshot().get("serve.server", {}).get("phases", {})
    row_before = _phase_row("pio_profile_phase_seconds_total",
                            "serve.server", "occupied")
    exported = []
    trace.set_exporter(exported.append)
    try:
        batcher, wall, answers = asyncio.run(drive())
    finally:
        trace.set_exporter(None)
    # occupancy is the server's state, nobody's request: a quiet server's
    # long empty intervals must not pass the spool's slow rule
    assert not [s.name for s in exported
                if s.name.startswith("serve.server.")]
    assert "serve.batch.assemble" in {s.name for s in exported}
    assert [a["echo"] for a in answers] == [1, 2]
    spans = _occupancy(trace.TRACES.spans())
    _assert_alternates_and_ends_empty(batcher, spans)
    assert len(spans) == 5
    dur = [s["durationSec"] for s in spans]
    # the intervals cover the batcher's life and nothing else. Only what no
    # loaded host can break is held: a sleep and a dispatch's 20 ms are
    # floors, and how long the scheduler kept anyone waiting is not the
    # server's to promise (Track reads the process's clock, not one a test
    # can step)
    assert sum(dur) <= wall
    assert sum(dur) == pytest.approx(wall, abs=0.02)
    assert dur[0] >= 0.03 and dur[2] >= 0.04 and dur[4] >= 0.01
    assert dur[1] >= 0.02 and dur[3] >= 0.02
    # back to back on one clock: each starts where the last one ended
    for a, b in zip(spans, spans[1:]):
        assert b["startUnix"] == pytest.approx(
            a["startUnix"] + a["durationSec"], abs=1e-3)
    # one trace of sibling roots, and rows of the aggregate with no family
    # of their own: utilisation = occupied / (occupied + empty)
    assert len({s["traceId"] for s in spans}) == 1
    assert all(s["parentId"] is None for s in spans)
    phases = prof.phase_snapshot()["serve.server"]["phases"]
    grew = {k: phases[k]["seconds"] - before.get(k, {}).get("seconds", 0.0)
            for k in ("empty", "occupied")}
    assert grew["empty"] == pytest.approx(dur[0] + dur[2] + dur[4])
    assert grew["occupied"] == pytest.approx(dur[1] + dur[3])
    # (the registry's family outlives ``reset_phases()`` and every batcher
    # an earlier test of this process ran: its growth is what is compared)
    assert _phase_row("pio_profile_phase_seconds_total", "serve.server",
                      "occupied") - row_before == pytest.approx(
        grew["occupied"])


async def _coalesced(MicroBatcher):
    """Eight requests at once, batches of four: one occupied interval."""
    batcher = MicroBatcher(_Echo(block_s=0.005), max_batch=4)
    got = await asyncio.gather(*(batcher.submit({"id": i}) for i in range(8)))
    assert [g["echo"] for g in got] == list(range(8))
    return batcher, 1


async def _shed(MicroBatcher):
    """Deadlines that pass while the one slot is held: ``ShedExpired``."""
    from incubator_predictionio_tpu.resilience.admission import ShedExpired
    from incubator_predictionio_tpu.resilience.clock import FakeClock

    clk = FakeClock()
    batcher = MicroBatcher(_Echo(block_s=0.03), max_batch=1, max_in_flight=1,
                           deadline_sec=0.5, clock=clk)
    tasks = [asyncio.create_task(batcher.submit({"id": i})) for i in range(3)]
    await asyncio.sleep(0.01)      # the first is in its dispatch
    assert batcher.held == 3
    clk.advance(1.0)               # the other two expire in the queue
    got = await asyncio.gather(*tasks, return_exceptions=True)
    assert got[0] == {"echo": 0}
    assert all(isinstance(g, ShedExpired) for g in got[1:])
    assert batcher.shed_expired == 2
    return batcher, 1


async def _cancelled(MicroBatcher):
    """A waiter that gives up while queued, one while in its dispatch: each
    entry is held until the batcher is rid of it (the dispatch over, the
    queued one dropped at the next assembly), not until its waiter left."""
    batcher = MicroBatcher(_Echo(block_s=0.03), max_batch=1, max_in_flight=1)
    tasks = [asyncio.create_task(batcher.submit({"id": i})) for i in range(3)]
    await asyncio.sleep(0.01)
    tasks[0].cancel(), tasks[2].cancel()
    await asyncio.sleep(0)
    assert batcher.held == 3
    got = await asyncio.gather(*tasks, return_exceptions=True)
    assert isinstance(got[0], asyncio.CancelledError)
    assert got[1] == {"echo": 1}
    assert isinstance(got[2], asyncio.CancelledError)
    await asyncio.sleep(0.005)     # the drainer meets the abandoned entry
    return batcher, 1


async def _failed(MicroBatcher):
    """``predict_batch`` raises: every caller of the batch gets the error."""
    batcher = MicroBatcher(_Echo(fail=True), max_batch=4)
    for _ in range(2):
        with pytest.raises(ValueError):
            await batcher.submit({"id": 0})
        assert batcher.held == 0
        await asyncio.sleep(0.005)
    return batcher, 2


async def _stopped(MicroBatcher):
    """``stop()`` with one request in its dispatch and two queued."""
    batcher = MicroBatcher(_Echo(block_s=0.05), max_batch=1, max_in_flight=1)
    tasks = [asyncio.create_task(batcher.submit({"id": i})) for i in range(3)]
    await asyncio.sleep(0.01)
    assert batcher.held == 3
    await batcher.stop()
    assert batcher.held == 0
    got = await asyncio.gather(*tasks, return_exceptions=True)
    assert all(isinstance(g, RuntimeError) for g in got)
    return batcher, 1


@pytest.mark.parametrize("path", [_coalesced, _shed, _cancelled, _failed,
                                  _stopped],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_no_path_out_of_the_batcher_leaks_a_hold(path):
    """A leaked hold would pin the server ``occupied`` for ever: after each
    way a request can leave (answered in a coalesced batch, shed, cancelled,
    failed, shut down) the count is 0 and the last interval is ``empty``."""
    from incubator_predictionio_tpu.server.query_server import MicroBatcher

    async def drive():
        batcher, occupied = await path(MicroBatcher)
        assert batcher.held == 0
        await asyncio.sleep(0.005)
        await batcher.stop()
        return batcher, occupied

    trace.TRACES.clear()
    batcher, occupied = asyncio.run(drive())
    spans = trace.TRACES.spans()
    _assert_alternates_and_ends_empty(batcher, spans)
    assert len(_occupancy(spans)) == 2 * occupied + 1


def test_empty_intervals_lie_on_the_profilers_timeline(tmp_path):
    """Under a capture every ``serve.server.empty`` interval that began
    inside it is a ``pio.serve.server.empty`` event on ``/host:CPU``, as
    long as the ring's span and clear of every ``pio.serve.batch.predict``
    (the worker's thread, the same clock); the interval that was open when
    the capture started has no event; ``occupied`` never has one."""
    from incubator_predictionio_tpu.server.query_server import MicroBatcher

    class Predicts(_Echo):
        def predict_batch(self, payloads):
            with trace.span("serve.batch.predict"):
                return super().predict_batch(payloads)

    async def drive():
        batcher = MicroBatcher(Predicts(block_s=0.01), max_batch=4)
        batcher.start()
        await asyncio.sleep(0.01)
        _capture(str(tmp_path))
        try:
            for i in range(3):
                await asyncio.sleep(0.01)
                await batcher.submit({"id": i})
            await asyncio.sleep(0.01)
            await batcher.stop()
        finally:
            jax.profiler.stop_trace()
        return batcher

    jnp.ones(()).block_until_ready()
    trace.TRACES.clear()
    batcher = asyncio.run(drive())
    spans = trace.TRACES.spans()
    _assert_alternates_and_ends_empty(batcher, spans)
    ours, _ = _pio_events(str(tmp_path))
    empty = sorted((s, e) for n, s, e in ours
                   if n == "pio.serve.server.empty")
    predict = sorted((s, e) for n, s, e in ours
                     if n == "pio.serve.batch.predict")
    assert not [n for n, _, _ in ours if n == "pio.serve.server.occupied"]
    # four empty intervals in the ring; the first began before the capture
    ring = [s for s in _occupancy(spans) if s["name"].endswith(".empty")]
    assert len(ring) == 4 and len(empty) == 3 and len(predict) == 3
    for (s, e), row in zip(empty, ring[1:]):
        assert (e - s) / 1e9 == pytest.approx(row["durationSec"], abs=1e-3)
        assert (s - empty[0][0]) / 1e9 == pytest.approx(
            row["startUnix"] - ring[1]["startUnix"], abs=1e-3)
    # empty, predict, empty, predict, empty, predict, empty: none overlaps
    for ps, pe in predict:
        assert not [1 for s, e in empty if s < pe and ps < e]
    edges = sorted(empty + predict)
    assert edges == [x for pair in zip(predict, empty) for x in pair]


# ---------------------------------------------------------------------------
# the two measured paths: one run_train, one POST /queries.json
# ---------------------------------------------------------------------------

DATA: dict[str, TrainingData] = {}


@dataclasses.dataclass(frozen=True)
class MemoryParams(Params):
    key: str = "spans"


class MemoryDataSource(PDataSource):
    params_class = MemoryParams

    def read_training(self, ctx) -> TrainingData:
        return DATA[self.params.key]


class SpanEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(MemoryDataSource, IdentityPreparator,
                      {"als": ALSAlgorithm}, FirstServing)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One ``run_train`` of a small catalog, device-resident (orbax persist)
    with the two-stage index forced, on sqlite + localfs under a temp home.
    A first verb compiles; the second is the one the tests read."""
    home = str(tmp_path_factory.mktemp("spans"))
    env = {
        "PIO_FS_BASEDIR": home, "PIO_RETRIEVAL_MODE": "two_stage",
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(home, "pio.db"),
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(home, "models"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
    }
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    rng = np.random.default_rng(0)
    n_users, n_items, n = 300, 400, 6000
    DATA["spans"] = TrainingData(
        rng.integers(0, n_users, n).astype(np.int32),
        rng.integers(0, n_items, n).astype(np.int32),
        (1 + 4 * rng.random(n)).astype(np.float32),
        np.asarray([f"u{i}" for i in range(n_users)]),
        np.asarray([f"i{i}" for i in range(n_items)]))
    variant = {
        "id": "spans", "version": "1", "engineFactory": FACTORY,
        "datasource": {"params": {"key": "spans"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "numIterations": 2, "batchSize": 1024,
            "gather": "device", "seed": 3}}],
    }
    path = os.path.join(home, "engine.json")
    with open(path, "w") as f:
        json.dump(variant, f)
    storage = Storage(env)
    engine = SpanEngine().apply()

    def verb() -> str:
        return run_train(
            engine, engine.engine_params_from_variant(variant),
            EngineInstance(
                id="", status="INIT",
                start_time=dt.datetime.now(dt.timezone.utc), end_time=None,
                engine_id="spans", engine_version="1",
                engine_variant=os.path.abspath(path), engine_factory=FACTORY),
            storage=storage, ctx=MeshContext.create())

    verb()
    trace.TRACES.clear()
    instance_id = verb()
    spans = trace.TRACES.spans()
    yield {"storage": storage, "variant_path": path, "spans": spans,
           "instance_id": instance_id}
    storage.close()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


VERB_CHILDREN = {
    "train.verb.read", "train.verb.bimaps", "train.fit.order",
    "train.fit.h2d", "train.fit.init", "train.fit.compute",
    "train.fit.gather", "train.verb.index", "train.verb.persist",
    "train.verb.commit",
}


def test_run_train_is_one_trace_under_train_verb(trained):
    roots = [s for s in trained["spans"] if s["name"] == "train.verb"]
    assert len(roots) == 1
    (root,) = roots
    assert root["parentId"] is None
    assert root["attrs"]["instance"] == trained["instance_id"]
    tree = [s for s in trained["spans"] if s["traceId"] == root["traceId"]]
    children = [s for s in tree if s["parentId"] == root["spanId"]]
    assert {s["name"] for s in children} == VERB_CHILDREN
    # each exactly once, but the ordering: once on the host before the
    # staging, once on the device after it (ISSUE 25)
    assert sum(s["name"] == "train.fit.order" for s in children) == 2
    assert len(children) == len(VERB_CHILDREN) + 1
    # the children account for the verb: within 10% of the root
    covered = sum(s["durationSec"] for s in children)
    assert covered <= root["durationSec"]
    assert covered >= 0.9 * root["durationSec"]
    # below them: the orbax save inside persist, the jitted schedule's one
    # dispatch inside compute
    by_id = {s["spanId"]: s for s in tree}
    below = {s["name"]: by_id[s["parentId"]]["name"] for s in tree
             if s["parentId"] not in (None, root["spanId"])}
    assert below["train.persist.orbax"] == "train.verb.persist"
    assert below["train.epochs.chunk"] == "train.fit.compute"
    # and the index build's two phases (the fixture forces two-stage
    # retrieval, so its 400 items are clustered): the programs' backend and
    # what they were given on the first
    assert below["train.index.cluster"] == "train.verb.index"
    assert below["train.index.layout"] == "train.verb.index"
    (cluster,) = [s for s in tree if s["name"] == "train.index.cluster"]
    assert cluster["attrs"] == {
        "backend": jax.default_backend(), "rows": 400, "partitions": 20,
        "iters": 6, "reseeded": cluster["attrs"]["reseeded"]}
    assert all(s["status"] == "ok" for s in tree)


def test_model_timings_keeps_exactly_its_four_keys(trained):
    """``benchmarks/layer_metrics/workflow_nonfit_s.py`` sums all values of
    ``model.timings``: the spans add none, and feed the four that are."""
    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerMF,
    )

    td = DATA["spans"]
    trace.TRACES.clear()
    model = TwoTowerMF(TwoTowerConfig(rank=8, epochs=1, batch_size=1024)).fit(
        MeshContext.create(), td.user_idx, td.item_idx, td.ratings, 300, 400)
    assert list(model.timings) == ["stage_sec", "init_sec", "train_sec",
                                   "gather_sec"]
    dur: dict[str, float] = {}  # a fit has two train.fit.order spans
    for s in trace.TRACES.spans():
        dur[s["name"]] = dur.get(s["name"], 0.0) + s["durationSec"]
    assert model.timings["stage_sec"] == pytest.approx(
        dur["train.fit.order"] + dur["train.fit.h2d"], abs=1e-4)
    assert model.timings["train_sec"] == pytest.approx(
        dur["train.fit.compute"], abs=1e-4)
    assert model.timings["init_sec"] == pytest.approx(
        dur["train.fit.init"], abs=1e-4)


def test_one_query_yields_request_and_batch_spans(trained):
    from incubator_predictionio_tpu.server.query_server import (
        QueryServer,
        ServerConfig,
    )

    async def drive():
        trace.TRACES.clear()
        server = QueryServer(
            ServerConfig(engine_variant=trained["variant_path"], max_batch=8),
            storage=trained["storage"], ctx=MeshContext.create())
        deploy = trace.TRACES.spans()
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            trace.TRACES.clear()
            resp = await client.post("/queries.json",
                                     json={"user": "u7", "num": 5})
            body = await resp.json()
            header = resp.headers[trace.TRACE_HEADER]
        finally:
            await client.close()
            await server.shutdown()
        return deploy, trace.TRACES.spans(), body, header

    deploy, spans, body, trace_id = asyncio.run(drive())
    assert len(body["itemScores"]) == 5

    # deploy: every span of the contract, warm-up with a child per bucket
    names = [s["name"] for s in deploy]
    for name in ("deploy.load", "deploy.restore", "deploy.quantize",
                 "deploy.ensure_host", "deploy.index", "deploy.warmup"):
        assert name in names, name
    warm = next(s for s in deploy if s["name"] == "deploy.warmup")
    buckets = [s for s in deploy if s["name"] == "deploy.warmup.bucket"]
    assert buckets and all(s["parentId"] == warm["spanId"] for s in buckets)
    assert all("bucket" in s["attrs"] for s in buckets)

    # the request: one trace, the id the client got back
    mine = [s for s in spans if s["traceId"] == trace_id]
    by_name = {s["name"]: s for s in mine}
    by_id = {s["spanId"]: s for s in mine}
    route = by_name["POST /queries.json"]
    assert route["parentId"] is None
    for name in ("serve.request.parse", "serve.request.queue",
                 "serve.request.respond", "serve.batch.assemble",
                 "serve.batch.dispatch", "serve.batch.merge"):
        assert by_name[name]["parentId"] == route["spanId"], name
    dispatch = by_name["serve.batch.dispatch"]
    assert dispatch["attrs"] == {"batch": 1, "bucket": 1}
    predict = by_name["serve.batch.predict"]
    assert predict["parentId"] == dispatch["spanId"]
    assert predict["durationSec"] <= dispatch["durationSec"]

    def ancestors(s):
        while s["parentId"] is not None:
            s = by_id[s["parentId"]]
            yield s["name"]

    for name in ("retrieval.batch.lookup", "retrieval.batch.coarse",
                 "retrieval.batch.rerank", "retrieval.batch.rows"):
        assert "serve.batch.predict" in ancestors(by_name[name]), name
    # request order on one clock: parse, then the queue wait, then the
    # batch, then the answer; together within the route span
    order = ["serve.request.parse", "serve.request.queue",
             "serve.batch.dispatch", "serve.request.respond"]
    starts = [by_name[n]["startUnix"] for n in order]
    assert starts == sorted(starts)
    assert sum(by_name[n]["durationSec"] for n in order) \
        <= route["durationSec"]
    # nothing of this request is left outside its trace; the server's
    # occupancy is nobody's request and keeps a trace of its own
    outside = [s for s in spans if s["traceId"] != trace_id
               and s["name"].startswith(("serve.", "retrieval."))]
    assert [s["name"] for s in outside] == [
        "serve.server.empty", "serve.server.occupied", "serve.server.empty"]
    assert len({s["traceId"] for s in outside}) == 1
    assert all(s["parentId"] is None for s in outside)


def test_exact_path_opens_the_device_span(trained):
    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerMF,
    )

    td = DATA["spans"]
    model = TwoTowerMF(TwoTowerConfig(rank=8, epochs=1, batch_size=1024)).fit(
        MeshContext.create(), td.user_idx, td.item_idx, td.ratings, 300, 400)
    model.prepare_for_serving(host_max_elements=0, build_index=False)
    trace.TRACES.clear()
    idx, _ = TwoTowerMF.recommend_batch(model, np.arange(3, dtype=np.int32), 5)
    assert idx.shape == (3, 5)
    (sp,) = [s for s in trace.TRACES.spans()
             if s["name"] == "retrieval.batch.device"]
    assert sp["attrs"] == {"bucket": 4, "k": 128, "path": "two_tower_topk"}


# ---------------------------------------------------------------------------
# device-side names: the executables the benchmark matches by substring, and
# the named scopes inside them (metadata only)
# ---------------------------------------------------------------------------

def _train_lowered():
    from incubator_predictionio_tpu.models import two_tower as tt
    from incubator_predictionio_tpu.utils.optim import adam_tree_init

    p = {"ue": jnp.zeros((64, 9)), "ie": jnp.zeros((32, 9))}
    o = adam_tree_init(p, "float32")
    idx, val = jnp.zeros((4, 16), jnp.int32), jnp.zeros((4, 16))
    return tt._train_epochs.lower(p, o, idx, idx, val, val, 0.03, 0.01, 2)


def _order_lowered():
    from incubator_predictionio_tpu.models import two_tower as tt

    idx, val = jnp.zeros((4, 16), jnp.int32), jnp.zeros((4, 16))
    return tt._order_batches.lower(idx, idx, val, 7, 16, idx.sharding)


def _init_lowered():
    from incubator_predictionio_tpu.utils.optim import _jit_adam_tree_init

    return _jit_adam_tree_init("float32").lower({"ue": jnp.zeros((8, 9))})


def _topk_lowered():
    from incubator_predictionio_tpu.models import two_tower as tt

    return tt._topk_quantized.lower(
        jnp.zeros(8, jnp.int32), jnp.zeros((64, 8), jnp.bfloat16),
        jnp.zeros(64), jnp.zeros((256, 8), jnp.int8), jnp.zeros(256),
        jnp.zeros(256), jnp.zeros(256), None, 3.5, 10)


def _centroids_lowered():
    from incubator_predictionio_tpu.ops import retrieval

    return retrieval.score_centroids_quantized.lower(
        jnp.zeros((8, 128), jnp.int8), jnp.zeros(8),
        jnp.zeros((512, 128), jnp.int8), jnp.zeros(512), jnp.zeros(512),
        interpret=True)


def _quantize_users_lowered():
    from incubator_predictionio_tpu.ops import retrieval

    return retrieval.quantize_user_rows.lower(
        jnp.zeros(8, jnp.int32), jnp.zeros((64, 128), jnp.bfloat16),
        jnp.zeros(64))


def _rerank_lowered():
    """The device leg's second stage, in its rule-filtered form (the plain
    one lacks only the ``gather`` of candidate ids for the row mask)."""
    from incubator_predictionio_tpu.ops import retrieval

    blocks = jnp.zeros((6, 1, 256))
    return retrieval.two_stage_rerank.lower(
        jnp.zeros((8, 512)), jnp.zeros((8, 128), jnp.int8), jnp.zeros(8),
        jnp.zeros(8), jnp.float32(3.5), jnp.zeros(6, jnp.int32),
        jnp.zeros((6, 256, 128), jnp.int8), blocks, blocks,
        blocks.astype(jnp.int32), blocks, jnp.zeros((8, 1000)),
        nprobe=3, k=16, interpret=True)


def _ivf_lowered(program: str):
    """A program of the index build (``serving/ann.build_ivf_fused``) at a
    small catalog: 64 rows of rank 8, 16 sampled, 4 partitions."""
    from incubator_predictionio_tpu.ops import retrieval

    rows, cent = jnp.zeros((64, 9)), jnp.zeros((4, 9))
    args, static = {
        "ivf_sample": ((rows, jnp.zeros(16, jnp.int32),
                        jnp.zeros(4, jnp.int32)), {}),
        "ivf_assign": ((rows, cent), {"n": 64}),
        "ivf_update": ((rows[:16], jnp.zeros(16, jnp.int32)), {"c": 4}),
        "ivf_layout": ((rows, jnp.zeros(64, jnp.int32)), {"quantize": True}),
    }[program]
    return getattr(retrieval, program).lower(*args, **static)


def _seq_layer_lowered(kind: str, bucket: tuple):
    """A layer executable of the sequence template's paged-cache serving,
    by the block's kind, as ``LatentServing`` names and lowers it."""
    from incubator_predictionio_tpu.models import latent_moe
    from incubator_predictionio_tpu.models.transformer import TransformerConfig
    from incubator_predictionio_tpu.serving.latent_cache import LatentServing

    shared = dict(vocab_size=64, max_len=64, d_model=32, n_heads=2,
                  n_layers=1, n_routed_experts=4, experts_per_token=2,
                  moe_intermediate_size=16, tie_head=False, cache_page=8,
                  cache_tokens=128)
    if kind == "mla":
        cfg = TransformerConfig(
            attention_kind="mla", q_lora_rank=8, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            n_shared_experts=1, rope_parameters=tuple(sorted({
                "beta_fast": 32, "beta_slow": 1, "factor": 128,
                "original_max_position_embeddings": 8192,
                "rope_theta": 10000}.items())), **shared)
    elif kind == "gqa_sparse":
        cfg = TransformerConfig(
            attention_kind="gqa_sparse", n_kv_heads=1, head_dim=16,
            index_n_heads=2, index_head_dim=16, index_topk=8,
            index_kv_tile=8, router_scoring="softmax", **shared)
    elif kind == "W":   # the window letter beside a full layer
        cfg = TransformerConfig(**{
            **shared, "n_layers": 4, "attention_kind": "gqa",
            "layer_pattern": "WEAE", "n_kv_heads": 1, "head_dim": 16,
            "attention_rope": True, "sliding_window": 8, "index_kv_tile": 8,
            "router_scoring": "softmax", "rope_parameters": tuple(sorted({
                "rope_type": "yarn", "beta_fast": 32, "beta_slow": 1,
                "factor": 4, "original_max_position_embeddings": 32,
                "rope_theta": 10000}.items()))})
    else:   # a letter of a layer pattern
        cfg = TransformerConfig(**{
            **shared, "n_layers": 3, "attention_kind": "gqa",
            "layer_pattern": "SAE", "n_kv_heads": 1, "head_dim": 16,
            "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 8,
            "ssm_groups": 2, "ssm_chunk": 8, "n_shared_experts": 1,
            "expert_activation": "relu2"})
    serving = LatentServing(
        latent_moe.init_params(jax.random.key(0), cfg), cfg)
    try:
        if kind == "W" and bucket[1] == 16:   # the pattern's turn program
            return serving._lower_turn(*bucket, 16)
        return serving._lower(*bucket)[kind if kind in "SAEW" else "layer"]
    finally:
        serving.close()


@pytest.mark.parametrize("lower, module, scopes", [
    (lambda: _seq_layer_lowered("mla", (4, 16, 64)),
     "jit_seq_layer_b4_t16_c64",
     ("mla_proj", "mla_attn", "moe_router", "moe_experts", "moe_shared")),
    (lambda: _seq_layer_lowered("gqa_sparse", (1, 16, 64)),
     "jit_seq_layer_b1_t16_c64",
     ("gqa_proj", "idx_score", "idx_select", "sparse_attn", "moe_router",
      "moe_experts")),
    (lambda: _seq_layer_lowered("gqa_sparse", (1, 32, 32)),
     "jit_seq_layer_b1_t32_c32",
     ("gqa_proj", "idx_score", "idx_select", "sparse_attn", "moe_router",
      "moe_experts")),
    (lambda: _seq_layer_lowered("S", (4, 16, 64)), "jit_seq_ssm_b4_t16",
     ("ssm_proj", "ssm_conv", "ssm_scan")),
    (lambda: _seq_layer_lowered("S", (1, 64, 64)), "jit_seq_ssm_b1_t64",
     ("ssm_proj", "ssm_conv", "ssm_scan")),
    (lambda: _seq_layer_lowered("A", (4, 16, 32)), "jit_seq_gqa_b4_t16_c32",
     ("gqa_proj", "gqa_attn")),
    (lambda: _seq_layer_lowered("E", (4, 16, 32)), "jit_seq_moe_b4_t16",
     ("moe_router", "moe_experts", "moe_shared")),
    (lambda: _seq_layer_lowered("W", (1, 32, 64)), "jit_seq_win_b1_t32",
     ("gqa_proj", "win_attn")),
    (lambda: _seq_layer_lowered("W", (4, 16, 64)), "jit_seq_turn_b4_t16_c64",
     ("gqa_proj", "win_attn", "gqa_attn", "moe_router", "moe_experts",
      "head_topk")),
    (_train_lowered, "jit__train_epochs",
     ("gather", "loss_grad", "scatter", "adam_user", "adam_item")),
    (_topk_lowered, "jit__topk_quantized", ("score", "topk")),
    (_centroids_lowered, "jit_score_centroids_quantized", ("score",)),
    (_init_lowered, "jit_init", ()),
    (_order_lowered, "jit__order_batches", ()),
    (_quantize_users_lowered, "jit_quantize_user_rows",
     ("gather", "quantize")),
    (_rerank_lowered, "jit_two_stage_rerank",
     ("probe_select", "rerank", "gather", "topk")),
    (lambda: _ivf_lowered("ivf_sample"), "jit_ivf_sample", ("gather",)),
    (lambda: _ivf_lowered("ivf_assign"), "jit_ivf_assign", ("assign",)),
    (lambda: _ivf_lowered("ivf_update"), "jit_ivf_update", ("update",)),
    (lambda: _ivf_lowered("ivf_layout"), "jit_ivf_layout",
     ("gather", "quantize")),
], ids=["seq_layer_latent", "seq_layer_sparse_turn", "seq_layer_sparse_piece",
        "seq_ssm_step", "seq_ssm_scan", "seq_gqa", "seq_moe", "seq_win_piece",
        "seq_win_turn", "train_epochs", "topk_quantized", "score_centroids", "init",
        "order_batches", "quantize_user_rows", "two_stage_rerank",
        "ivf_sample", "ivf_assign", "ivf_update", "ivf_layout"])
def test_executable_names_and_scopes_are_pinned(lower, module, scopes):
    """``benchmarks/layer_metrics/*_roofline.py`` find these executables by
    name in a device trace's ``XLA Modules`` line (the sequence template's
    by the ``jit_seq_layer_`` names, a layer pattern's ``jit_seq_ssm_`` /
    ``jit_seq_gqa_`` / ``jit_seq_moe_``, and the scopes inside them, through
    ``benchmarks/seq_trace.py``): a rename has to fail here, not read as a
    missing roofline on the chip."""
    lowered = lower()
    assert re.search(rf"\bmodule @{module}\b", lowered.as_text())
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert re.search(rf'[/"]{scope}[/"]', text), scope


def test_the_expert_kernel_lies_under_the_moe_experts_scope(monkeypatch):
    """Where the routed experts run through the Pallas grouped matmul
    (a stored width of at least one lane tile, on a backend that runs the
    package's kernels: every served width on a TPU), its two calls are
    lowered under ``moe_experts``: ``LatentServing.
    device_scopes()`` places a trace's operations by that path, and the
    experts' roofline reads the scope. Lowered for the TPU from here; no
    ``ragged_dot`` is left, and no activation over every held expert."""
    from incubator_predictionio_tpu.models import latent_moe
    from tests.fixtures.ssm_tiny import config

    cfg = config(moe_intermediate_size=384, d_model=128)
    lw = latent_moe.init_params(jax.random.key(0), cfg)["layers"][1]
    assert lw["we1"].shape == (8, 128, 384)
    monkeypatch.setattr(latent_moe, "kernel_backend", lambda: "mosaic")

    def seq_moe_b4_t16(lw, counters, h, counts):
        return latent_moe.expert_step(lw, {}, counters, h, None, None, counts,
                                      cfg=cfg, form="")

    text = jax.jit(seq_moe_b4_t16).trace(
        lw, jnp.zeros(10, jnp.int32), jnp.zeros((4, 16, 128)),
        jnp.zeros(4, jnp.int32)).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    assert len(re.findall(r"custom_call @tpu_custom_call", text)) == 2
    # (the kernel's entry is a jit of its own: the scope is on its call)
    sites = re.findall(r"call @grouped_matmul\w*\(.*loc\((#loc\d+)\)", text)
    where = [line for line in text.splitlines()
             if any(line.startswith(site + " =") for site in sites)]
    assert len(where) == 2 and all(
        "/moe_experts/jit(grouped_matmul)" in line for line in where), where
    assert "chlo.ragged_dot" not in text
    assert "tensor<8x64x384xf32>" not in text     # [held, slots, f]


def test_train_schedule_lowers_to_the_parents_program():
    """ISSUE 25 moved the per-batch sort in front of ``_train_epochs`` and
    left the schedule alone: its lowered text (no debug info: line numbers
    move) is the one of commit b8876ac."""
    text = _train_lowered().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "d5d87a7d0b6e5fa3957ec63e625d4d301413863c5676eec3e34ddd652b68c4c8"), (
        f"the digest was taken under jax 0.9.0 and this is jax "
        f"{jax.__version__}: after a JAX upgrade, or a deliberate change to "
        f"the step, pin the new digest")
