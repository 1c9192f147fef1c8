"""Continuous performance plane (ISSUE 17): always-on profiler phase
conservation, durable metrics history round-trips (torn tails, eviction,
cross-process merge), SLO burn-rate math + the chaos error storm, jit
compile attribution, process self-metrics, and the new CLI verbs.

Determinism discipline: every timeline here is FakeClock-stamped or
hand-constructed — the chaos storm flips an SLO red without one wall
sleep. The only real-clock timing is the tiny loop-lag drive (the span
primitive's own clock is covered in tests/test_program_spans.py).
"""

from __future__ import annotations

import asyncio
import json
import os
import struct
import threading

import pytest

from incubator_predictionio_tpu.obs import history as hist
from incubator_predictionio_tpu.obs import profile as prof
from incubator_predictionio_tpu.obs import slo as slomod
from incubator_predictionio_tpu.resilience.clock import FakeClock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLO_CONF = os.path.join(REPO, "conf", "slo.json")


# ---------------------------------------------------------------------------
# profiler: phase timers + conservation contract
# ---------------------------------------------------------------------------

def test_record_phases_folds_external_timers():
    """record_phases (the fold / per-shard search path) feeds the same
    aggregate the spans feed; wall defaults to the phase sum."""
    prof.reset_phases()
    prof.record_phases("t.fold", {"assemble": 0.5, "compute": 1.5})
    prof.record_phases("t.fold", {"assemble": 0.5, "compute": 0.5},
                       wall_seconds=1.2)
    snap = prof.phase_snapshot()["t.fold"]
    assert snap["wall_seconds"] == pytest.approx(2.0 + 1.2)
    assert snap["count"] == 2
    assert snap["phases"]["assemble"] == {"seconds": 1.0, "count": 2}
    assert snap["phases"]["compute"]["seconds"] == pytest.approx(2.0)
    # negative intervals (clock skew in a caller's math) clamp, not poison
    prof.record_phases("t.fold", {"assemble": -1.0})
    assert prof.phase_snapshot()["t.fold"]["phases"]["assemble"][
        "seconds"] == pytest.approx(1.0)


def test_training_instrumentation_feeds_profiler():
    """TwoTowerMF.fit's spans book the train.fit scope and the step-time
    histogram — the live twin of bench MFU; model.timings reads the same
    spans back."""
    import numpy as np

    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerMF,
    )
    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    prof.reset_phases()
    rng = np.random.default_rng(0)
    n = 400
    model = TwoTowerMF(TwoTowerConfig(rank=4, batch_size=128, epochs=1)).fit(
        MeshContext.create(), rng.integers(0, 20, n).astype(np.int32),
        rng.integers(0, 30, n).astype(np.int32),
        rng.random(n).astype(np.float32), 20, 30)
    snap = prof.phase_snapshot()["train.fit"]
    assert set(snap["phases"]) == {"order", "h2d", "init", "compute",
                                   "gather"}
    # model.timings rounds for display; the profiler keeps full precision
    assert snap["phases"]["compute"]["seconds"] == pytest.approx(
        model.timings["train_sec"], rel=0.01)
    assert (snap["phases"]["order"]["seconds"]
            + snap["phases"]["h2d"]["seconds"]) == pytest.approx(
        model.timings["stage_sec"], abs=2e-4)
    # no span encloses the fit: the scope's wall is the sum of its phases
    phase_sum = sum(p["seconds"] for p in snap["phases"].values())
    assert phase_sum == pytest.approx(snap["wall_seconds"])
    assert set(model.timings) == {"stage_sec", "init_sec", "train_sec",
                                  "gather_sec"}


def test_record_training_step_mfu_with_injected_peak():
    assert prof.record_training_step(1e12, 2.0, peak_flops=1e12) == \
        pytest.approx(0.5)
    assert prof.record_training_step(1e12, 0.0) is None  # degenerate


def test_stack_sampler_aggregates_own_stacks():
    """sample_once symbolizes every live thread; top() ranks collapsed
    stacks leaf-first with stable percentages."""
    import sys as _sys

    s = prof.StackSampler(hz=50.0, topn=5)
    # sample_once skips the CALLING thread (never profile the profiler);
    # inject a frames dict under a synthetic tid, as the sampler thread
    # would see this one
    for _ in range(3):
        s.sample_once(frames={-1: _sys._getframe()})
    top = s.top(3)
    assert s.samples == 3
    assert top and top[0]["samples"] <= 3
    assert all(e["stack"] for e in top)
    total = sum(e["samples"] for e in s.top(1000))
    assert top[0]["pct"] == pytest.approx(
        100.0 * top[0]["samples"] / total, abs=0.01)


def test_configure_profiler_from_env_gates_sampler(monkeypatch):
    monkeypatch.delenv(prof.ENV_HZ, raising=False)
    assert prof.configure_profiler_from_env("t_svc") is None
    monkeypatch.setenv(prof.ENV_HZ, "37")
    s = prof.configure_profiler_from_env("t_svc")
    try:
        assert s is not None and s.hz == 37.0
        assert prof.active_sampler() is s
        payload = prof.profile_payload()
        assert payload["service"] == "t_svc"
        assert payload["sampler"]["hz"] == 37.0
    finally:
        prof.close_profiler()
    assert prof.active_sampler() is None


# ---------------------------------------------------------------------------
# history: durable segments, torn tails, eviction, series math
# ---------------------------------------------------------------------------

def _mk_record(ts: float, service: str = "query_server",
               ok: float = 0.0, err: float = 0.0,
               buckets: dict | None = None) -> dict:
    """Hand-built snapshot in the exact on-disk record shape."""
    samples = [
        ["pio_http_requests_total",
         {"service": service, "route": "/queries.json", "method": "POST",
          "status": "200"}, ok],
        ["pio_http_requests_total",
         {"service": service, "route": "/queries.json", "method": "POST",
          "status": "500"}, err],
    ]
    for le, v in (buckets or {}).items():
        samples.append(
            ["pio_http_request_seconds_bucket",
             {"service": service, "route": "/queries.json", "le": le}, v])
    return {"t": ts, "service": service, "samples": samples,
            "types": {"pio_http_requests_total": "counter",
                      "pio_http_request_seconds": "histogram"}}


def test_history_store_round_trip(tmp_path):
    store = hist.HistoryStore(str(tmp_path), service="svc_a")
    for i in range(5):
        store.append(_mk_record(1000.0 + i, ok=float(i)))
    store.close()
    records = hist.read_history(str(tmp_path))
    assert [r["t"] for r in records] == [1000.0 + i for i in range(5)]
    assert hist.read_history(str(tmp_path), since=1003.0)[0]["t"] == 1003.0
    pts = hist.series(records, "pio_http_requests_total",
                      where={"status": "200"})
    assert pts == [(1000.0 + i, float(i)) for i in range(5)]


def test_history_torn_tail_is_waiting_not_corruption(tmp_path):
    """A live writer killed mid-frame leaves a torn tail; readers keep the
    whole valid prefix (the tail_frames contract, same as the WAL)."""
    store = hist.HistoryStore(str(tmp_path), service="svc_a")
    for i in range(3):
        store.append(_mk_record(2000.0 + i))
    store.close()
    [seg] = hist.history_files(str(tmp_path))
    with open(seg, "ab") as f:  # torn: header promises more than exists
        f.write(struct.pack("<II", 10_000, 0) + b"partial")
    records = hist.read_history(str(tmp_path))
    assert [r["t"] for r in records] == [2000.0, 2001.0, 2002.0]


def test_history_corrupt_frame_keeps_valid_prefix(tmp_path):
    store = hist.HistoryStore(str(tmp_path), service="svc_a")
    for i in range(3):
        store.append(_mk_record(3000.0 + i))
    store.close()
    [seg] = hist.history_files(str(tmp_path))
    data = bytearray(open(seg, "rb").read())
    data[-3] ^= 0xFF  # flip a payload byte inside the LAST frame
    open(seg, "wb").write(bytes(data))
    records = hist.read_history(str(tmp_path))
    assert [r["t"] for r in records] == [3000.0, 3001.0]


def test_history_rotation_and_whole_segment_eviction(tmp_path):
    """Per-process bytes stay under max_bytes via whole-segment eviction —
    readers racing an eviction lose old whole segments, never a torn
    prefix — and newest records always survive."""
    store = hist.HistoryStore(str(tmp_path), service="svc_a",
                              segment_bytes=4096, max_bytes=12288)
    for i in range(120):
        store.append(_mk_record(4000.0 + i, ok=float(i)))
    store.close()
    total = sum(os.path.getsize(p)
                for p in hist.history_files(str(tmp_path)))
    assert total <= 12288 + 4096  # bound + one in-flight segment of slack
    records = hist.read_history(str(tmp_path))
    assert records, "eviction must never empty the history"
    assert records[-1]["t"] == 4119.0  # newest survives; oldest evicted
    assert records[0]["t"] > 4000.0


def test_history_multi_writer_shared_dir(tmp_path):
    """Two services (processes) share one dir without coordination; the
    reader merges by timestamp and series() filters by service."""
    a = hist.HistoryStore(str(tmp_path), service="query_server")
    b = hist.HistoryStore(str(tmp_path), service="event_server")
    a.append(_mk_record(5000.0, service="query_server", ok=1.0))
    b.append(_mk_record(5000.5, service="event_server", ok=7.0))
    a.append(_mk_record(5001.0, service="query_server", ok=2.0))
    a.close(); b.close()
    records = hist.read_history(str(tmp_path))
    assert [r["service"] for r in records] == [
        "query_server", "event_server", "query_server"]
    pts = hist.series(records, "pio_http_requests_total",
                      service="event_server", where={"status": "200"})
    assert pts == [(5000.5, 7.0)]


def test_rate_series_tolerates_counter_reset():
    pts = [(0.0, 0.0), (10.0, 100.0), (20.0, 200.0),
           (30.0, 5.0),  # process restart: counter reset
           (40.0, 105.0)]
    rates = dict(hist.rate_series(pts))
    assert rates[10.0] == pytest.approx(10.0)
    assert rates[30.0] == pytest.approx(5.0 / 10.0)  # reset: absolute value
    assert rates[40.0] == pytest.approx(10.0)
    assert all(r >= 0 for r in rates.values())


def test_recorder_scrape_while_registry_mutates():
    """The self-scrape must survive a registry being actively mutated —
    new label children mid-expose is the racing-server steady state."""
    from incubator_predictionio_tpu.obs.metrics import REGISTRY

    fam = REGISTRY.counter(
        "pio_test_race_total", "scrape-race fixture counter",
        labels=("k",))
    rec = hist.HistoryRecorder(service="race_svc", ring_size=64)
    stop = threading.Event()
    errors: list[BaseException] = []

    def mutate():
        i = 0
        while not stop.is_set():
            fam.labels(k=f"k{i % 97}").inc()
            i += 1

    threads = [threading.Thread(target=mutate) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for i in range(25):
            r = rec.record_once(ts=6000.0 + i)
            if r is None:
                errors.append(AssertionError("scrape failed under race"))
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    assert len(rec.recent()) == 25
    assert len(rec.recent(since=6020.0)) == 5


def test_configure_history_from_env_durable_and_off(tmp_path, monkeypatch):
    monkeypatch.delenv(hist.ENV_DIR, raising=False)
    assert hist.configure_history_from_env("t_svc") is None
    monkeypatch.setenv(hist.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(hist.ENV_INTERVAL_MS, "60000")
    rec = hist.configure_history_from_env("t_svc")
    try:
        assert rec is not None and rec.store is not None
        assert hist.configured_recorder() is rec
        rec.record_once(ts=7000.0)
    finally:
        hist.close_history()
    assert hist.configured_recorder() is None
    assert [r["t"] for r in hist.read_history(str(tmp_path))] == [7000.0]


# ---------------------------------------------------------------------------
# SLO engine: validation, burn-rate math, the chaos storm
# ---------------------------------------------------------------------------

def test_validate_config_names_positions():
    errors = slomod.validate_config({"objectives": [
        {"name": "a", "type": "availability"},
        {"type": "bogus", "objective": 2.0},
    ]})
    assert any(e.startswith("objectives[0].service") for e in errors)
    assert any(e.startswith("objectives[0].objective") for e in errors)
    assert any(e.startswith("objectives[1].name") for e in errors)
    assert any(e.startswith("objectives[1].type") for e in errors)
    assert any(e.startswith("objectives[1].objective") for e in errors)


def test_validate_config_unknown_keys_and_window_monotonicity():
    errors = slomod.validate_config({
        "objetives": [],  # typo'd top-level key must be called out
        "objectives": [
            {"name": "a", "service": "s", "type": "availability",
             "objective": 0.99, "burn_treshold": 1,
             "windows": {"fast": [3600, 300]}},
            {"name": "b", "service": "s", "type": "availability",
             "objective": 0.99,
             "windows": {"fast": [300, 86400], "slow": [3600, 21600]}},
        ]})
    assert any(e == "top-level: unknown key 'objetives'" for e in errors)
    assert any(e.startswith("objectives[0]: unknown key 'burn_treshold'")
               for e in errors)
    assert any(e.startswith("objectives[0].windows.fast: non-monotonic")
               for e in errors)
    assert any(e.startswith("objectives[1].windows: non-monotonic")
               for e in errors)


def test_repo_slo_config_is_valid():
    """conf/slo.json (the config CI gates on) must always load."""
    objectives = slomod.load_config(SLO_CONF)
    assert {o["name"] for o in objectives} >= {
        "query-availability", "query-latency-p99-250ms"}
    for o in objectives:
        assert set(o["windows"]) == {"fast", "slow"}


def _storm_records(error_after: float, error_rate: float = 0.5,
                   span: float = 7200.0, interval: float = 60.0,
                   qps: float = 10.0) -> list[dict]:
    """FakeClock-stamped availability timeline: healthy closed-loop
    traffic, then ``error_rate`` of requests 500ing after ``error_after``
    seconds. Pure data — zero sleeps, zero threads."""
    clock = FakeClock(start=1_700_000_000.0)
    t0 = clock.monotonic()
    records, ok, err = [], 0.0, 0.0
    while clock.monotonic() - t0 <= span:
        elapsed = clock.monotonic() - t0
        n = qps * interval
        if elapsed > error_after:
            err += n * error_rate
            ok += n * (1.0 - error_rate)
        else:
            ok += n
        records.append(_mk_record(clock.monotonic(), ok=ok, err=err))
        clock.advance(interval)
    return records


def test_evaluate_healthy_timeline_has_full_budget():
    objectives = slomod.load_config(SLO_CONF)
    records = _storm_records(error_after=float("inf"))
    verdicts = {v["name"]: v for v in slomod.evaluate(objectives, records)}
    v = verdicts["query-availability"]
    assert not v["breaching"] and not v["no_data"]
    assert v["budget_remaining"] == pytest.approx(1.0)
    assert v["windows"]["fast"]["burn_short"] == pytest.approx(0.0)


def test_chaos_error_storm_flips_slo_within_one_fast_window():
    """The acceptance chaos case: a 50% 500-storm must breach the fast
    burn pair within ONE short window (300s) of storm — on virtual
    timestamps, with zero wall sleeps."""
    objectives = [o for o in slomod.load_config(SLO_CONF)
                  if o["name"] == "query-availability"]
    span = 3600.0 + 300.0  # healthy hour, then exactly one fast window
    records = _storm_records(error_after=3600.0, span=span)
    [v] = slomod.evaluate(objectives, records)
    fast = v["windows"]["fast"]
    assert fast["breaching"] and v["breaching"]
    assert fast["burn_short"] > fast["threshold"]
    assert fast["burn_long"] > fast["threshold"]
    assert v["budget_remaining"] < 1.0
    # pre-storm evaluation of the same timeline was green
    pre = [r for r in records if r["t"] <= records[0]["t"] + 3600.0]
    [v0] = slomod.evaluate(objectives, pre)
    assert not v0["breaching"]


def test_slo_engine_health_block_and_gauges():
    """SloEngine over an injected records source: /health block goes red
    and the pio_slo_* gauges carry the verdict."""
    from incubator_predictionio_tpu.obs.metrics import REGISTRY

    objectives = [o for o in slomod.load_config(SLO_CONF)
                  if o["name"] == "query-availability"]
    records = _storm_records(error_after=3600.0, span=3900.0)
    engine = slomod.SloEngine(objectives, records_fn=lambda: records)
    block = engine.health_block()
    assert block["breaching"] is True
    [row] = block["objectives"]
    assert row["name"] == "query-availability" and row["breaching"]
    assert row["maxBurn"] > 14.4
    engine.collect()
    text = REGISTRY.expose()
    assert 'pio_slo_breaching{slo="query-availability"} 1' in text
    assert "pio_slo_burn_rate" in text


def test_evaluate_no_data_and_idle_service():
    objectives = slomod.load_config(SLO_CONF)
    verdicts = slomod.evaluate(objectives, [])
    assert all(v["no_data"] and not v["breaching"] for v in verdicts)
    # records exist but carry no samples for one service: that objective
    # reads no-data, the others still evaluate
    records = _storm_records(error_after=float("inf"), span=600.0)
    verdicts = {v["name"]: v for v in slomod.evaluate(objectives, records)}
    assert verdicts["event-ingest-availability"]["no_data"]
    assert not verdicts["query-availability"]["no_data"]


def test_configure_slo_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv(slomod.ENV_CONFIG, raising=False)
    assert slomod.configure_slo_from_env("t_svc") is None
    assert slomod.health_block() is None
    monkeypatch.setenv(slomod.ENV_CONFIG, SLO_CONF)
    engine = slomod.configure_slo_from_env("t_svc")
    try:
        assert engine is not None
        assert slomod.health_block() is not None
        # bad config degrades to disabled, never raises at boot
        bad = tmp_path / "bad.json"
        bad.write_text('{"objectives": [{"name": "x"}]}')
        monkeypatch.setenv(slomod.ENV_CONFIG, str(bad))
        assert slomod.configure_slo_from_env("t_svc") is None
        assert slomod.health_block() is None
    finally:
        slomod.close_slo()
        hist.close_history()  # the engine may have started a ring recorder


# ---------------------------------------------------------------------------
# jitstats compile attribution + process self-metrics
# ---------------------------------------------------------------------------

def test_jitstats_compile_attribution():
    from incubator_predictionio_tpu.utils import jitstats

    jitstats.reset()
    try:
        jitstats.observe_compile(("two_tower_train", 64, 65536), 2.5)
        jitstats.observe_compile(("two_tower_train", 64, 65536), 0.5)
        jitstats.observe_compile(("topk", 100), 0.25)
        top = jitstats.top_compiles()
        assert top[0][0] == "two_tower_train"
        assert top[0][1] == pytest.approx(3.0) and top[0][2] == 2
        assert jitstats.compile_seconds_total() == pytest.approx(3.25)
        # dispatch_timer: fresh key books wall as compile, warm does not
        with jitstats.dispatch_timer(("warmable", 1)):
            pass
        booked = jitstats.compile_seconds_total()
        with jitstats.dispatch_timer(("warmable", 1)):
            pass
        assert jitstats.compile_seconds_total() == booked
    finally:
        jitstats.reset()


def test_procstats_self_metrics():
    from incubator_predictionio_tpu.obs import procstats
    from incubator_predictionio_tpu.obs.metrics import REGISTRY

    assert procstats.rss_bytes() > 0
    assert procstats.open_fd_count() > 0
    procstats.register("t_proc")
    text = REGISTRY.expose()
    assert "pio_process_rss_bytes" in text
    assert "pio_process_open_fds" in text


def test_loop_lag_monitor_sets_gauge():
    from incubator_predictionio_tpu.obs import procstats
    from incubator_predictionio_tpu.obs.metrics import REGISTRY

    async def drive():
        task = procstats.start_loop_lag("t_lag", interval_sec=0.01)
        await asyncio.sleep(0.05)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    asyncio.run(drive())
    assert 'pio_process_loop_lag_seconds{service="t_lag"}' in \
        REGISTRY.expose()


# ---------------------------------------------------------------------------
# CLI verbs + the CI config gate
# ---------------------------------------------------------------------------

def test_cli_slo_check_repo_config_green():
    """The CI gate (verify runs this verbatim): the checked-in objectives
    must validate."""
    from incubator_predictionio_tpu.tools.cli import main as cli_main

    assert cli_main(["slo", "--check", SLO_CONF]) == 0


def test_cli_slo_check_invalid_names_positions(tmp_path, capsys):
    from incubator_predictionio_tpu.tools.cli import main as cli_main

    bad = tmp_path / "slo.json"
    bad.write_text(json.dumps({"objectives": [
        {"name": "x", "type": "latency", "service": "s",
         "objective": 0.99}]}))  # latency without threshold_ms
    assert cli_main(["slo", "--check", str(bad)]) == 1
    err = capsys.readouterr().err
    assert "INVALID" in err and "objectives[0].threshold_ms" in err


def test_cli_slo_verdict_over_history_dir(tmp_path, capsys):
    from incubator_predictionio_tpu.tools.cli import main as cli_main

    store = hist.HistoryStore(str(tmp_path), service="query_server")
    for rec in _storm_records(error_after=3600.0, span=3900.0):
        store.append(rec)
    store.close()
    assert cli_main(["slo", str(tmp_path), "--config", SLO_CONF]) == 1
    out = capsys.readouterr().out
    assert "query-availability" in out and "BREACHING" in out

    healthy = tmp_path / "healthy"
    store = hist.HistoryStore(str(healthy), service="query_server")
    for rec in _storm_records(error_after=float("inf"), span=3900.0):
        store.append(rec)
    store.close()
    assert cli_main(["slo", str(healthy), "--config", SLO_CONF]) == 0


def test_cli_history_summary_and_series(tmp_path, capsys):
    from incubator_predictionio_tpu.tools.cli import main as cli_main

    store = hist.HistoryStore(str(tmp_path), service="query_server")
    for i in range(4):
        store.append(_mk_record(8000.0 + 60.0 * i, ok=100.0 * i))
    store.close()
    assert cli_main(["history", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "4 snapshot(s)" in out and "pio_http_requests_total" in out
    assert cli_main(["history", str(tmp_path),
                     "--series", "pio_http_requests_*"]) == 0
    out = capsys.readouterr().out
    assert "pio_http_requests_total (counter)" in out
    assert cli_main(["history", str(tmp_path), "--series", "no_match_*"]) == 1
    assert cli_main(["history", str(tmp_path / "missing")]) == 1


def test_health_row_marks_slo_breach():
    from incubator_predictionio_tpu.tools.cli import _health_row

    row = _health_row("http://x", {
        "status": "ok", "service": "query_server",
        "slo": {"breaching": True, "objectives": [
            {"name": "query-availability", "breaching": True}]},
    }, None)
    assert row["red"] is True
    assert "SLO BREACH: query-availability" in row["detail"]
    green = _health_row("http://x", {"status": "ok",
                                     "service": "query_server",
                                     "slo": {"breaching": False,
                                             "objectives": []}}, None)
    assert green["red"] is False


def test_cli_profile_top_history_against_live_obs_server():
    """profile/top/history verbs against a real obs HTTP surface (the
    same add_observability_routes every server mounts)."""
    from incubator_predictionio_tpu.obs.http import start_obs_server
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from incubator_predictionio_tpu.tools.cli import main as cli_main

    prof.reset_phases()
    prof.record_phases("serve.batch", {"assemble": 0.01, "dispatch": 0.04})
    rec = hist.HistoryRecorder(service="obs_t", ring_size=16)
    hist._RECORDER = rec  # ring-only recorder without env plumbing
    port = free_port()
    handle = start_obs_server("obs_t", port=port)
    try:
        rec.record_once(ts=9000.0)
        url = f"http://127.0.0.1:{port}"
        assert cli_main(["profile", url]) == 0
        assert cli_main(["top", url, "-n", "1"]) == 0
        assert cli_main(["history", url]) == 0
        assert cli_main(["history", url, "--since", "9999"]) == 1
    finally:
        handle.close()
        hist._RECORDER = None
