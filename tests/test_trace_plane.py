"""Fleet-wide trace plane (ISSUE 14): durable span export, head/tail
sampling, cross-process assembly, exemplars.

Everything here is tier-1-fast: sampling decisions, tail keep rules, and
"slow" spans are driven with constructed spans and explicit durations —
zero wall sleeps (the FakeClock discipline). The real-process proofs
(router → replica → storage assembly, SIGKILL mid-request) live in
tests/test_chaos_procs.py.
"""

import asyncio
import json
import os

import pytest
from aiohttp.test_utils import TestClient, TestServer

from incubator_predictionio_tpu.obs import collect, spool, trace
from incubator_predictionio_tpu.obs.metrics import (
    MetricsRegistry,
    parse_prometheus_text,
)
from incubator_predictionio_tpu.resilience.wal import tail_frames


@pytest.fixture(autouse=True)
def _clean_trace_state(monkeypatch):
    """Every test starts and ends with export disabled and default
    sampling — module state must never leak across tests."""
    for var in (spool.ENV_DIR, spool.ENV_SAMPLE, spool.ENV_SLOW_MS,
                spool.ENV_SEGMENT_BYTES, spool.ENV_MAX_BYTES):
        monkeypatch.delenv(var, raising=False)
    yield
    spool.close_export()
    trace.set_sampling(None, None)


def _span(trace_id, span_id, parent_id=None, name="op", service="svc",
          start=0.0, duration=0.001, status="ok", sampled=True) -> trace.Span:
    sp = trace.Span(name, service)
    sp.trace_id, sp.span_id, sp.parent_id = trace_id, span_id, parent_id
    sp.sampled = sampled
    sp.start_unix = start
    sp.duration = duration
    sp.status = status
    return sp


# ---------------------------------------------------------------------------
# sampling: wire format + decision rules
# ---------------------------------------------------------------------------

def test_header_carries_sampling_flag_and_old_peers_ignore_it():
    trace.set_sampling(rate=0.0)
    with trace.span("root"):
        value = trace.header_value()
        assert value.endswith(":s=0")
        # new parser round-trips the decision
        ctx = trace.parse_header(value)
        assert ctx is not None and ctx.sampled is False
        # an "old peer" reading only the first two fields still gets valid
        # ids (the flag rides as an extra field old parse loops ignore)
        tid, sid = value.split(":")[0], value.split(":")[1]
        assert ctx.trace_id == tid and ctx.span_id == sid
    trace.set_sampling(rate=1.0)
    with trace.span("root"):
        assert trace.header_value().endswith(":s=1")


def test_parse_header_flag_compat():
    # header from an old peer (no flag) = sampled
    assert trace.parse_header("abc:def").sampled is True
    # unknown extra fields are ignored, flag still parses
    assert trace.parse_header("abc:def:s=0").sampled is False
    assert trace.parse_header("abc:def:s=1:x=9").sampled is True
    assert trace.parse_header("abc:def:junk").sampled is True
    # malformed ids still rejected
    assert trace.parse_header("ab c:def:s=0") is None


def test_child_spans_inherit_the_minted_decision():
    trace.set_sampling(rate=0.0)
    with trace.span("root") as root:
        with trace.span("child") as child:
            assert child.sampled is False
    assert root.sampled is False
    # adopting a remote parent adopts its decision, not the local rate
    with trace.trace_scope(trace.SpanContext("t", "s", sampled=True)):
        with trace.span("adopted") as sp:
            assert sp.sampled is True


def test_keep_reason_tail_rules_outrank_head_decision():
    # error always kept, slow always kept, ordinary follows the head flag
    assert trace.keep_reason(False, "error:Boom", 0.0, None) == "error"
    assert trace.keep_reason(False, "ok", 2.0, 1.0) == "slow"
    assert trace.keep_reason(False, "ok", 0.5, 1.0) is None
    assert trace.keep_reason(True, "ok", 0.5, 1.0) == "head"
    # no slow rule configured -> duration can never force a keep
    assert trace.keep_reason(False, "ok", 999.0, None) is None


# ---------------------------------------------------------------------------
# the spool: framing, rotation, eviction
# ---------------------------------------------------------------------------

def test_spool_round_trips_spans_through_wal_frames(tmp_path):
    sp = spool.SpanSpool(str(tmp_path), service="query_server")
    for i in range(5):
        sp.add(_span("t1", f"s{i}", start=float(i)).to_dict())
    sp.close()
    files = spool.spool_files(str(tmp_path))
    assert len(files) == 1 and "query_server" in files[0]
    records, _, status = tail_frames(files[0])
    assert status == "ok"
    assert [r["spanId"] for _, r in records] == [f"s{i}" for i in range(5)]


def test_spool_rotates_and_evicts_whole_segments(tmp_path):
    big = {"pad": "x" * 600}
    sp = spool.SpanSpool(str(tmp_path), service="svc",
                         segment_bytes=4096, max_bytes=3 * 4096)
    before = spool.EVICTED.value
    for i in range(200):
        rec = _span("t", f"s{i:04d}").to_dict()
        rec["attrs"] = big
        sp.add(rec)
    sp.close()
    files = spool.spool_files(str(tmp_path))
    total = sum(os.path.getsize(f) for f in files)
    assert total <= 3 * 4096 + 4096  # bound + the active segment's slack
    assert spool.EVICTED.value > before
    # survivors are the NEWEST spans — eviction ate whole old segments
    spans, probs = collect.read_spool_dir(str(tmp_path))
    assert not probs
    ids = sorted(s["spanId"] for s in spans)
    assert ids[-1] == "s0199" and "s0000" not in ids


def test_spool_shared_dir_multi_writer(tmp_path):
    a = spool.SpanSpool(str(tmp_path), service="router")
    b = spool.SpanSpool(str(tmp_path), service="replica")
    a.add(_span("t", "ra", service="router").to_dict())
    b.add(_span("t", "rb", service="replica").to_dict())
    a.close()
    b.close()
    spans, _ = collect.read_spool_dir(str(tmp_path))
    assert {s["spanId"] for s in spans} == {"ra", "rb"}


def test_configure_export_unwritable_dir_degrades_to_ring_only(
        tmp_path, monkeypatch):
    target = tmp_path / "blocked" / "spool"
    (tmp_path / "blocked").write_text("a file where a dir must go")
    monkeypatch.setenv(spool.ENV_DIR, str(target))
    before = spool.EXPORT_ERRORS.value
    assert spool.configure_export_from_env("svc") is None
    assert spool.EXPORT_ERRORS.value == before + 1
    # tracing itself still works (ring only)
    with trace.span("still-works"):
        pass


# ---------------------------------------------------------------------------
# tail sampling proof (zero wall sleeps): at s=0, error + slow spans spool,
# ordinary spans do not — and the spooled fragments assemble
# ---------------------------------------------------------------------------

def test_tail_sampling_spools_only_error_and_slow_at_s0(
        tmp_path, monkeypatch):
    monkeypatch.setenv(spool.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(spool.ENV_SAMPLE, "0")
    monkeypatch.setenv(spool.ENV_SLOW_MS, "50")
    spool.configure_export_from_env("svc")

    # ordinary span through the REAL span() path: minted s=0, fast, ok
    with trace.span("ordinary", service="svc"):
        pass
    # error span through the real path (exception -> error:<Type>)
    with pytest.raises(RuntimeError):
        with trace.span("failing", service="svc"):
            raise RuntimeError("boom")
    # slow span: constructed duration (no wall sleep), exported directly
    slow = _span("tslow", "sslow", duration=0.2, sampled=False,
                 service="svc", name="slow-op")
    spool.export_span(slow)

    spool.close_export()
    spans, probs = collect.read_spool_dir(str(tmp_path))
    assert not probs
    names = {s["name"] for s in spans}
    assert names == {"failing", "slow-op"}, names
    # and they assemble: the error trace is a complete one-span tree
    trees = collect.assemble(spans)
    failing = [t for t in trees
               if t["spans"][0]["name"] == "failing"][0]
    assert failing["complete"] is True
    assert failing["spans"][0]["status"].startswith("error:")


def test_head_sampling_spools_everything_at_s1(tmp_path, monkeypatch):
    monkeypatch.setenv(spool.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(spool.ENV_SAMPLE, "1")
    spool.configure_export_from_env("svc")
    with trace.span("kept", service="svc"):
        pass
    spool.close_export()
    spans, _ = collect.read_spool_dir(str(tmp_path))
    assert [s["name"] for s in spans] == ["kept"]


def test_middleware_marks_5xx_spans_as_errors_for_the_tail_rule(
        tmp_path, monkeypatch):
    """An unhandled 500 through the telemetry middleware reaches the spool
    even at s=0 — the error-status tail rule sees `error:http500`."""
    from aiohttp import web

    from incubator_predictionio_tpu.obs.http import telemetry_middleware

    monkeypatch.setenv(spool.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(spool.ENV_SAMPLE, "0")
    spool.configure_export_from_env("test_server")

    async def boom(request):
        raise RuntimeError("kaboom")

    async def fine(request):
        return web.json_response({"ok": True})

    app = web.Application(middlewares=[telemetry_middleware("test_server")])
    app.router.add_get("/boom", boom)
    app.router.add_get("/fine", fine)

    async def t():
        client = TestClient(TestServer(app))
        await client.start_server()
        resp = await client.get("/fine")
        assert resp.status == 200
        resp = await client.get("/boom")
        assert resp.status == 500
        await client.close()

    asyncio.run(t())
    spool.close_export()
    spans, _ = collect.read_spool_dir(str(tmp_path))
    names = {s["name"]: s for s in spans}
    assert "GET /boom" in names and names["GET /boom"]["status"] == \
        "error:http500"
    assert "GET /fine" not in names  # ordinary span dropped at s=0


def test_middleware_raised_4xx_is_not_tail_kept(tmp_path, monkeypatch):
    """A raised HTTPException 4xx is an orderly answer: a client hammering
    401s at s=0 must NOT flood the spool (and evict the 5xx/slow traces
    the tail rules exist to retain)."""
    from aiohttp import web

    from incubator_predictionio_tpu.obs.http import telemetry_middleware

    monkeypatch.setenv(spool.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(spool.ENV_SAMPLE, "0")
    spool.configure_export_from_env("auth_server")

    async def denied(request):
        raise web.HTTPUnauthorized(text="bad accessKey")

    app = web.Application(middlewares=[telemetry_middleware("auth_server")])
    app.router.add_get("/denied", denied)

    async def t():
        client = TestClient(TestServer(app))
        await client.start_server()
        resp = await client.get("/denied")
        assert resp.status == 401
        await client.close()

    asyncio.run(t())
    spool.close_export()
    spans, _ = collect.read_spool_dir(str(tmp_path))
    assert spans == [], [s["name"] for s in spans]


# ---------------------------------------------------------------------------
# assembly: trees, completeness, orphans, clock skew, waterfall
# ---------------------------------------------------------------------------

def _fleet_spans(skew_replica=0.0):
    """A synthetic router→replica→storage trace with controllable replica
    clock skew."""
    return [
        _span("T", "root", None, "POST /queries.json", "fleet_router",
              start=100.0, duration=0.100).to_dict(),
        _span("T", "fwd", "root", "forward", "fleet_router",
              start=100.005, duration=0.090).to_dict(),
        _span("T", "serve", "fwd", "POST /queries.json", "query_server",
              start=100.010 + skew_replica, duration=0.080).to_dict(),
        _span("T", "rpc", "serve", "events.find_by_entities",
              "storage_server",
              start=100.020 + skew_replica, duration=0.030).to_dict(),
    ]


def test_assemble_builds_complete_tree_with_parent_child_edges():
    trees = collect.assemble(_fleet_spans())
    assert len(trees) == 1
    t = trees[0]
    assert t["complete"] is True and not t["orphans"]
    assert t["services"] == ["fleet_router", "query_server",
                             "storage_server"]
    by_id = {s["spanId"]: s for s in t["spans"]}
    assert by_id["fwd"]["parentId"] == "root"
    assert by_id["serve"]["parentId"] == "fwd"
    assert by_id["rpc"]["parentId"] == "serve"
    assert t["durationSec"] == pytest.approx(0.100)


def test_assemble_flags_orphans_and_incompleteness():
    spans = _fleet_spans()[2:]  # router fragment lost (SIGKILL / eviction)
    trees = collect.assemble(spans)
    t = trees[0]
    assert t["complete"] is False
    assert t["orphans"] == ["serve"]  # its parent "fwd" is missing


def test_clock_skew_estimated_from_parent_child_overlap():
    # replica clock 10s ahead: its spans can't nest in the router's window
    trees = collect.assemble(_fleet_spans(skew_replica=10.0))
    t = trees[0]
    skew = t["clockSkewSec"]
    assert skew["fleet_router"] == 0.0
    # correction pulls the replica (and its storage child) back ~10s
    assert skew["query_server"] == pytest.approx(-10.0, abs=0.1)
    # corrected offsets nest inside the root again
    by_id = {s["spanId"]: s for s in t["spans"]}
    assert 0.0 <= by_id["serve"]["offsetSec"] <= 0.1


def test_waterfall_renders_one_line_per_span_with_status():
    spans = _fleet_spans()
    spans[2]["status"] = "error:Timeout"
    t = collect.assemble(spans)[0]
    lines = collect.waterfall(t)
    assert "complete=false" in lines[0] or "complete=true" in lines[0]
    body = [ln for ln in lines if "|" in ln]
    assert len(body) == 4
    assert any("!! error:Timeout" in ln for ln in body)
    assert any("storage_server: events.find_by_entities" in ln
               for ln in body)


def test_gather_spans_dedupes_across_spool_and_live_ring(tmp_path):
    sp = spool.SpanSpool(str(tmp_path), service="svc")
    rec = _span("T", "dup").to_dict()
    sp.add(rec)
    sp.close()

    def fake_fetch(url, timeout):
        return [rec, _span("T", "only-live").to_dict()]

    spans, problems = collect.gather_spans(
        spools=[str(tmp_path)], urls=["http://stub"], fetch=fake_fetch)
    assert not problems
    assert sorted(s["spanId"] for s in spans) == ["dup", "only-live"]


def test_gather_spans_reports_dead_urls_as_problems():
    def dead(url, timeout):
        raise OSError("connection refused")

    spans, problems = collect.gather_spans(urls=["http://dead"], fetch=dead)
    assert spans == [] and len(problems) == 1 and "dead" in problems[0]


# ---------------------------------------------------------------------------
# ring completeness flag (satellite): /traces.json marks partial traces
# ---------------------------------------------------------------------------

def test_trace_buffer_marks_partial_traces_incomplete():
    buf = trace.TraceBuffer(capacity=8)
    buf.add(_span("whole", "a", None))
    buf.add(_span("whole", "b", "a"))
    buf.add(_span("evicted", "c", "gone"))  # parent lost to the ring
    out = {t["traceId"]: t for t in buf.traces()}
    assert out["whole"]["complete"] is True
    assert out["evicted"]["complete"] is False


def test_traces_json_exposes_complete_flag():
    from aiohttp import web

    from incubator_predictionio_tpu.obs.http import add_observability_routes

    trace.TRACES.clear()
    trace.TRACES.add(_span("tj", "x", "missing-parent"))
    app = web.Application()
    add_observability_routes(app)

    async def t():
        client = TestClient(TestServer(app))
        await client.start_server()
        body = await (await client.get("/traces.json")).json()
        await client.close()
        return body

    body = asyncio.run(t())
    row = [tr for tr in body["traces"] if tr["traceId"] == "tj"][0]
    assert row["complete"] is False


# ---------------------------------------------------------------------------
# exemplars: histogram -> /metrics -> parser -> CLI display
# ---------------------------------------------------------------------------

def test_exemplar_round_trips_exposition_and_parser():
    reg = MetricsRegistry()
    hist = reg.histogram("pio_x_seconds", "test hist")
    with trace.span("slow-query") as sp:
        hist.observe_exemplar(0.2)
        tid = sp.trace_id
    # exemplars are opt-in: the default 0.0.4 page must stay parseable
    # by scrapers that never heard of them
    assert "# {trace_id=" not in reg.expose()
    text = reg.expose(exemplars=True)
    assert "# {trace_id=" in text
    fams = parse_prometheus_text(text)
    exemplars = fams["pio_x_seconds"]["exemplars"]
    assert len(exemplars) == 1
    name, labels, ex = exemplars[0]
    assert labels["le"] == "0.25"
    assert ex["labels"]["trace_id"] == tid
    assert ex["value"] == pytest.approx(0.2)
    # plain samples stay 3-tuples: bucket counts unchanged by the exemplar
    bucket = [v for n, l, v in fams["pio_x_seconds"]["samples"]
              if n.endswith("_bucket") and l.get("le") == "0.25"]
    assert bucket == [1.0]


def test_exemplar_without_active_trace_is_a_plain_observe():
    reg = MetricsRegistry()
    hist = reg.histogram("pio_y_seconds", "t")
    hist.observe_exemplar(0.01)  # no ambient trace
    assert "# {" not in reg.expose(exemplars=True)
    assert hist.percentiles()["p50"] == pytest.approx(0.01)


def test_metrics_route_exemplars_are_explicit_opt_in(tmp_path, monkeypatch):
    """Exemplar syntax is served ONLY on `?exemplars=1`. A stock
    Prometheus scrape must never see it — including one that advertises
    openmetrics in its default Accept header (it expects spec-exact
    OpenMetrics, which this exposition is not)."""
    from aiohttp import web

    from incubator_predictionio_tpu.obs.http import (
        HTTP_LATENCY,
        add_observability_routes,
    )

    HTTP_LATENCY.labels(service="nego", route="/x").observe_exemplar(
        0.01, trace_id="abc123")
    app = web.Application()
    add_observability_routes(app)

    async def t():
        client = TestClient(TestServer(app))
        await client.start_server()
        plain = await (await client.get("/metrics")).text()
        # stock Prometheus default Accept mentions openmetrics — it still
        # must get the strict 0.0.4 page
        sniffy = await (await client.get(
            "/metrics",
            headers={"Accept": "application/openmetrics-text;"
                               "version=1.0.0,text/plain;q=0.5"})).text()
        ext = await (await client.get("/metrics?exemplars=1")).text()
        await client.close()
        return plain, sniffy, ext

    plain, sniffy, ext = asyncio.run(t())
    assert "# {trace_id=" not in plain
    assert "# {trace_id=" not in sniffy
    parse_prometheus_text(plain)
    assert "# {trace_id=" in ext
    parse_prometheus_text(ext)


def test_exemplars_expire_at_exposition(monkeypatch):
    """An exemplar older than EXEMPLAR_MAX_AGE_SEC is dropped from the
    page — it likely outlived the spool's retention, and a dangling
    exemplar points an operator at a trace nothing can show."""
    from incubator_predictionio_tpu.obs import metrics as m

    reg = MetricsRegistry()
    hist = reg.histogram("pio_age_seconds", "t")
    hist.observe_exemplar(0.01, trace_id="old123")
    child = hist._default()
    # age the recorded exemplar in place (zero wall sleeps)
    with child._lock:
        idx, (v, tid, ts) = next(iter(child._exemplars.items()))
        child._exemplars[idx] = (v, tid, ts - m.EXEMPLAR_MAX_AGE_SEC - 1)
    assert "old123" not in reg.expose(exemplars=True)
    hist.observe_exemplar(0.01, trace_id="fresh456")
    assert "fresh456" in reg.expose(exemplars=True)


def test_middleware_exemplar_only_for_findable_traces(
        tmp_path, monkeypatch):
    """At s=0 with the spool on, an ordinary request's exemplar would point
    at a trace nothing durably keeps — the middleware records a plain
    observation instead; an error request (tail-kept) gets the exemplar."""
    from aiohttp import web

    from incubator_predictionio_tpu.obs.http import (
        HTTP_LATENCY,
        telemetry_middleware,
    )

    monkeypatch.setenv(spool.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(spool.ENV_SAMPLE, "0")
    spool.configure_export_from_env("exm_server")

    async def fine(request):
        return web.json_response({"ok": True})

    async def boom(request):
        raise RuntimeError("x")

    app = web.Application(middlewares=[telemetry_middleware("exm_server")])
    app.router.add_get("/fine", fine)
    app.router.add_get("/boom", boom)

    async def t():
        client = TestClient(TestServer(app))
        await client.start_server()
        await client.get("/fine")
        await client.get("/boom")
        await client.close()

    asyncio.run(t())
    spool.close_export()
    assert HTTP_LATENCY.labels(
        service="exm_server", route="/fine").exemplars() == {}
    boom_ex = HTTP_LATENCY.labels(
        service="exm_server", route="/boom").exemplars()
    assert boom_ex, "tail-kept error span lost its exemplar"


def test_cli_metrics_renders_exemplar(monkeypatch, capsys):
    from incubator_predictionio_tpu.tools import cli

    reg = MetricsRegistry()
    hist = reg.histogram("pio_z_seconds", "zz")
    hist.observe_exemplar(0.2, trace_id="feedc0de")
    page = reg.expose(exemplars=True)  # what ?exemplars=1 serves
    monkeypatch.setattr(cli, "_fetch_metrics_text",
                        lambda url, timeout=10.0, exemplars=False: page)
    rc = cli.main(["metrics", "http://stub:1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "exemplar le=0.25" in out and "trace=feedc0de" in out


# ---------------------------------------------------------------------------
# multi-URL metrics (satellite): merged table + aggregate column
# ---------------------------------------------------------------------------

def _page(counter_v: float, gauge_v: float, obs: float) -> str:
    reg = MetricsRegistry()
    reg.counter("pio_m_total", "c", labels=("k",)).labels(k="a").inc(
        counter_v)
    reg.gauge("pio_m_depth", "g").set(gauge_v)
    reg.histogram("pio_m_seconds", "h").observe(obs)
    return reg.expose()


def test_cli_metrics_raw_never_requests_exemplars(monkeypatch, capsys):
    """`--raw` output is pasted into strict 0.0.4 consumers (promtool) —
    the fetch must not opt into exemplar suffixes for it."""
    from incubator_predictionio_tpu.tools import cli

    asked = {}

    def fetch(url, timeout=10.0, exemplars=False):
        asked["exemplars"] = exemplars
        return _page(1, 1, 0.004)

    monkeypatch.setattr(cli, "_fetch_metrics_text", fetch)
    assert cli.main(["metrics", "http://a:1", "--raw"]) == 0
    assert asked["exemplars"] is False
    assert cli.main(["metrics", "http://a:1"]) == 0
    assert asked["exemplars"] is True
    capsys.readouterr()


def test_cli_metrics_multi_url_merges_with_aggregates(monkeypatch, capsys):
    from incubator_predictionio_tpu.tools import cli

    pages = {"http://a:1/metrics": _page(3, 7, 0.004),
             "http://b:1/metrics": _page(5, 9, 0.020)}
    monkeypatch.setattr(cli, "_fetch_metrics_text",
                        lambda url, timeout=10.0, exemplars=False: pages[url])
    rc = cli.main(["metrics", "http://a:1", "http://b:1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "s1 = http://a:1/metrics" in out
    # counters sum, gauges max
    assert "s1=3 s2=5 sum=8" in out
    assert "s1=7 s2=9 max=9" in out
    # histograms merge buckets for the fleet aggregate
    assert "all count=2" in out


def test_cli_metrics_single_url_fleet_flag_forces_table(
        monkeypatch, capsys):
    from incubator_predictionio_tpu.tools import cli

    monkeypatch.setattr(cli, "_fetch_metrics_text",
                        lambda url, timeout=10.0, exemplars=False: _page(1, 2, 0.004))
    rc = cli.main(["metrics", "http://a:1", "--fleet"])
    out = capsys.readouterr().out
    assert rc == 0 and "s1 = " in out and "sum=1" in out


def test_cli_metrics_partial_fleet_failure_keeps_the_living(
        monkeypatch, capsys):
    from incubator_predictionio_tpu.tools import cli

    def fetch(url, timeout=10.0, exemplars=False):
        if "dead" in url:
            raise OSError("refused")
        return _page(1, 1, 0.004)

    monkeypatch.setattr(cli, "_fetch_metrics_text", fetch)
    rc = cli.main(["metrics", "http://ok:1", "http://dead:1"])
    captured = capsys.readouterr()
    assert rc == 1  # partial failure is visible in the exit code
    assert "pio_m_total" in captured.out
    assert "dead" in captured.err


# ---------------------------------------------------------------------------
# CLI trace verbs over a spool
# ---------------------------------------------------------------------------

def _seed_spool(tmp_path) -> str:
    sp = spool.SpanSpool(str(tmp_path), service="fleet_router")
    for rec in _fleet_spans():
        sp.add(rec)
    slow = _span("SLOW", "sr", None, "POST /queries.json", "fleet_router",
                 start=200.0, duration=2.0).to_dict()
    sp.add(slow)
    sp.close()
    return str(tmp_path)


def test_cli_trace_list_show_slowest(tmp_path, capsys):
    from incubator_predictionio_tpu.tools import cli

    d = _seed_spool(tmp_path)
    assert cli.main(["trace", "list", "--spool", d]) == 0
    out = capsys.readouterr().out
    assert "T" in out and "complete=true" in out

    assert cli.main(["trace", "show", "T", "--spool", d]) == 0
    out = capsys.readouterr().out
    assert "fleet_router" in out and "storage_server" in out

    assert cli.main(["trace", "slowest", "--spool", d, "-n", "2"]) == 0
    out = capsys.readouterr().out
    # the 2s trace ranks first and renders as the waterfall
    assert out.splitlines()[0].startswith("SLOW")

    assert cli.main(["trace", "show", "SLOW", "--spool", d,
                     "--json"]) == 0
    tree = json.loads(capsys.readouterr().out)
    assert tree["traceId"] == "SLOW" and tree["spanCount"] == 1


def test_cli_trace_show_unknown_id_fails(tmp_path, capsys):
    from incubator_predictionio_tpu.tools import cli

    d = _seed_spool(tmp_path)
    assert cli.main(["trace", "show", "nope", "--spool", d]) == 1


def test_cli_trace_show_ambiguous_prefix_lists_matches(tmp_path, capsys):
    """An ambiguous prefix is NOT 'not found' — the error names the
    matching ids so the operator can pick one."""
    from incubator_predictionio_tpu.tools import cli

    sp = spool.SpanSpool(str(tmp_path), service="svc")
    sp.add(_span("abc111", "r1", None).to_dict())
    sp.add(_span("abc222", "r2", None).to_dict())
    sp.close()
    assert cli.main(["trace", "show", "abc", "--spool",
                     str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "ambiguous" in err and "abc111" in err and "abc222" in err
    # a unique prefix still resolves
    assert cli.main(["trace", "show", "abc1", "--spool",
                     str(tmp_path)]) == 0


def test_cli_trace_requires_a_source(monkeypatch, capsys):
    from incubator_predictionio_tpu.tools import cli

    monkeypatch.delenv("PIO_TRACE_SPOOL_DIR", raising=False)
    assert cli.main(["trace", "list"]) == 2


# ---------------------------------------------------------------------------
# dark-plane obs server (satellite): /metrics + /traces.json on a thread
# ---------------------------------------------------------------------------

def test_obs_server_serves_metrics_and_traces():
    import urllib.request

    from incubator_predictionio_tpu.obs.http import start_obs_server
    from tests.fixtures.procs import free_port

    port = free_port()
    handle = start_obs_server("stream_updater", port, ip="127.0.0.1")
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            text = resp.read().decode()
        parse_prometheus_text(text)  # strict: must be valid exposition
        assert "pio_http_requests_total" in text
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/traces.json", timeout=5) as resp:
            body = json.loads(resp.read())
        assert "traces" in body
    finally:
        handle.close()
