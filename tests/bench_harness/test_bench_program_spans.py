"""The readers of the program's own spans (benchmarks/program_spans.py and the
17 readers ISSUE 24 adds under benchmarks/layer_metrics/): each gives a number
on recorded evidence and ``None`` where the program publishes nothing — a
commit before PR 24, or an empty dict — and the idle-by-span reduction gives
hand-checked seconds on plain tuples and on a trace recorded on one v5e chip
(benchmarks/testdata/spans.xplane.pb)."""

import os

import pytest

from benchmarks import harness, program_spans as ps

from bench_tiny import ROOT

MS = 1e6  # ns


def _row(family, span, value):
    scope, _, phase = span.rpartition(".")
    return {f'{family}{{scope="{scope}",phase="{phase}"}}': value}


def _metrics(spans: dict) -> dict:
    """``{span: (seconds, count)}`` as the parsed ``/metrics`` page has it."""
    out = {"pio_serving_batches": 1.0}
    for span, (seconds, count) in spans.items():
        out.update(_row("pio_profile_phase_seconds_total", span, seconds))
        out.update(_row("pio_profile_phases_total", span, count))
    return out


#: what a served window leaves: warm-up and deploy before it, 100 requests in
#: 40 batches inside it
BEFORE = _metrics({
    "serve.request.parse": (0.010, 50), "serve.request.queue": (0.100, 50),
    "serve.request.respond": (0.020, 50),
    "serve.batch.dispatch": (0.200, 20), "serve.batch.predict": (0.180, 20),
    "retrieval.batch.lookup": (0.002, 20), "retrieval.batch.rows": (0.004, 20),
    "retrieval.batch.device": (0.100, 29),
    "deploy.restore": (6.0, 1), "deploy.quantize": (1.5, 1),
    "deploy.ensure_host": (2.5, 1), "deploy.warmup": (12.0, 1),
})
AFTER = _metrics({
    "serve.request.parse": (0.010 + 0.030, 150),
    "serve.request.queue": (0.100 + 0.250, 150),
    "serve.request.respond": (0.020 + 0.045, 150),
    "serve.batch.dispatch": (0.200 + 0.160, 60),
    "serve.batch.predict": (0.180 + 0.140, 60),
    "retrieval.batch.lookup": (0.002 + 0.008, 60),
    "retrieval.batch.rows": (0.004 + 0.020, 60),
    "retrieval.batch.device": (0.100 + 0.120, 69),
    "deploy.restore": (6.0, 1), "deploy.quantize": (1.5, 1),
    "deploy.ensure_host": (2.5, 1), "deploy.warmup": (12.0, 1),
})
SERVE = {"metrics_before": BEFORE, "metrics_after": AFTER}


def _span(trace, name, seconds, **attrs):
    return {"traceId": trace, "spanId": name + trace, "parentId": None,
            "name": name, "durationSec": seconds, "attrs": attrs}


#: the ring after a warm-up verb (w) and two verbs of the window (a, b)
RING = [
    _span("w", "train.verb", 30.0, instance="warm"),
    _span("w", "train.verb.read", 9.0),
    _span("a", "train.verb", 22.0, instance="A"),
    _span("a", "train.verb.read", 0.02), _span("a", "train.verb.bimaps", 0.5),
    _span("a", "train.verb.index", 2.0), _span("a", "train.verb.persist", 2.4),
    _span("a", "train.verb.commit", 0.04), _span("a", "train.fit.order", 3.0),
    _span("b", "train.verb", 24.0, instance="B"),
    _span("b", "train.verb.read", 0.04), _span("b", "train.verb.bimaps", 0.7),
    _span("b", "train.verb.index", 2.2), _span("b", "train.verb.persist", 4.6),
    _span("b", "train.verb.commit", 0.06), _span("b", "train.fit.order", 3.4),
    _span("x", "train.verb.read", 99.0),  # no train.verb root of the window
]
TRAIN = {"kind": "train", "verbs": [{"instance_id": "A"},
                                    {"instance_id": "B"}]}

SERVE_READERS = {
    "server_parse_ms": 0.3, "batcher_queue_wait_ms": 2.5,
    "batcher_handover_ms": 0.5, "server_respond_ms": 0.45,
    "retrieval_lookup_ms": 0.2, "retrieval_device_ms": 3.0,
    "retrieval_rows_ms": 0.5, "deploy_restore_s": 10.0,
    "deploy_warmup_s": 12.0,
}
TRAIN_READERS = {
    "workflow_read_s": 0.03, "workflow_bimaps_s": 0.6,
    "workflow_index_build_s": 2.1, "workflow_persist_s": 3.5,
    "workflow_commit_s": 0.05, "trainer_stage_order_s": 3.2,
}
TRACE_READERS = ("device_idle_unspanned_pct.train",
                 "device_idle_unspanned_pct.serve")


def test_benchmark_json_lists_exactly_these_readers():
    bench = harness.load_benchmark(ROOT)
    spans = [m["name"] for m in bench["per_layer"]
             if m["source"] == "program_span"
             and m["name"] not in ("workflow_nonfit_s", "trainer_step_ms",
                                   "trainer_stage_init_s")]
    assert sorted(spans) == sorted(
        [*SERVE_READERS, *TRAIN_READERS, *TRACE_READERS])
    assert len(spans) == 17


@pytest.mark.parametrize("name, want", sorted(SERVE_READERS.items()))
def test_serve_reader_on_recorded_and_on_empty_evidence(name, want):
    read = harness.load_reader(name)
    assert read(SERVE) == pytest.approx(want)
    assert read({}) is None
    # a program that publishes no such span (the parent commit): its page
    # has other rows, and the reader finds nothing to read
    bare = {"metrics_before": {"pio_serving_batches": 0.0},
            "metrics_after": {"pio_serving_batches": 9.0}}
    assert read(bare) is None


@pytest.mark.parametrize("name, want", sorted(TRAIN_READERS.items()))
def test_train_reader_on_recorded_and_on_empty_evidence(
        name, want, monkeypatch):
    read = harness.load_reader(name)
    monkeypatch.setattr(ps, "_ring_spans", lambda: RING)
    assert read(TRAIN) == pytest.approx(want)
    assert read({}) is None
    # the parent's ring has no train.verb span: nothing to read
    monkeypatch.setattr(ps, "_ring_spans", lambda: [
        s for s in RING if s["name"] != "train.verb"])
    assert read(TRAIN) is None


def test_window_means_leave_out_what_came_before_the_window():
    assert ps.window(SERVE, "serve.request.queue") == pytest.approx(
        (0.250, 100))
    # no span of that name finished inside the window (the exact scorer's
    # span in a two-stage cell: warm-up opened it, the traffic never does)
    quiet = {"metrics_before": BEFORE, "metrics_after": {
        **AFTER, **_metrics({"retrieval.batch.device": (0.100, 29)})}}
    assert ps.mean_s(quiet, "retrieval.batch.device") is None
    assert harness.load_reader("retrieval_device_ms")(quiet) is None


def test_the_ring_reader_reads_the_programs_own_ring():
    from incubator_predictionio_tpu.obs import trace

    trace.TRACES.clear()
    with trace.span("train.verb") as root:
        root.set_attr("instance", "live")
        with trace.span("train.verb.index"):
            pass
        with trace.span("train.verb.index"):  # summed within the verb
            pass
    rows = trace.TRACES.spans()
    want = sum(s["durationSec"] for s in rows
               if s["name"] == "train.verb.index")
    ev = {"kind": "train", "verbs": [{"instance_id": "live"}]}
    assert ps.verb_span_s(ev, "train.verb.index") == pytest.approx(want)
    assert ps.verb_span_s(ev, "train.verb.read") == 0.0  # spanned, not run
    assert ps.verb_span_s(
        {"verbs": [{"instance_id": "other"}]}, "train.verb.index") is None


def test_idle_by_span_on_plain_tuples():
    ops = [(0 * MS, 2 * MS), (1 * MS, 3 * MS), (10 * MS, 11 * MS)]
    spans = [("train.verb", 0 * MS, 16 * MS),
             ("train.verb.persist", 4 * MS, 9 * MS),
             ("serve.batch.predict", 18 * MS, 20 * MS)]
    idle = ps.idle_by_span(ops, spans)
    # busy 0..3 and 10..11 of an extent 0..20: idle 3..10, 11..20
    assert idle["train.verb.persist"] == pytest.approx(5e-3)
    assert idle["train.verb"] == pytest.approx(2e-3 + 5e-3)
    assert idle["serve.batch.predict"] == pytest.approx(2e-3)
    assert idle[ps.UNSPANNED] == pytest.approx(2e-3)
    assert sum(idle.values()) == pytest.approx(16e-3)
    assert ps.idle_by_span(ops, []) is None      # the parent: no pio.* span
    assert ps.idle_by_span([], spans) is None    # no device in the trace


TESTDATA = os.path.join(ROOT, "benchmarks", "testdata", "spans.xplane.pb")


#: benchmarks/testdata/spans.xplane.pb by hand, in ns, from the events
#: bench_scratch/record_spans_trace.py printed when it recorded the file on
#: one v5e (PR 24). Device operations (``XLA Ops``), merged:
#:   40397969..40397982, 40397984..40499493 (matmul), 49425481..49450798
#:   (tanh), 58237499..58249195, 58249197..58339285 (matmul)
#: Program spans (``pio.*`` on ``/host:CPU``):
#:   outer 41474630..53977560, inner 47435350..51349160,
#:   tail 59385320..61556070
#: (the device's clock reads about 1.1 ms behind the host's in this file:
#: each kernel "runs" that long before the host call that launched it). Idle
#: gaps over the extent 40397969..61556070, by innermost covering span:
#:   40499493..49425481: unspanned to 41474630, outer to 47435350, then inner
#:   49450798..58237499: inner to 51349160, outer to 53977560, then unspanned
#:   58339285..61556070: unspanned to 59385320, then tail
#:   and the two 2 ns seams inside the matmuls, under no span
BY_HAND_IDLE_NS = {
    "test.trace.outer": 5_960_720 + 2_628_400,
    "test.trace.inner": 1_990_131 + 1_898_362,
    "test.trace.tail": 2_170_750,
    ps.UNSPANNED: 2 + 975_137 + 4_259_939 + 2 + 1_046_035,
}


def test_recorded_chip_trace_with_program_spans(capsys):
    """``test.trace.outer`` around a matmul, a 4 ms sleep and
    ``test.trace.inner`` (a 3 ms sleep, then a tanh pass), a 5 ms sleep
    under no span, then ``test.trace.tail`` (a matmul and a 1 ms sleep); an
    explicit span and an await-crossing one were recorded too."""
    ops, spans = ps.load(TESTDATA)
    # explicit and await-crossing spans never reach the timeline
    assert sorted(n for n, _, _ in spans) == [
        "test.trace.inner", "test.trace.outer", "test.trace.tail"]
    assert len(ops) == 7
    idle = ps.idle_by_span(ops, spans)
    assert set(idle) == set(BY_HAND_IDLE_NS)
    for name, ns in BY_HAND_IDLE_NS.items():
        assert idle[name] == pytest.approx(ns / 1e9, abs=2e-9), name
    # all of the extent that is not busy: 21158101 - 228623 ns
    assert sum(idle.values()) == pytest.approx(20_929_478 / 1e9, abs=1e-8)
    pct = ps.unspanned_pct({"trace": {"busy_s": 1.0}}, path=TESTDATA)
    assert pct == pytest.approx(100.0 * 6_281_115 / 20_929_478)
    table = capsys.readouterr().out
    assert "device idle by program span" in table
    assert "test.trace.outer" in table and ps.UNSPANNED in table
    # an untraced run has no trace to read
    assert ps.unspanned_pct({}, path=TESTDATA) is None


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_on_recorded_and_on_empty_evidence(name, monkeypatch):
    read = harness.load_reader(name)
    monkeypatch.setattr(ps, "newest_trace", lambda root=None: TESTDATA)
    kind = "train" if name.endswith(".train") else "serve"
    ev = {"trace": {"busy_s": 1.0}, **({"kind": "train"}
                                       if kind == "train" else {})}
    assert 0.0 < read(ev) < 100.0
    assert read({}) is None
    # the other kind of cell is not this reader's
    other = {"trace": {"busy_s": 1.0}, **({} if kind == "train"
                                          else {"kind": "train"})}
    assert read(other) is None
    # the parent's trace holds bench.* spans only
    old = os.path.join(ROOT, "benchmarks", "testdata", "small.xplane.pb")
    monkeypatch.setattr(ps, "newest_trace", lambda root=None: old)
    assert read(ev) is None
    monkeypatch.setattr(ps, "newest_trace", lambda root=None: None)
    assert read(ev) is None


def test_newest_trace_is_the_runs_own(tmp_path):
    assert ps.newest_trace(str(tmp_path)) is None
    for i, cell in enumerate(("cell-a", "cell-b")):
        d = tmp_path / cell / "trace" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        p = d / "host.xplane.pb"
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))
    assert ps.newest_trace(str(tmp_path)).endswith(
        os.path.join("cell-b", "trace", "plugins", "profile", "t",
                     "host.xplane.pb"))
