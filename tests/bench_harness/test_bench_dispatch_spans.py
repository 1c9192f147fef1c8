"""The eight readers ISSUE 36 adds under benchmarks/layer_metrics/: the
server's occupancy (``serve.server.empty`` / ``.occupied``) and the sequence
dispatch's lock, stage, launch and wait. Each gives a hand-checked number on
recorded evidence and ``None`` where the program publishes nothing: an empty
dict, or the parent commit's page, which has other rows but not these. The
timeline reader and the refined idle-by-span table are checked on a trace
recorded on one v5e chip (benchmarks/testdata/dispatch_spans.xplane.pb)."""

import os

import pytest

from benchmarks import harness, program_spans as ps

from bench_tiny import ROOT

SERVE_CELLS = 5
SEQ_CELLS = 3


def _metrics(spans: dict) -> dict:
    """``{span: (seconds, count)}`` as the parsed ``/metrics`` page has it."""
    out = {"pio_serving_batches": 1.0}
    for span, (seconds, count) in spans.items():
        scope, _, phase = span.rpartition(".")
        labels = f'{{scope="{scope}",phase="{phase}"}}'
        out["pio_profile_phase_seconds_total" + labels] = seconds
        out["pio_profile_phases_total" + labels] = count
    return out


#: a sequence cell's window: 400 requests in 100 batches; the server fell
#: empty 400 times; 90 short dispatches, 30 long ones of which 10 were
#: head-less pieces; 20 hand-overs of the lock between pieces
BEFORE = _metrics({
    "serve.server.empty": (2.0, 10), "serve.server.occupied": (1.0, 10),
    "seq.batch.lock": (0.010, 20), "seq.batch.match": (0.020, 20),
    "seq.batch.extend": (0.400, 24),
    "seq.turn.stage": (0.009, 18), "seq.turn.launch": (0.070, 18),
    "seq.turn.wait": (0.050, 18),
    "seq.miss.stage": (0.001, 6), "seq.miss.launch": (0.020, 6),
    "seq.miss.wait": (0.300, 6),
})
AFTER = _metrics({
    "serve.server.empty": (2.0 + 20.4, 10 + 400),
    "serve.server.occupied": (1.0 + 30.6, 10 + 400),
    "seq.batch.lock": (0.010 + 0.450, 20 + 120),
    "seq.batch.match": (0.020 + 0.050, 20 + 100),
    "seq.batch.extend": (0.400 + 2.2, 24 + 120),
    "seq.turn.stage": (0.009 + 0.045, 18 + 90),
    "seq.turn.launch": (0.070 + 0.315, 18 + 90),
    "seq.turn.wait": (0.050 + 0.270, 18 + 90),
    "seq.miss.stage": (0.001 + 0.006, 6 + 30),
    "seq.miss.launch": (0.020 + 0.090, 6 + 30),
    "seq.miss.wait": (0.300 + 1.404, 6 + 20),
})
#: the traced part's executables: 40 short dispatches of a pattern (6 + 1 + 1
#: layer programs), three of them in the ``4x16`` bucket, and two long ones
TRACE = {
    "module_s": {
        "jit_seq_embed_b1_t16_c512": 37 * 0.00001,
        "jit_seq_embed_b4_t16_c512": 3 * 0.00002,
        "jit_seq_ssm_b1_t16": 37 * 6 * 0.0002,
        "jit_seq_ssm_b4_t16": 3 * 6 * 0.0006,
        "jit_seq_moe_b1_t16": 37 * 0.0013, "jit_seq_moe_b4_t16": 3 * 0.0023,
        "jit_seq_gqa_b1_t16_c512": 37 * 0.0002,
        "jit_seq_gqa_b4_t16_c512": 3 * 0.0003,
        "jit_seq_head_b1_t16_c512": 37 * 0.0005,
        "jit_seq_head_b4_t16_c512": 3 * 0.0006,
        # the long blocks', another block size that starts with the short
        # one's digits, and a program that is no dispatch's
        "jit_seq_embed_b1_t128_c128": 2 * 0.00003,
        "jit_seq_ssm_b1_t128": 2 * 6 * 0.0004,
        "jit_seq_moe_b1_t160": 9.0, "jit_quantize_user_rows": 5.0},
    "module_runs": {
        "jit_seq_embed_b1_t16_c512": 37, "jit_seq_embed_b4_t16_c512": 3,
        "jit_seq_ssm_b1_t16": 222, "jit_seq_ssm_b4_t16": 18,
        "jit_seq_moe_b1_t16": 37, "jit_seq_moe_b4_t16": 3,
        "jit_seq_gqa_b1_t16_c512": 37, "jit_seq_gqa_b4_t16_c512": 3,
        "jit_seq_head_b1_t16_c512": 37, "jit_seq_head_b4_t16_c512": 3,
        "jit_seq_embed_b1_t128_c128": 2, "jit_seq_ssm_b1_t128": 12,
        "jit_seq_moe_b1_t160": 1, "jit_quantize_user_rows": 1}}
EV = {"metrics_before": BEFORE, "metrics_after": AFTER, "trace": TRACE,
      "shape": {"short_block": 16}}
#: the parent commit: one ``seq.batch.extend`` a dispatch, no occupancy
PARENT = {"metrics_before": _metrics({"seq.batch.match": (0.020, 20),
                                      "seq.batch.extend": (0.400, 24),
                                      "serve.batch.predict": (0.5, 20)}),
          "metrics_after": _metrics({"seq.batch.match": (0.070, 120),
                                     "seq.batch.extend": (2.6, 144),
                                     "serve.batch.predict": (2.9, 120)}),
          "trace": TRACE, "shape": {"short_block": 16}}

BY_HAND = {
    "server_empty_pct": 100.0 * 20.4 / 51.0,            # 40%
    "seq_lock_wait_ms": 450.0 / 100,                     # a BATCH, offers in
    "seq_turn_stage_ms": 45.0 / 90, "seq_turn_launch_ms": 315.0 / 90,
    "seq_turn_wait_ms": 270.0 / 90,
    "seq_miss_extend_ms": (6.0 + 90.0 + 1404.0) / 30,   # 50 a DISPATCH
    # 37 lone: 0.01 + 1.2 + 1.3 + 0.2 + 0.5 = 3.21 ms; 3 groups: 0.02 + 3.6
    # + 2.3 + 0.3 + 0.6 = 6.82 ms; over the 40 embed runs
    "seq_turn_device_ms": (37 * 3.21 + 3 * 6.82) / 40,
}
TIMELINE = "device_idle_occupied_pct.serve"


def test_benchmark_json_appends_the_eight_and_no_program_span():
    bench = harness.load_benchmark(ROOT)
    new = bench["per_layer"][-8:]
    assert [m["name"] for m in new] == [
        "server_empty_pct", TIMELINE, "seq_lock_wait_ms",
        "seq_turn_stage_ms", "seq_turn_launch_ms", "seq_turn_wait_ms",
        "seq_miss_extend_ms", "seq_turn_device_ms"]
    assert sorted(m["name"] for m in new) == sorted([*BY_HAND, TIMELINE])
    serve = [w["name"] for w in bench["workloads"]
             if w["traffic"].startswith("serve-")]
    assert len(serve) == SERVE_CELLS
    for m in new:
        assert m["moves"] == "serve_p50_ms" and m["better"] == "lower"
        cells = serve if m["name"] in ("server_empty_pct", TIMELINE) \
            else [c for c in serve if c.startswith("seq-")]
        assert m["workloads"] == cells, m["name"]
        # the aggregate's rows are read off /metrics, the timeline and the
        # executables off the trace; the pinned program_span list stays
        assert m["source"] == ("device_trace" if m["name"] in (
            TIMELINE, "seq_turn_device_ms") else "program_counter")
    assert len([c for c in serve if c.startswith("seq-")]) == SEQ_CELLS
    assert {m["layer"] for m in new} == {
        "query server + micro-batcher", "device", "sequence serving",
        "kernels"}


@pytest.mark.parametrize("name, want", sorted(BY_HAND.items()))
def test_reader_on_recorded_on_empty_and_on_the_parents_evidence(name, want):
    read = harness.load_reader(name)
    assert read(EV) == pytest.approx(want)
    assert read({}) is None
    assert read(PARENT) is None
    # a page with other rows and none of any span
    bare = {"metrics_before": {"pio_serving_batches": 0.0},
            "metrics_after": {"pio_serving_batches": 9.0},
            "trace": TRACE, "shape": {"short_block": 16}}
    assert read(bare) is None


def test_a_window_in_which_one_state_never_ended():
    """The exact cell is almost never empty: a window in which no ``empty``
    interval closed reads 0, not nothing; one in which neither closed (an
    idle deployment between two scrapes a second apart) reads nothing."""
    read = harness.load_reader("server_empty_pct")
    never = {"metrics_before": BEFORE, "metrics_after": {
        **AFTER, **_metrics({"serve.server.empty": (2.0, 10)})}}
    assert read(never) == 0.0
    assert read({"metrics_before": AFTER, "metrics_after": AFTER}) is None


def test_a_window_without_long_blocks_or_without_traced_turns():
    quiet = {**EV, "metrics_after": {
        **AFTER, **_metrics({"seq.miss.stage": (0.001, 6),
                             "seq.miss.launch": (0.020, 6),
                             "seq.miss.wait": (0.300, 6)})}}
    assert harness.load_reader("seq_miss_extend_ms")(quiet) is None
    assert harness.load_reader("seq_turn_stage_ms")(quiet) == 0.5
    # no short dispatch fell into the traced part; an untraced run
    long_only = {**EV, "trace": {
        "module_s": {"jit_seq_embed_b1_t128_c128": 1.0},
        "module_runs": {"jit_seq_embed_b1_t128_c128": 2}}}
    read = harness.load_reader("seq_turn_device_ms")
    assert read(long_only) is None
    assert read({**EV, "trace": None}) is None
    assert read({**EV, "shape": {}}) is None


# -- the timeline reader, on a trace recorded on the chip ------------------------------

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata",
                        "dispatch_spans.xplane.pb")

#: benchmarks/testdata/dispatch_spans.xplane.pb (my chip run, PR 36: one v5e;
#: bench_scratch/record_dispatch_trace.py printed its events, from which the
#: numbers below were summed independently of ``trace_reduce``; the file is
#: the capture less everything the readers never take: the device plane's
#: ``XLA Ops`` / ``XLA Modules`` lines without stats, the ``pio.*`` events of
#: ``/host:CPU``). A ``MicroBatcher`` in front of the CPU tests' tiny latent
#: block answers, with ~3 ms of empty server between them: a miss (40 items,
#: ``1x128@128``), a turn (+3, ``1x16@64``), then ONE batch of a turn (+2)
#: and a miss (200 items, ``1x256@256``). The capture starts inside the first
#: empty interval, which therefore has no event; the last one ends at
#: ``stop()``. Extent 49363710..80916949 = 31553239 ns (the first
#: ``pio.serve.batch.assemble`` to the end of the last ``serve.server.empty``);
#: 1,985 device operations, merged busy 641475 ns (the device's clock reads
#: ~0.4 ms behind the host's here: a dispatch's embed "starts" before its
#: launch span). Idle ns by innermost covering span:
BY_HAND_IDLE_NS = {
    "serve.server.empty": 10_757_864,   # 4008787 + 3672048 + 3081088 less ops
    "seq.turn.launch": 7_175_023, "seq.miss.launch": 6_753_010,
    "seq.miss.wait": 1_834_548, "seq.turn.wait": 1_666_379,
    ps.UNSPANNED: 1_487_471,            # hand-over, the loop between spans
    "serve.batch.predict": 749_150, "seq.batch.extend": 168_209,
    "seq.batch.match": 143_340, "serve.batch.assemble": 48_440,
    "seq.miss.stage": 39_080, "seq.turn.stage": 34_890,
    "serve.batch.merge": 32_210, "seq.batch.lock": 22_150,
}
IDLE_NS = 30_911_764
EXTENT_NS = 31_553_239
#: the two turns' executables on ``XLA Modules``: embed 3027 + 3113, three
#: layers each 31182 + 29739 + 29805 and 30578 + 29783 + 30993, head 10482 +
#: 10342 = 209044 ns over the 2 embed runs; the misses' (t128, t256) are not in
TURN_DEVICE_NS = 209_044 / 2


def test_the_refined_idle_table_on_the_recorded_trace():
    ops, spans = ps.load(TESTDATA)
    assert len(ops) == 1985
    names = sorted(n for n, _, _ in spans)
    assert names.count("serve.server.empty") == 3
    assert names.count("seq.batch.lock") == 3 == names.count("seq.batch.match")
    for kind in ("turn", "miss"):
        for part in ("stage", "launch", "wait"):
            assert names.count(f"seq.{kind}.{part}") == 2
    assert "serve.server.occupied" not in names   # explicit: ring only
    # an empty server overlaps no batch's predict
    empty = [(s, e) for n, s, e in spans if n == "serve.server.empty"]
    for n, s, e in spans:
        if n == "serve.batch.predict":
            assert not [1 for a, b in empty if a < e and s < b]
    idle = ps.idle_by_span(ops, spans)
    assert set(idle) == set(BY_HAND_IDLE_NS)
    for name, ns in BY_HAND_IDLE_NS.items():
        assert idle[name] == pytest.approx(ns / 1e9, abs=2e-9), name
    # the classes sum to the idle time (the acceptance asks for 1%)
    assert sum(BY_HAND_IDLE_NS.values()) == IDLE_NS
    assert sum(idle.values()) == pytest.approx(IDLE_NS / 1e9, rel=1e-6)


def test_idle_while_occupied_on_the_recorded_trace(capsys, monkeypatch):
    read = harness.load_reader(TIMELINE)
    ev = {"trace": {"busy_s": 641475e-9}}
    want = 100.0 * (IDLE_NS - BY_HAND_IDLE_NS["serve.server.empty"]) \
        / EXTENT_NS                                   # 63.87%
    assert read(ev, path=TESTDATA) == pytest.approx(want)
    table = capsys.readouterr().out
    assert "every row" in table and "0.0309 s idle + 0.0006 s busy" in table
    for name in BY_HAND_IDLE_NS:
        assert name in table
    # the run's own trace is found as the sibling reader finds it
    monkeypatch.setattr(ps, "newest_trace", lambda root=None: TESTDATA)
    assert read(ev) == pytest.approx(want)
    assert read({}) is None                            # an untraced run
    assert read({**ev, "kind": "train"}) is None
    # the parent: a timeline with pio.* spans and no occupancy, a page with
    # no such row (spans.xplane.pb is PR 24's recording)
    old = os.path.join(ROOT, "benchmarks", "testdata", "spans.xplane.pb")
    assert read({**ev, **PARENT}, path=old) is None
    # the program books occupancy but the server was never empty in the
    # traced part: all of the idle time is the program's to shorten
    assert 0.0 < read({**ev, **EV}, path=old) < 100.0
    monkeypatch.setattr(ps, "newest_trace", lambda root=None: None)
    assert read(ev) is None


def test_a_turns_device_time_on_the_recorded_trace():
    from benchmarks import trace_reduce

    reduced = trace_reduce.reduce_file(TESTDATA)
    assert reduced["busy_s"] == pytest.approx(641475e-9)
    ev = {**EV, "trace": reduced}
    assert harness.load_reader("seq_turn_device_ms")(ev) == pytest.approx(
        TURN_DEVICE_NS / 1e6)
    assert reduced["module_runs"]["jit_seq_embed_b1_t16_c64"] == 2
    assert reduced["module_runs"]["jit_seq_layer_b1_t256_c256"] == 3
