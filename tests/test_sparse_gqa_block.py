"""The grouped-query / sparse-index block (models/sparse_gqa.py composed with
models/latent_moe.py's experts) held to its plain reference
(benchmarks/reference/gqa_sparse_moe_ref.py) at a small size on the CPU,
float32 weights: the block as ``forward`` runs it, the selection itself, and
the paged cache's serve path in both forms (a miss cut into pieces that
straddle pages and piece edges, turns through the cache, eviction and
re-miss, batched turns of unequal length in one context bucket), and the
hand-over between a miss's pieces (another caller's turns run between them,
a caller that names the session being cut waits for it).

Tolerance: both sides compute in float32 at ``highest`` precision and differ
in the order of sums (grouped against dense experts, tiled running softmax
against a whole one, gathered rows against a mask); logits of unit scale
agree to a few 1e-6, ``TOL`` = 1e-4 as ISSUE 30 asks.
"""

from __future__ import annotations

import hashlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import gqa_sparse_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models import sparse_gqa as sg
from incubator_predictionio_tpu.obs import trace
from incubator_predictionio_tpu.obs.metrics import REGISTRY, parse_prometheus_text
from incubator_predictionio_tpu.serving.latent_cache import LatentServing
from tests.fixtures.sparse_tiny import (
    config,
    masked_reference,
    reference_logits,
    seeded_params,
)

TOL = 1e-4


@pytest.fixture(scope="module")
def sessions():
    return np.random.default_rng(5).integers(1, 512, (8, 96)).astype(np.int32)


def assert_answers(serving, params, cfg, requests, tol=TOL):
    scores, items = serving.extend(requests)
    for (_, tokens), s, i in zip(requests, scores, items):
        want_s, want_i = masked_reference(params, cfg, tokens)
        np.testing.assert_array_equal(i, want_i)
        np.testing.assert_allclose(s, want_s, atol=tol, rtol=0)


def _samples(name: str) -> dict:
    fam = parse_prometheus_text(REGISTRY.expose()).get(name, {"samples": []})
    return {tuple(sorted(labels.items())): value
            for _, labels, value in fam["samples"]}


def _counter(name: str) -> float:
    return sum(_samples(name).values())


# ---------------------------------------------------------------------------
# the equations
# ---------------------------------------------------------------------------

def test_block_forward_matches_the_reference(sessions):
    cfg = config()
    params = seeded_params(cfg)
    rows = np.zeros((3, cfg.max_len), np.int32)   # left-padded, as fit stages
    for r, n in enumerate((96, 40, 9)):
        rows[r, cfg.max_len - n:] = sessions[r, :n]
    h = jax.jit(lambda p, t, q: lm.forward(p, t, q, cfg))(
        params, rows, lm.real_positions(rows))
    got = lm._mm(h, params["head"].T)
    for r, n in enumerate((96, 40, 9)):
        want = reference_logits(params, cfg, sessions[r, :n])
        assert np.abs(want).max() > 0.3   # logits of unit scale, not zeros
        np.testing.assert_allclose(got[r, -1], want, atol=TOL, rtol=0)


def test_kth_largest_is_exact_with_ties_negatives_and_masked_entries():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 40)).astype(np.float32)
    x[0, :10] = 0.0                      # ties at zero
    x[1] = -np.abs(x[1])                 # all negative
    x[2, 5:] = lm.NEG                    # fewer real entries than k
    x[3, ::2] = x[3, 1]                  # a tie at the threshold
    for k in (1, 8, 40):
        want = np.sort(x, -1)[:, ::-1][:, k - 1]
        np.testing.assert_array_equal(
            np.asarray(sg.kth_largest(jnp.asarray(x), k)), want)


def test_the_selected_set_is_the_references_for_every_query(sessions):
    """Every query's selection, in the mask forms (threshold) and in the
    gather form (``lax.top_k``), against the reference's mask."""
    cfg = config()
    lw = seeded_params(cfg)["layers"][0]
    x = lm.rms_norm(
        jax.random.normal(jax.random.key(3), (1, 96, cfg.d_model)) * 3.0,
        lw["norm1"], cfg.rms_norm_eps)
    pos = jnp.arange(96)[None]
    _, want = ref.attention(x[0], lw, sg.published(cfg), pos[0],
                            with_selection=True)
    want = np.asarray(want)
    assert (want.sum(-1) == np.minimum(np.arange(96) + 1, 8)).all()
    _, q_idx, w_idx, rows = sg.project(x, lw, cfg, pos)
    seen = np.tril(np.ones((96, 96), bool))
    score = jnp.where(seen, sg.index_scores(
        q_idx, w_idx, rows["idx"], jnp.float32)[0], lm.NEG)
    least, room = sg.cut(score, 8)
    mask = np.asarray(sg.chosen_by(score, least, room, jnp.zeros_like(room))[0]) & seen
    np.testing.assert_array_equal(mask, want)
    _, at = jax.lax.top_k(score, 8)
    gathered = np.zeros((96, 96), bool)
    gathered[np.arange(96)[:, None], np.asarray(at)] = True
    np.testing.assert_array_equal(gathered & seen, want)


def test_a_context_no_longer_than_topk_is_plain_grouped_query_attention(
        sessions):
    """With no more keys than ``index_topk`` nothing is left out: the block
    with top-8 on 8 tokens equals the block whose index keeps everything,
    and differs from it on 40."""
    cfg, plain = config(), sg.published(config(index_topk=96))
    params = seeded_params(cfg)
    fwd = jax.jit(lambda p, t: ref.forward(p, t, plain, last_only=True))
    for n, same in ((8, True), (40, False)):
        tokens = sessions[2, :n]
        got = reference_logits(params, cfg, tokens)
        want = np.asarray(fwd(params, tokens))
        assert (np.abs(got - want).max() < TOL) == same
        serving = LatentServing(params, cfg)
        serving.warmup(1)
        scores, items = serving.extend([("s", tokens)])
        serving.close()
        masked = want.copy()
        masked[0] = masked[tokens] = -np.inf
        top = np.argsort(-masked, kind="stable")[:16]
        assert (np.abs(scores[0] - masked[top]).max() < TOL) == same


def test_softmax_router_takes_no_bias_and_normalises_over_the_picks():
    cfg = config()
    lw = seeded_params(cfg)["layers"][1]
    assert "b_r" not in lw and "ws1" not in lw
    x = jax.random.normal(jax.random.key(4), (50, cfg.d_model))
    idx, w = lm.moe_router(x, lw, cfg)
    want_idx, want_w = ref.route(x, lw, sg.published(cfg))
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)


# ---------------------------------------------------------------------------
# the paged cache: extend == full forward
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = config()
    params = seeded_params(cfg)
    serving = LatentServing(params, cfg)
    # turns go one session a dispatch, whatever the server's max_batch
    assert serving.warmup(4) == 7
    assert serving.info()["buckets"] == [
        "1x16@16:select", "1x16@32:select", "1x16@64:select",
        "1x16@96:select",
        "1x32@32:chunk", "1x32@64:chunk", "1x32@96:chunk"]
    info = serving.info()
    assert info["path"] == "device-kv-index-cache"
    assert info["cache_row_widths"] == {"kv": 128, "idx": 128}
    assert info["cache_bytes_per_token"] == 2 * (128 + 128) * 4 + 4
    yield serving, params, cfg
    serving.close()


def _dispatched() -> dict:
    return {dict(k)["bucket"]: v
            for k, v in _samples("pio_seq_dispatches_total").items()}


def test_a_miss_cut_into_pieces_then_turns_equal_the_full_forward(
        served, sessions):
    """70 tokens: pieces of 32 + 32 over the contexts 32 and 64, then the
    6-token tail in the turns' form, with pages of 8 and key tiles of 16;
    then turns of 3, 5 and 16 tokens through the cache."""
    serving, params, cfg = served
    before, chunks = _dispatched(), _counter("pio_seq_prefill_chunks_total")
    scored = _counter("pio_seq_index_rows_scored_total")
    chosen = _counter("pio_seq_sparse_rows_selected_total")
    tokens = sessions[0]
    for n in (70, 73, 78, 94):
        assert_answers(serving, params, cfg, [("a", tokens[:n])])
    now = _dispatched()
    grew = {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}
    # (the 6-token tail of the cut block goes as a turn does)
    assert grew == {"1x32@32": 1, "1x32@64": 1, "1x16@96": 4}
    assert _counter("pio_seq_prefill_chunks_total") - chunks == 2
    # every query at absolute index i scored i + 1 rows and attended min(i + 1, 8)
    assert _counter("pio_seq_index_rows_scored_total") - scored \
        == 94 * 95 // 2
    assert _counter("pio_seq_sparse_rows_selected_total") - chosen \
        == 8 * 9 // 2 + 86 * 8


def test_turns_of_one_request_batch_take_each_its_own_context_bucket(
        served, sessions):
    serving, params, cfg = served
    lengths = {"b1": 20, "b2": 45, "b3": 50, "b4": 33}
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 1, :n]) for i, (k, n) in enumerate(lengths.items())])
    before = _dispatched()
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 1, :n + 2 + i])
        for i, (k, n) in enumerate(lengths.items())])
    now = _dispatched()
    grew = {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}
    assert grew == {"1x16@32": 1, "1x16@64": 3}   # 22 | 48, 54, 38


def test_batched_turns_of_unequal_length_share_a_context_bucket(sessions):
    """The ``select`` form at a batch of four (a ladder this block's
    ``serve_shapes`` does not ask for: a batch costs the device what its
    sessions cost one by one): one dispatch over the bucket of the
    longest."""
    cfg = config()
    params = seeded_params(cfg)
    serving = LatentServing(params, cfg)
    serving.batches = (1, 4)
    serving.warmup(4)
    lengths = {"b1": 20, "b2": 45, "b3": 50, "b4": 33}
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 1, :n]) for i, (k, n) in enumerate(lengths.items())])
    before = _dispatched()
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 1, :n + 2 + i])
        for i, (k, n) in enumerate(lengths.items())])
    now = _dispatched()
    serving.close()
    # one dispatch of four turns over the bucket of the longest (53 -> 64)
    assert now["4x16@64"] - before.get("4x16@64", 0) == 1
    assert sum(now.values()) - sum(before.values()) == 1


def _cut_beside(serving, miss, other, until_queued=True):
    """Runs ``extend(miss)`` on a thread of its own and ``extend(other)`` on
    another, which starts while the miss's first piece is in flight and is
    waiting at the lock before that piece ends. Returns both answers and the
    order of the dispatches as ``(who, bucket)``."""
    order, started, answers = [], threading.Event(), {}
    dispatch, lock = serving._dispatch, serving._lock

    def recorded(group, batch, block, ctx, *a, **kw):
        who = threading.current_thread().name
        order.append((who, serving.label(batch, block, ctx)))
        if who == "miss" and not started.is_set():
            started.set()
            for _ in range(2000):          # until the other waits at the lock
                if lock._next - lock._serving >= 2:
                    break
                threading.Event().wait(0.005)
        return dispatch(group, batch, block, ctx, *a, **kw)

    def call(name, requests):
        answers[name] = serving.extend(requests)

    serving._dispatch = recorded
    try:
        first = threading.Thread(target=call, args=("miss", miss), name="miss")
        second = threading.Thread(
            target=lambda: (started.wait(60), call("other", other)),
            name="other")
        first.start(), second.start()
        first.join(120), second.join(120)
    finally:
        del serving._dispatch
    assert not first.is_alive() and not second.is_alive()
    return answers, order


def _assert_answer(params, cfg, tokens, answer):
    want_s, want_i = masked_reference(params, cfg, tokens)
    np.testing.assert_array_equal(answer[1][0], want_i)
    np.testing.assert_allclose(answer[0][0], want_s, atol=TOL, rtol=0)


def test_another_callers_turn_runs_between_the_pieces_of_a_miss(
        served, sessions):
    serving, params, cfg = served
    assert_answers(serving, params, cfg, [("t", sessions[2, :40])])
    answers, order = _cut_beside(
        serving, [("m", sessions[1, :94])], [("t", sessions[2, :43])])
    # the turn took the lock after the miss's first piece, not after its last
    assert order == [("miss", "1x32@32"), ("other", "1x16@64"),
                     ("miss", "1x32@64"), ("miss", "1x32@96")]
    _assert_answer(params, cfg, sessions[1, :94], answers["miss"])
    _assert_answer(params, cfg, sessions[2, :43], answers["other"])
    assert not serving._cutting


def test_a_cut_miss_and_the_turn_beside_it_in_spans(served, sessions):
    """A miss of 94 tokens is two head-less pieces and a last one; a turn of
    another caller waits at the lock through the first piece and runs after
    it. Pieces are ``seq.miss.*`` and only the last waits for an answer; the
    turn is ``seq.turn.*``; the miss's hand-overs are ``seq.batch.lock``
    spans with ``why="offer"``, the first as long as the turn it let in."""
    serving, params, cfg = served
    assert_answers(serving, params, cfg, [("u", sessions[4, :40])])
    trace.TRACES.clear()
    _, order = _cut_beside(
        serving, [("n", sessions[5, :94])], [("u", sessions[4, :43])])
    assert [who for who, _ in order] == ["miss", "other", "miss", "miss"]
    spans = trace.TRACES.spans()
    by_id = {s["spanId"]: s for s in spans}
    named = lambda name: [s for s in spans if s["name"] == name]
    extends = named("seq.batch.extend")
    assert [s["attrs"]["bucket"] for s in extends] == [b for _, b in order]
    first, turn = extends[0], extends[1]
    # stage and launch a dispatch, a wait only where a head answers
    for part, n in (("stage", 3), ("launch", 3), ("wait", 1)):
        assert len(named(f"seq.miss.{part}")) == n, part
        assert len(named(f"seq.turn.{part}")) == 1, part
    assert [s["attrs"]["launches"] for s in named("seq.miss.launch")] == [
        cfg.n_layers + 1, cfg.n_layers + 1, cfg.n_layers + 2]
    assert by_id[named("seq.miss.wait")[0]["parentId"]] is extends[3]
    assert by_id[named("seq.turn.wait")[0]["parentId"]] is turn
    # the children cover a dispatch that waits for its answer (a head-less
    # piece of this size is over in 0.3 ms: three spans' own cost shows)
    for s in (turn, extends[3]):
        assert trace.self_seconds(spans)[s["spanId"]] \
            <= 0.05 * s["durationSec"], s["attrs"]
    # the locks: each caller's entry, and the miss's two hand-overs
    locks = named("seq.batch.lock")
    entries = [s for s in locks if "ahead" in s["attrs"]]
    offers = [s for s in locks if s["attrs"].get("why") == "offer"]
    assert len(entries) == 2 and len(offers) == 2
    # the turn stood at the lock through the miss's whole first piece ...
    waited = max(entries, key=lambda s: s["durationSec"])
    assert waited["attrs"]["ahead"] == 1
    assert waited["durationSec"] >= first["durationSec"]
    # ... and the miss stood aside for the whole turn, then for nobody
    assert offers[0]["durationSec"] >= turn["durationSec"]
    assert offers[1]["durationSec"] < turn["durationSec"]


def test_a_caller_that_names_the_session_being_cut_waits_for_it(
        served, sessions):
    serving, params, cfg = served
    reused = _counter("pio_seq_tokens_reused_total")
    answers, order = _cut_beside(
        serving, [("c", sessions[3, :94])], [("c", sessions[3, :96])])
    assert order == [("miss", "1x32@32"), ("miss", "1x32@64"),
                     ("miss", "1x32@96"), ("other", "1x16@96")]
    _assert_answer(params, cfg, sessions[3, :94], answers["miss"])
    _assert_answer(params, cfg, sessions[3, :96], answers["other"])
    assert _counter("pio_seq_tokens_reused_total") - reused == 94


def test_a_session_being_cut_is_not_evicted_by_another_callers_miss(
        served, sessions):
    """The cache holds 6 x 96 tokens: with five whole sessions beside it the
    other caller's 90-token miss has to evict, and takes an older session,
    not the one whose pieces are still running."""
    serving, params, cfg = served
    for i in range(5):
        assert_answers(serving, params, cfg, [(f"f{i}", sessions[i, :90])])
    evicted = _counter("pio_seq_cache_evictions_total")
    answers, order = _cut_beside(
        serving, [("m2", sessions[5, :94])], [("o2", sessions[6, :90])])
    assert [who for who, _ in order[:2]] == ["miss", "other"]
    assert _counter("pio_seq_cache_evictions_total") > evicted
    _assert_answer(params, cfg, sessions[5, :94], answers["miss"])
    _assert_answer(params, cfg, sessions[6, :90], answers["other"])
    assert_answers(serving, params, cfg, [("m2", sessions[5, :96])])


def test_a_context_full_of_real_keys_still_masks_the_padding_item(
        served, sessions):
    """96 tokens fill the largest context: no invalid key stands for item 0
    there (this session's padding logit is among its best 16)."""
    serving, params, cfg = served
    assert_answers(serving, params, cfg, [("full", sessions[5, :96])])


def test_a_list_sent_again_is_encoded_from_where_it_grew(served, sessions):
    serving, params, cfg = served
    encoded = []

    def encode(items):
        encoded.append(len(items))
        return np.asarray([int(i) for i in items], np.int32)

    given = [str(t) for t in sessions[4, :60]]
    for n, want in ((50, 50), (53, 3), (53, 0), (60, 7)):
        del encoded[:]
        answer = serving.extend([("g", given[:n])], encode)
        assert encoded == [want]
        _assert_answer(params, cfg, sessions[4, :n], answer)
    # a list whose head changed is encoded whole, and answered as it stands
    changed = [given[1]] + given[1:60]
    del encoded[:]
    answer = serving.extend([("g", changed)], encode)
    assert encoded == [60]
    _assert_answer(params, cfg, np.asarray(changed, np.int32), answer)


def test_a_failed_dispatch_leaves_nothing_of_its_sessions_in_the_table(
        served, sessions, monkeypatch):
    serving, params, cfg = served
    assert_answers(serving, params, cfg, [("x", sessions[7, :30])])
    held = len(serving._free) + sum(
        len(s.pages) for s in serving._sessions.values())

    def broken(*a, **kw):
        raise RuntimeError("the device said no")

    monkeypatch.setattr(serving, "_dispatch", broken)
    with pytest.raises(RuntimeError, match="said no"):
        serving.extend([("x", sessions[7, :33]), ("y", sessions[6, :20])])
    monkeypatch.undo()
    assert "x" not in serving._sessions and "y" not in serving._sessions
    assert not serving._cutting
    assert len(serving._free) + sum(
        len(s.pages) for s in serving._sessions.values()) == held
    reused = _counter("pio_seq_tokens_reused_total")
    assert_answers(serving, params, cfg, [("x", sessions[7, :33])])
    assert _counter("pio_seq_tokens_reused_total") == reused   # a miss again


def test_eviction_and_re_miss_answer_as_a_miss(served, sessions):
    """The cache holds 6 x 96 tokens: seven whole sessions evict the first,
    which then comes back as a miss with the same answer; a session whose
    prefix changed is recomputed from the change on."""
    serving, params, cfg = served
    evicted = _counter("pio_seq_cache_evictions_total")
    for i in range(7):
        assert_answers(serving, params, cfg, [(f"e{i}", sessions[i, :90])])
    assert _counter("pio_seq_cache_evictions_total") > evicted
    reused = _counter("pio_seq_tokens_reused_total")
    assert_answers(serving, params, cfg, [("e0", sessions[0, :92])])
    assert _counter("pio_seq_tokens_reused_total") == reused  # a miss again
    changed = sessions[6, :90].copy()
    changed[40:] = sessions[5, 40:90]
    assert_answers(serving, params, cfg, [("e6", changed)])
    assert _counter("pio_seq_tokens_reused_total") - reused == 40


def test_the_tolerance_catches_a_wrong_selection(served, sessions, monkeypatch):
    """Top-4 in place of top-8 moves the logits by far more than ``TOL``."""
    import dataclasses

    serving, params, cfg = served
    wrong = LatentServing(params, dataclasses.replace(cfg, index_topk=4))
    wrong.warmup(1)
    scores, _ = wrong.extend([("w", sessions[3, :60])])
    wrong.close()
    want, _ = masked_reference(params, cfg, sessions[3, :60])
    assert np.abs(scores[0] - want).max() > 100 * TOL


def test_scopes_in_the_programs(served):
    serving, _, _ = served
    scopes = serving.device_scopes()
    # the short block's buckets are one turn program each, the pieces'
    # their layer and their head
    assert set(scopes) == {
        f"jit_seq_turn_b{b}_t{t}_c{c}" for b, t, c in serving.ladder()
        if t == serving.blocks[0]} | {
        f"jit_seq_{kind}_b{b}_t{t}_c{c}" for kind in ("layer", "head")
        for b, t, c in serving.ladder() if t != serving.blocks[0]}
    layer = {"gqa_proj", "idx_score", "idx_select", "sparse_attn",
             "moe_router", "moe_experts"}
    for module, found in scopes.items():
        want = {"head_topk"} if "_head_" in module else layer \
            if "_layer_" in module else layer | {"head_topk"}
        assert set(found.values()) == want, module
    # a trace shows a loop's operations inside the loop's own event: a map
    # with the loop in it counts the body twice
    text = serving._exe[1, 32, 96]["layer"].as_text()
    loops = re.findall(r"^\s*%?([\w.\-]+) = [^\n]* while\(", text, re.M)
    assert loops and not set(loops) & set(
        scopes["jit_seq_layer_b1_t32_c96"])


@pytest.mark.parametrize("bucket, digest", [
    ((1, 16, 32),
     "cc75a8353c516859cf3fcb2f69da17f92f35ad8bcc4d0bb66eb114e8ad748e8a"),
    ((1, 32, 96),
     "4832938d8b210ce28d9a09094e10c5007840b124c56f023e99597decfaea5385"),
], ids=["select", "chunk"])
def test_the_sparse_index_layer_lowers_to_the_parents_program(
        served, bucket, digest):
    """ISSUE 34 gave the config a layer pattern, the cache a per-session
    state and the experts a second activation, and had to leave this block's
    programs alone: the layer's lowered text (no debug info) is commit
    38e73b2's, taken before that change was made."""
    serving, _, _ = served
    text = serving._lower(*bucket)["layer"].as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest, (
        f"the digest was taken under jax 0.9.0 and this is jax "
        f"{jax.__version__}: after a JAX upgrade, or a deliberate change to "
        f"the sparse-index block, pin the new digest")
