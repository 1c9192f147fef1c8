"""The state-space / grouped-query / routed-expert layer pattern
(models/state_space.py composed with models/sparse_gqa.py's plain attention
and models/latent_moe.py's experts) held to its plain reference
(benchmarks/reference/ssm_gqa_moe_ref.py) at a small size on the CPU, float32
weights: the pattern as ``forward`` runs it, the chunked scan against the
token-by-token recurrence, the chip's share of the experts, and the session
cache's serve path in every form and join (a scan from zero, steps from a
cached state, a block cut into pieces that hand the state on, a batch of
sessions of unequal growth with padding, a cold session in the short form),
the reuse rule (continue / restart / miss / eviction / unchanged list: the
same function of the list), the slot table, and the whole normal path
(``run_train`` → orbax persist → ``QueryServer`` → ``POST /queries.json``).

Tolerance: both sides compute in float32 at ``highest`` precision and differ
in the order of sums (tiles against tokens, grouped against dense experts);
logits of unit scale agree to a few 1e-6, ``TOL`` = 1e-4.
"""

from __future__ import annotations

import asyncio
import dataclasses
import datetime as dt
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ssm_gqa_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models import state_space as ssm
from incubator_predictionio_tpu.models.transformer import (
    TransformerConfig,
    TransformerModel,
)
from incubator_predictionio_tpu.obs import trace
from incubator_predictionio_tpu.obs.metrics import REGISTRY, parse_prometheus_text
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.serving.latent_cache import LatentServing
from tests.fixtures.ssm_tiny import (
    config,
    masked_reference,
    published_params,
    reference_logits,
    seeded_params,
)

TOL = 1e-4


@pytest.fixture(scope="module")
def sessions():
    return np.random.default_rng(5).integers(1, 512, (12, 96)).astype(np.int32)


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


def _counter(name: str, **labels) -> float:
    return sum(v for k, v in _samples(name).items()
               if set(labels.items()) <= set(k))


def _dispatched() -> dict:
    return {dict(k)["bucket"]: v
            for k, v in _samples("pio_seq_dispatches_total").items()}


def _grew(before: dict) -> dict:
    now = _dispatched()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


# ---------------------------------------------------------------------------
# the equations
# ---------------------------------------------------------------------------

def test_pattern_forward_matches_the_reference(sessions):
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


def _mixer_inputs(cfg, t, seed=3):
    lw = seeded_params(cfg)["layers"][0]
    h = jax.random.normal(jax.random.key(seed), (1, t, cfg.d_model)) * 2.0
    return lw, h


@pytest.mark.parametrize("t, chunk", [(16, 128), (96, 8), (40, 16), (96, 96)],
                         ids=["one_tile", "twelve_tiles", "gcd_tiles",
                              "whole"])
def test_chunked_scan_is_the_token_by_token_recurrence(t, chunk):
    """Outputs and the state after the block, whatever the tile."""
    cfg = config(ssm_chunk=chunk)
    lw, h = _mixer_inputs(cfg, t)
    got, (state, _) = ssm.mixer(lw, h, cfg, jnp.ones((1, t), bool),
                                jnp.asarray([t]))
    x = ref.rms_norm(h[0], lw["norm1"], cfg.rms_norm_eps)
    want, want_state = ref.mixer(x, lw, ssm.published(cfg), count=t)
    np.testing.assert_allclose(got[0] - h[0], want, atol=TOL, rtol=0)
    np.testing.assert_allclose(state[0], want_state, atol=TOL, rtol=0)


def test_a_block_continues_from_what_the_last_one_carried():
    """40 tokens as 25 + 15: the state and the convolution's last inputs
    carried over are what make the second block's outputs the whole's."""
    cfg = config()
    lw, h = _mixer_inputs(cfg, 40)
    whole, (state, kept) = ssm.mixer(lw, h, cfg, jnp.ones((1, 40), bool),
                                     jnp.asarray([40]))
    _, carried = ssm.mixer(lw, h[:, :25], cfg, jnp.ones((1, 25), bool),
                           jnp.asarray([25]))
    tail, (state2, kept2) = ssm.mixer(
        lw, h[:, 25:], cfg, jnp.ones((1, 15), bool), jnp.asarray([15]),
        carried)
    np.testing.assert_allclose(tail, whole[:, 25:], atol=TOL, rtol=0)
    np.testing.assert_allclose(state2, state, atol=TOL, rtol=0)
    np.testing.assert_array_equal(kept2, kept)
    # without the carried convolution inputs the first tokens differ
    cold, _ = ssm.mixer(lw, h[:, 25:], cfg, jnp.ones((1, 15), bool),
                        jnp.asarray([15]), (carried[0], 0 * carried[1]))
    assert np.abs(cold - whole[:, 25:]).max() > 100 * TOL


@pytest.mark.parametrize("real", [0, 1, 2, 5, 16])
def test_padding_leaves_state_and_convolution_rows_untouched(real):
    """A block of 16 with ``real`` real tokens carries on what the real
    tokens alone carry: none leaves both as they were."""
    cfg = config()
    lw, h = _mixer_inputs(cfg, 16)
    _, before = ssm.mixer(lw, h[:, :9] * 0.7, cfg, jnp.ones((1, 9), bool),
                          jnp.asarray([9]))
    valid = (jnp.arange(16) < real)[None]
    got, after = ssm.mixer(lw, h, cfg, valid, jnp.asarray([real]), before)
    if real:
        want, alone = ssm.mixer(lw, h[:, :real], cfg,
                                jnp.ones((1, real), bool),
                                jnp.asarray([real]), before)
        np.testing.assert_allclose(got[:, :real], want, atol=TOL, rtol=0)
    else:
        alone = before
    np.testing.assert_allclose(after[0], alone[0], atol=1e-6, rtol=0)
    np.testing.assert_array_equal(after[1], alone[1])


def test_router_is_sigmoid_with_a_selection_bias_and_a_scaling_factor():
    cfg = config()
    lw = seeded_params(cfg)["layers"][1]
    x = jax.random.normal(jax.random.key(4), (50, cfg.d_model))
    idx, w = lm.moe_router(x, lw, cfg)
    want_idx, want_w = ref.route(x, lw, ssm.published(cfg))
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    # the bias moves picks and never a weight
    plain = lm.moe_router(x, {**lw, "b_r": 0 * lw["b_r"]}, cfg)[0]
    assert (np.sort(plain, -1) != np.sort(idx, -1)).any()


def test_relu2_experts_have_two_matrices_stored_lane_aligned():
    cfg = config()
    lw = seeded_params(cfg)["layers"][1]
    assert "we3" not in lw and "ws3" not in lw
    assert lw["we1"].shape == (8, 64, 256) and lw["we2"].shape == (8, 256, 64)
    assert not np.asarray(lw["we1"][..., 160:]).any()  # the padding is zeros
    assert not np.asarray(lw["we2"][:, 160:]).any()
    assert np.asarray(lw["we1"][..., :160]).all()
    assert np.asarray(lw["we2"][:, :160]).all()
    # (a width under one lane tile is stored as it is)
    assert lm.expert_shapes(config(moe_intermediate_size=32))["we1"][0] \
        == (8, 64, 32)
    assert lw["ws1"].shape == (64, 48)                 # the shared one's own
    gated = lm.expert_shapes(config(expert_activation="gated_silu"))
    assert {"we1", "we3", "we2", "ws1", "ws3", "ws2"} <= set(gated)
    x = jax.random.normal(jax.random.key(6), (40, cfg.d_model))
    idx, w = lm.moe_router(x, lw, cfg)
    y, _ = lm.moe_experts(x, idx, w, jnp.ones(40, bool), lw, cfg)
    plain = published_params({"layers": [lw]}, cfg)["layers"][0]
    np.testing.assert_allclose(
        y + lm.moe_shared(x, lw), ref.experts(x, plain, ssm.published(cfg)),
        atol=TOL, rtol=0)


def test_two_shares_add_up_to_the_uncut_layer():
    """Two chips of four experts each: the routed parts of the two shares
    plus what both compute alike (ONE shared expert) are the uncut
    reference's expert layer."""
    cfg = config()
    lw = seeded_params(cfg)["layers"][3]
    plain = published_params({"layers": [lw]}, cfg)["layers"][0]
    x = jax.random.normal(jax.random.key(7), (96, cfg.d_model))
    want = ref.experts(x, plain, ssm.published(cfg))
    shared = lm.moe_shared(x, lw)
    total, unheld = shared, 0
    for share in range(2):
        part = dataclasses.replace(cfg, experts_held=4, expert_offset=4 * share)
        cut = slice(4 * share, 4 * share + 4)
        mine = {**lw, "we1": lw["we1"][cut], "we2": lw["we2"][cut]}
        idx, w = lm.moe_router(x, mine, part)
        y, counters = lm.moe_experts(x, idx, w, jnp.ones(96, bool), mine, part)
        total = total + y
        unheld += int(counters[4])
        # the share alone is the reference's share alone
        np.testing.assert_allclose(
            y + shared, ref.experts(
                x, {**plain, "we1": plain["we1"][cut],
                    "we2": plain["we2"][cut]}, ssm.published(part)),
            atol=TOL, rtol=0)
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    assert unheld == 96 * 2   # every pick is held by exactly one share


@pytest.mark.parametrize("change, message", [
    (dict(layer_pattern="SEX"), "one of 'S'"),
    (dict(layer_pattern="SESE"), "n_layers=6 of them"),
    (dict(ssm_groups=3), "ssm_groups dividing ssm_heads"),
    (dict(n_kv_heads=3), "n_kv_heads dividing n_heads"),
    (dict(expert_activation="gelu"), "unknown expert_activation"),
    (dict(layer_pattern=""), "served in a pattern only"),
    (dict(attention_kind="mla", rope_parameters=(("a", 1),)),
     "takes its 'A' layers from attention_kind='gqa'"),
])
def test_config_says_what_a_pattern_needs(change, message):
    with pytest.raises(ValueError, match=message):
        config(**change)


# ---------------------------------------------------------------------------
# the session cache: extend == full forward
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = config()
    params = seeded_params(cfg)
    serving = LatentServing(params, cfg)
    assert serving.warmup(4) == 7
    info = serving.info()
    assert info["buckets"] == [
        "1x16@24:step", "1x16@48:step", "1x16@96:step", "4x16@24:step",
        "4x16@48:step", "4x16@96:step", "1x96@96:scan"]
    assert info["path"] == "device-state-kv-cache"
    assert info["cache_row_widths"] == {"kv": 128}
    # one attention layer's rows and the token id; two mixers' states
    assert info["cache_bytes_per_token"] == 128 * 4 + 4
    assert info["state_bytes_per_session"] == 2 * (8 * 8 * 16 + 3 * 128) * 4
    assert info["state_slots"] == 6 and info["layer_pattern"] == "SESEAE"
    yield serving, params, cfg
    serving.close()


def test_a_scan_from_zero_then_steps_from_the_cached_state(served, sessions):
    """A 70-token miss in the long form, then turns of 3, 5 and 16 tokens
    from the state it left; the counters say which form ran what."""
    serving, params, cfg = served
    before = _dispatched()
    scan = _counter("pio_seq_state_tokens_total", form="scan")
    step = _counter("pio_seq_state_tokens_total", form="step")
    alone = _counter("pio_seq_state_step_sessions_total")
    tokens = sessions[0]
    for n in (70, 73, 78, 94):
        assert_answers(serving, params, cfg, [("a", tokens[:n])])
    assert _grew(before) == {"1x96@96": 1, "1x16@96": 3}
    assert _counter("pio_seq_state_tokens_total", form="scan") - scan == 70
    assert _counter("pio_seq_state_tokens_total", form="step") - step == 24
    assert _counter("pio_seq_state_step_sessions_total") - alone == 3


def test_a_cold_session_of_a_few_items_runs_in_the_short_form(served,
                                                              sessions):
    serving, params, cfg = served
    before = _dispatched()
    assert_answers(serving, params, cfg, [("cold", sessions[1, :11])])
    assert _grew(before) == {"1x16@24": 1}
    assert_answers(serving, params, cfg, [("cold", sessions[1, :12])])


def test_a_batch_of_unequal_growth_with_a_padding_row(served, sessions):
    """Three sessions of different lengths grow by 1, 4 and 9 items in ONE
    dispatch of four rows: the padding row lands in slot 0 and page 0."""
    serving, params, cfg = served
    lengths = {"b1": 20, "b2": 40, "b3": 33}
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 2, :n]) for i, (k, n) in enumerate(lengths.items())])
    before = _dispatched()
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 2, :n + g])
        for (i, (k, n)), g in zip(enumerate(lengths.items()), (1, 4, 9))])
    assert _grew(before) == {"4x16@48": 1}     # 21, 44 and 42 items
    assert not np.asarray(serving.cache[0]["state"][0]).any()


def test_a_block_cut_into_pieces_hands_the_state_on(sessions):
    """With blocks of 16 and 32 a 75-token miss is 32 + 32 and an 11-token
    tail in the short form: each piece starts from the state the one before
    left in the session's slot."""
    cfg = config()
    params = seeded_params(cfg)
    serving = LatentServing(params, cfg)
    serving.shapes = dataclasses.replace(serving.shapes, blocks=(16, 32))
    serving.blocks = serving.shapes.blocks
    serving.warmup(4)
    before = _dispatched()
    assert_answers(serving, params, cfg, [("p", sessions[5, :75])])
    assert _grew(before) == {"1x32@32": 1, "1x32@96": 1, "1x16@96": 1}
    assert_answers(serving, params, cfg, [("p", sessions[5, :80])])
    serving.close()


@pytest.mark.parametrize("case", ["continue", "diverge", "shorter",
                                  "unchanged", "slid"])
def test_the_reuse_rule(served, sessions, case):
    """The state stands at the length last computed: only a longer list
    that begins with the cached one continues from it; every other list is
    computed from 0 and gives the same function of the list."""
    serving, params, cfg = served
    base = sessions[6, :50]
    assert_answers(serving, params, cfg, [("r", base)])
    reused = _counter("pio_seq_tokens_reused_total")
    restarts = _counter("pio_seq_state_restarts_total")
    asked = {
        "continue": sessions[6, :55],
        "diverge": np.concatenate([base[:30], sessions[7, 30:60]]),
        "shorter": base[:40],
        "unchanged": base,
        "slid": np.concatenate([base[1:], sessions[7, :5]]),
    }[case]
    assert_answers(serving, params, cfg, [("r", asked)])
    got = _counter("pio_seq_tokens_reused_total") - reused
    assert got == (50 if case == "continue" else 0)
    # a restart is counted where a prefix did match
    assert _counter("pio_seq_state_restarts_total") - restarts == (
        case in ("diverge", "shorter", "unchanged"))
    # and what follows continues from the list as last computed
    assert_answers(serving, params, cfg, [
        ("r", np.concatenate([asked, sessions[8, :3]])[:96])])
    assert _counter("pio_seq_tokens_reused_total") - reused - got \
        == len(asked)


def test_the_state_a_session_holds_is_the_references_recurrence(
        served, sessions):
    """A miss and two turns, then the first layer's slot read back: the
    tokens the state stands at and the reference's state after them (the
    padding behind ``count`` steps by 0 there)."""
    serving, params, cfg = served
    for n in (40, 43, 51):
        assert_answers(serving, params, cfg, [("st", sessions[11, :n])])
    tokens, kept = serving.session_state("st", 0)
    np.testing.assert_array_equal(tokens, sessions[11, :51])
    plain = published_params(params, cfg)
    padded = np.ones(cfg.max_len, np.int32)
    padded[:51] = tokens
    want = ref.first_state(plain, plain["layers"][0], padded, 51,
                           ssm.published(cfg), None)
    np.testing.assert_allclose(kept["state"].reshape(want.shape), want,
                               atol=TOL, rtol=0)
    assert np.abs(want).max() > 10 * TOL
    assert kept["conv"].shape == (3 * (64 + 2 * 2 * 16),)
    assert serving.session_state("nobody", 0) is None


def test_eviction_frees_pages_and_slot_and_a_reused_slot_starts_from_zeros(
        served, sessions):
    """Six slots: a seventh session evicts the least recently used one and
    takes its slot, whose old state it must not see; the evicted session
    comes back as a miss with the same answer."""
    serving, params, cfg = served
    for i in range(6):
        assert_answers(serving, params, cfg, [(f"e{i}", sessions[i, :60])])
    assert not serving._free_slots
    victim = serving._sessions["e0"].slot
    evicted = _counter("pio_seq_state_evictions_total")
    pages = _counter("pio_seq_cache_evictions_total")
    assert _samples("pio_seq_state_slots")[(("state", "used"),)] == 6
    assert _samples("pio_seq_state_slots")[(("state", "capacity"),)] == 6
    assert np.asarray(serving.cache[0]["state"][victim]).any()
    assert_answers(serving, params, cfg, [("new", sessions[9, :5])])
    assert "e0" not in serving._sessions
    assert serving._sessions["new"].slot == victim
    assert _counter("pio_seq_state_evictions_total") - evicted == 1
    assert _counter("pio_seq_cache_evictions_total") - pages == 1
    assert_answers(serving, params, cfg, [("new", sessions[9, :9])])
    reused = _counter("pio_seq_tokens_reused_total")
    assert_answers(serving, params, cfg, [("e0", sessions[0, :62])])
    assert _counter("pio_seq_tokens_reused_total") == reused   # a miss again


def test_a_request_without_a_key_gives_its_slot_back(served, sessions):
    serving, params, cfg = served
    held = len(serving._free_slots) + len(serving._sessions)
    assert_answers(serving, params, cfg, [(None, sessions[10, :30]),
                                          (None, sessions[11, :8])])
    assert len(serving._free_slots) + len(serving._sessions) == held
    assert len(set(serving._free_slots)) == len(serving._free_slots)


@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "no_key"])
@pytest.mark.parametrize("slots", [6, 9], ids=["slots_run_out", "pages_run_out"])
def test_a_batch_the_cache_cannot_hold_leaves_neither_pages_nor_slots_behind(
        sessions, slots, keyed):
    """Seven whole-length sessions in one batch against 72 pages: the slots
    or the pages run out while a NEW session, which the table does not know
    yet, already holds the other kind. (``_match`` fails before anything is
    dispatched: nothing is compiled here.)"""
    cfg = config(state_slots=slots)
    serving = LatentServing(seeded_params(cfg), cfg)
    total = len(serving._free), len(serving._free_slots)
    assert total[1] == slots
    with pytest.raises(RuntimeError, match="too small for this batch"):
        serving.extend([(f"big{i}" if keyed else None, sessions[i, :96])
                        for i in range(7)])
    assert not serving._sessions
    assert (len(serving._free), len(serving._free_slots)) == total
    assert len(set(serving._free_slots)) == slots
    assert len(set(serving._free)) == total[0]
    serving.close()


def test_a_failed_dispatch_leaves_neither_pages_nor_slots_behind(
        served, sessions, monkeypatch):
    serving, params, cfg = served
    assert_answers(serving, params, cfg, [("x", sessions[7, :30])])

    def held():
        return (len(serving._free) + sum(
            len(s.pages) for s in serving._sessions.values()),
            len(serving._free_slots) + len(serving._sessions))

    before = held()

    def broken(*a, **kw):
        raise RuntimeError("the device said no")

    monkeypatch.setattr(serving, "_dispatch", broken)
    with pytest.raises(RuntimeError, match="said no"):
        serving.extend([("x", sessions[7, :33]), ("y", sessions[6, :20])])
    monkeypatch.undo()
    assert "x" not in serving._sessions and "y" not in serving._sessions
    assert held() == before and not serving._cutting
    assert_answers(serving, params, cfg, [("x", sessions[7, :33])])


def test_the_match_span_says_how_many_slots_were_taken(served, sessions):
    serving, params, cfg = served
    trace.TRACES.clear()
    serving.extend([("s1", sessions[3, :20]), ("s1", sessions[3, :22])])
    match = [s for s in trace.TRACES.spans()
             if s["name"] == "seq.batch.match"][-1]
    assert match["attrs"]["sessions"] == 2
    assert match["attrs"]["slots_taken"] in (0, 1)   # 0: an evicted one's
    extend = [s["attrs"] for s in trace.TRACES.spans()
              if s["name"] == "seq.batch.extend"]
    assert [e["form"] for e in extend] == ["scan", "step"]


def test_a_scan_and_a_step_in_spans(served, sessions):
    """A pattern's long block stages its operands on the device once
    (``stage``), issues embed + one program a layer + the head (``launch``)
    and fetches the answer (``wait``): ``seq.miss.*``, the scan form. The
    step from the cached state is ``seq.turn.*``: its operands stay numpy
    and ride the ONE launch of the bucket's turn program."""
    serving, params, cfg = served
    trace.TRACES.clear()
    serving.extend([("sp", sessions[2, :20]), ("sp", sessions[2, :22])])
    spans = [s for s in trace.TRACES.spans() if s["name"].startswith("seq.")]
    parts = ("stage", "launch", "wait")
    assert [s["name"] for s in spans] == [
        "seq.batch.lock", "seq.batch.match",
        *(f"seq.miss.{p}" for p in parts), "seq.batch.extend",
        *(f"seq.turn.{p}" for p in parts), "seq.batch.extend"]
    for kids, parent, launches in (
            (spans[2:5], spans[5], len(serving.kinds) + 2),
            (spans[6:9], spans[9], 1)):
        assert all(s["parentId"] == parent["spanId"] for s in kids)
        assert kids[0]["attrs"] == {"sessions": 1}
        assert kids[1]["attrs"] == {"launches": launches}
    assert [s["attrs"]["form"] for s in (spans[5], spans[9])] \
        == ["scan", "step"]


def test_a_state_kept_in_bfloat16_is_outside_the_tolerance(served, sessions):
    """The precision step on the mechanism itself: turns through a state
    rounded to bfloat16 between requests move the logits by far more than
    ``TOL`` (the sound path's gap is 2e-6)."""
    serving, params, cfg = served
    low = LatentServing(params, dataclasses.replace(
        cfg, state_dtype="bfloat16"))
    low.warmup(1)
    assert low.cache[0]["state"].dtype == jnp.bfloat16
    for n in range(40, 71, 3):
        scores, _ = low.extend([("w", sessions[3, :n])])
    low.close()
    want, _ = masked_reference(params, cfg, sessions[3, :70])
    assert np.abs(scores[0] - want).max() > TOL


def test_programs_scopes_and_what_a_bucket_shares(served):
    serving, _, _ = served
    scopes = serving.device_scopes()
    short = [b for b in serving.ladder() if b[1] == serving.blocks[0]]
    long = [b for b in serving.ladder() if b[1] != serving.blocks[0]]
    assert len(short) == 6 and long == [(1, 96, 96)]
    # a short bucket is ONE program, a long one a program a layer kind
    assert set(scopes) == (
        {f"jit_seq_turn_b{b}_t{t}_c{c}" for b, t, c in short}
        | {f"jit_seq_{kind}_b{b}_t{t}_c{c}" for kind in ("gqa", "head")
           for b, t, c in long}
        | {f"jit_seq_{kind}_b{b}_t{t}" for kind in ("ssm", "moe")
           for b, t, _ in long})
    want = {"ssm": {"ssm_proj", "ssm_conv", "ssm_scan"},
            "gqa": {"gqa_proj", "gqa_attn"},
            "moe": {"moe_router", "moe_experts", "moe_shared"},
            "head": {"head_topk"}}
    want["turn"] = set().union(*want.values())
    for module, found in scopes.items():
        assert set(found.values()) == want[module.split("_")[2]], module
    assert lm.scopes(serving.cfg) == (
        "ssm_proj", "ssm_conv", "ssm_scan", "gqa_proj", "gqa_attn",
        "moe_router", "moe_experts", "moe_shared", "head_topk")
    # the short buckets hold the turn program and nothing a layer
    assert all(set(serving._exe[b]) == {"turn"} for b in short)
    assert serving._exe[1, 16, 24]["turn"] is not serving._exe[1, 16, 96]["turn"]
    assert set(serving._exe[1, 96, 96]) == {"embed", "S", "A", "E", "head"}
    # a long bucket's context-free kinds are ONE program a (batch, block)
    assert set(serving._shared) == {("S", 1, 96), ("E", 1, 96)}
    assert serving._shared["S", 1, 96] is serving._exe[1, 96, 96]["S"]
    text = serving._exe[1, 96, 96]["S"].as_text()
    assert re.search(r"HloModule jit_seq_ssm_b1_t96\b", text)
    # the tiles' loop is in a trace as a `while` around its own operations
    loops = re.findall(r"^\s*%?([\w.\-]+) = [^\n]* while\(", text, re.M)
    assert loops and not set(loops) & set(scopes["jit_seq_ssm_b1_t96"])


# ---------------------------------------------------------------------------
# the normal path: run_train -> persist -> QueryServer -> POST /queries.json
# ---------------------------------------------------------------------------

def test_train_persist_deploy_query_through_the_query_server(
        tmp_path, monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data import Event
    from incubator_predictionio_tpu.data.storage import App, Storage
    from incubator_predictionio_tpu.data.storage import registry
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.server.query_server import (
        QueryServer,
        ServerConfig,
    )
    from incubator_predictionio_tpu.templates.sequential import (
        SequentialEngine,
    )

    home = str(tmp_path)
    env = {
        "PIO_FS_BASEDIR": home,
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
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    storage = Storage(env)
    # (the DataSource reads through the process's Storage: this one)
    monkeypatch.setattr(registry, "_storage_singleton", storage)
    app_id = storage.get_meta_data_apps().insert(App(0, "pattern-seq"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(2)
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(24):
        start, n = int(rng.integers(0, 40)), int(rng.integers(6, 30))
        for step in range(n):
            events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(start + step * 3) % 40}",
                event_time=t0 + dt.timedelta(seconds=u * 1000 + step)), app_id)
    factory = ("incubator_predictionio_tpu.templates.sequential."
               "SequentialEngine")
    variant = {
        "id": "pattern", "version": "1", "engineFactory": factory,
        "datasource": {"params": {"appName": "pattern-seq", "maxLen": 32}},
        "algorithms": [{"name": "transformer", "params": {
            "appName": "pattern-seq", "maxLen": 32, "dModel": 32, "nHeads": 2,
            "nLayers": 4, "epochs": 3, "batchSize": 16, "seed": 1,
            "attentionKind": "gqa", "layerPattern": "SEAE",
            "numKeyValueHeads": 1, "headDim": 16, "ssmNumHeads": 4,
            "ssmHeadDim": 8, "ssmStateSize": 8, "ssmGroups": 2,
            "ssmChunkSize": 8, "rmsNormEps": 1e-5, "nRoutedExperts": 8,
            "numExpertsPerTok": 2, "moeIntermediateSize": 16,
            "nSharedExperts": 1, "sharedIntermediateSize": 24,
            "expertActivation": "relu2", "routedScalingFactor": 2.5,
            "tieHead": False, "cachePage": 8, "cacheTokens": 512,
            "stateSlots": 5}}],
    }
    path = os.path.join(home, "engine.json")
    with open(path, "w") as f:
        json.dump(variant, f)
    engine = SequentialEngine().apply()
    instance_id = run_train(
        engine, engine.engine_params_from_variant(variant),
        EngineInstance(
            id="", status="INIT", start_time=dt.datetime.now(dt.timezone.utc),
            end_time=None, engine_id="pattern", engine_version="1",
            engine_variant=os.path.abspath(path), engine_factory=factory),
        storage=storage, ctx=MeshContext.create())
    # persisted through the SPI: an orbax checkpoint and a sidecar
    saved = os.path.join(home, "device_models", f"{instance_id}_0")
    assert os.path.exists(os.path.join(saved, "sidecar.pkl"))

    session = [f"i{(5 + 3 * j) % 40}" for j in range(12)]

    async def drive():
        server = QueryServer(
            ServerConfig(engine_variant=path, max_batch=8),
            storage=storage, ctx=MeshContext.create())
        model = server.deployed.models[0]
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            answers = []
            for n in (8, 10, 12, 12):   # a miss, two turns, the list again
                resp = await client.post("/queries.json", json={
                    "user": "visitor", "recent_items": session[:n], "num": 5})
                answers.append(await resp.json())
            cold = await (await client.post("/queries.json", json={
                "recent_items": ["nope"], "num": 3})).json()
            status = await (await client.get("/")).json()
        finally:
            await client.close()
            await server.shutdown()
        return model, answers, cold, status

    model, answers, cold, status = asyncio.run(drive())
    assert isinstance(model, TransformerModel) and model.config.latent
    assert model.config.layer_pattern == "SEAE"
    assert [sorted(lw)[0] for lw in model.params["layers"]] == [
        "a_log", "b_r", "norm1", "b_r"]     # restored by kind, in order
    info = status["servingPaths"][0]
    assert info["path"] == "device-state-kv-cache"
    assert info["state_slots"] == 5
    assert info["state_bytes_per_session"] == (4 * 8 * 8 + 3 * 64) * 4
    assert info["cache_bytes_per_token"] == 128 * 4 + 4
    assert info["buckets"] == [
        "1x16@16:step", "1x16@32:step", "4x16@16:step", "4x16@32:step",
        "8x16@32:step", "1x32@32:scan"]
    for n, body in zip((8, 10, 12, 12), answers):
        tokens = np.asarray([model.item_map[i] for i in session[:n]], np.int32)
        want_s, want_i = masked_reference(model.params, model.config, tokens, 5)
        inv = model.item_map.inverse()
        assert [r["item"] for r in body["itemScores"]] == \
            [inv[int(t)] for t in want_i]
        np.testing.assert_allclose(
            [r["score"] for r in body["itemScores"]], want_s, atol=TOL)
    # the same list again is computed from 0: the same answer
    assert [r["item"] for r in answers[2]["itemScores"]] == \
        [r["item"] for r in answers[3]["itemScores"]]
    assert cold["itemScores"] == []
    storage.close()
