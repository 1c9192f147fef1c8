"""The window / full grouped-query attention pattern with softmax-routed
experts (models/sparse_gqa.py's ``W`` letter beside its ``A`` letter with
yarn-scaled rotary positions, and models/latent_moe.py's experts) held to its
plain reference (benchmarks/reference/window_gqa_moe_ref.py) at a small size
on the CPU, float32 weights: the pattern as ``forward`` runs it, the yarn
angles past the rule's original context, the band a long block attends in,
the chip's share of the experts, and the session cache's serve path (a miss
in pieces, then turns from the ring with sessions under the window, across
it and several turns past the ring's wrap, in batches of sessions of unequal
length, eviction and slot reuse), the benchmark's controls at this size, and
the whole normal path (``run_train`` → orbax persist → ``QueryServer`` →
``POST /queries.json``).

Tolerance: both sides compute in float32 at ``highest`` precision and differ
in the order of sums (ring rows against the whole matrix, grouped against
dense experts); logits of unit scale agree to a few 1e-6, ``TOL`` = 1e-4.
"""

from __future__ import annotations

import asyncio
import dataclasses
import datetime as dt
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import window_gqa_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models import sparse_gqa
from incubator_predictionio_tpu.models import state_space as ssm
from incubator_predictionio_tpu.obs.metrics import REGISTRY, parse_prometheus_text
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.serving.latent_cache import LatentServing
from tests.fixtures import conv_tiny
from tests.fixtures.window_tiny import (
    YARN,
    config,
    masked_reference,
    published,
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

@pytest.mark.parametrize("seed", [0, 3])
def test_pattern_forward_matches_the_reference(sessions, seed):
    cfg = config()
    params = seeded_params(cfg, seed)
    lengths = (96, 40, 9, 1)
    rows = np.zeros((len(lengths), cfg.max_len), np.int32)   # left-padded
    for r, n in enumerate(lengths):
        rows[r, cfg.max_len - n:] = sessions[r, :n]
    h = jax.jit(lambda p, t, q: lm.forward(p, t, q, cfg))(
        params, rows, lm.real_positions(rows))
    got = lm._mm(h, params["head"].T)             # the untied head
    for r, n in enumerate(lengths):
        want = reference_logits(params, cfg, sessions[r, :n])
        assert np.abs(want).max() > 0.3   # logits of unit scale, not zeros
        np.testing.assert_allclose(got[r, -1], want, atol=TOL, rtol=0)


@pytest.mark.parametrize("kind, part", [("W", "sliding_attention"),
                                        ("A", "full_attention")])
def test_each_attention_letter_is_the_references_layer(kind, part):
    """The ``W`` letter (a window of 8 keys, plain angles) and the ``A``
    letter (every key, yarn angles) against the reference's layer over 40
    tokens, five windows long and past the yarn rule's 32 positions; and the
    two letters differ from each other on the same weights."""
    cfg = config()
    lw = seeded_params(cfg)["layers"][0]
    h = jax.random.normal(jax.random.key(2), (1, 40, 64))
    q_index = jnp.arange(40)[None]
    ctx = ssm.block_context(jnp.ones((1, 40), bool), jnp.float32)
    got, _ = ssm.mixer_layer(kind, lw, h, cfg, q_index,
                             jnp.ones((1, 40), bool), ctx)
    pub = published(cfg)
    want = ref.sub_block(h[0], lw, pub, part)
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=0)
    other = ref.sub_block(h[0], lw, pub, {
        "W": "full_attention", "A": "sliding_attention"}[kind])
    assert np.abs(other - want)[10:].max() > 0.05
    # inside the window the two masks agree and only the angles differ
    assert np.abs(other - want)[:8].max() > 1e-3


def test_yarn_angles_past_the_original_context_are_the_references():
    """At the published head size and rule (factor 16 over 8,192 original
    positions): the program's frequencies and amplitude are the reference's,
    the slow pairs are divided by 16 and the fast ones kept, and a vector
    rotated at positions past 8,192 and past 14k comes out the same."""
    scaled = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
              "original_max_position_embeddings": 8192, "beta_fast": 32,
              "beta_slow": 1, "attention_factor": 1.2772588722239782}
    cfg = config(head_dim=128, n_heads=1, n_kv_heads=1, rope_theta=500000.0,
                 rope_parameters=tuple(sorted(scaled.items())))
    f, m = sparse_gqa.rotary_rule(cfg, "A")
    want_f, want_m = ref.yarn(scaled, 128)
    np.testing.assert_allclose(f, want_f, rtol=1e-6)
    assert m == want_m == 1.2772588722239782
    plain = 500000.0 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(f[:12], plain[:12], rtol=1e-6)     # fast: kept
    np.testing.assert_allclose(f[40:], plain[40:] / 16, rtol=1e-6)
    assert sparse_gqa.rotary_rule(cfg, "W") == (None, 1.0)
    # an absent attention_factor is the rule's own 0.1 ln(factor) + 1
    less = {k: v for k, v in scaled.items() if k != "attention_factor"}
    bare = dataclasses.replace(
        cfg, rope_parameters=tuple(sorted(less.items())))
    assert sparse_gqa.rotary_rule(bare, "A")[1] == pytest.approx(
        1.2772588722239782, rel=1e-12)
    x = jax.random.normal(jax.random.key(0), (1, 3, 2, 128))
    pos = jnp.asarray([[100, 9000, 14335]])
    got = sparse_gqa.rope(x, pos, cfg.rope_theta, f, m)
    # (the reference rotates positions 0..T-1: a long zero prefix)
    for j, p in enumerate((100, 9000, 14335)):
        ang = p * want_f
        a, b = x[0, j, :, :64], x[0, j, :, 64:]
        want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1) * want_m
        np.testing.assert_allclose(got[0, j], want, atol=2e-4)
    # ... and the plain rule at the same positions turns the slow pairs 16
    # times further
    assert np.abs(sparse_gqa.rope(x, pos, cfg.rope_theta) * want_m
                  - got)[0, 1:].max() > 0.1


def test_reference_rope_is_the_programs_at_its_own_positions():
    x = jax.random.normal(jax.random.key(1), (40, 2, 16))
    f, m = ref.yarn(YARN, 16)
    want = ref.rope(x, f, m)
    got = sparse_gqa.rope(x[None], jnp.arange(40)[None], 1e4, f, m)[0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert m == pytest.approx(0.1 * np.log(4) + 1)


@pytest.mark.parametrize("chunk", [8, 16])
def test_a_long_block_attends_in_a_band(monkeypatch, chunk):
    """A block of 32 queries after 8 ring rows, in chunks of ``chunk``
    queries that each read the 8 keys before their first and their own (the
    banded form a 2,048-token piece takes at the served size), against the
    same block attended whole."""
    cfg = config()
    q = jax.random.normal(jax.random.key(0), (2, 32, 4, 16))
    rows = jax.random.normal(jax.random.key(1), (2, 40, 64))
    offsets = jnp.asarray([20, 3])
    q_index = offsets[:, None] + jnp.arange(32)[None]
    before = offsets[:, None] - 8 + jnp.arange(8)[None]
    key_pos = jnp.concatenate([jnp.where(before >= 0, before, -1),
                               q_index], 1)
    whole = sparse_gqa.attend_dense(q, rows, q_index, key_pos, cfg,
                                    jnp.float32, 8)
    monkeypatch.setattr(sparse_gqa, "Q_CHUNK", chunk)
    monkeypatch.setattr(sparse_gqa, "CHUNK_SCORES", chunk * 4096)
    band = sparse_gqa.attend_dense(q, rows, q_index, key_pos, cfg,
                                   jnp.float32, 8)
    np.testing.assert_allclose(band, whole, atol=1e-5, rtol=0)
    # the band is what is read: rows outside it may hold anything
    text = jax.jit(lambda *a: sparse_gqa.attend_dense(
        *a, cfg, jnp.float32, 8)).lower(q, rows, q_index, key_pos).as_text()
    assert f"tensor<2x{8 + chunk}x2x16xf32>" in text


def test_a_long_context_is_attended_in_smaller_chunks_of_queries(monkeypatch):
    """Past ``CHUNK_SCORES / Q_CHUNK`` keys the chunk of queries shrinks so
    that a head's scores stay ``CHUNK_SCORES`` elements: the same result."""
    cfg = conv_tiny.config()
    q = jax.random.normal(jax.random.key(0), (1, 64, 4, 16))
    rows = jax.random.normal(jax.random.key(1), (1, 96, 64))
    q_index = 32 + jnp.arange(64)[None]
    valid = jnp.ones((1, 96), bool)
    whole = sparse_gqa.attend_dense(q, rows, q_index, valid, cfg, jnp.float32)
    monkeypatch.setattr(sparse_gqa, "Q_CHUNK", 32)
    monkeypatch.setattr(sparse_gqa, "CHUNK_SCORES", 32 * 48)
    text = jax.jit(lambda *a: sparse_gqa.attend_dense(
        *a, cfg, jnp.float32)).lower(q, rows, q_index, valid).as_text()
    assert "tensor<1x2x2x16x96xf32>" in text      # 16 queries a chunk
    small = sparse_gqa.attend_dense(q, rows, q_index, valid, cfg, jnp.float32)
    np.testing.assert_allclose(small, whole, atol=1e-5, rtol=0)


@pytest.mark.parametrize("batch", [1, 3])
def test_a_full_layer_reads_its_context_a_page_at_a_time(batch):
    """``dense_step`` reads a session's context as whole pages of the table
    (one slice a page: a row at a time the served 16k-row context came at an
    eighth of the memory's rate): the same rows, in the same order, as the
    row-wise read of ``_block_geometry``'s flat rows, for sessions whose
    pages lie scattered and out of order, and the same layer output."""
    cfg = config()
    lw = seeded_params(cfg)["layers"][6]                     # the "A" layer
    page, n_pages = cfg.cache_page, cfg.max_len // cfg.cache_page
    width = sparse_gqa.dense_row_layout(cfg)["kv"]
    rng = np.random.default_rng(batch)
    cache = {"kv": jnp.asarray(rng.normal(size=(40 * page, width)),
                               jnp.float32)}
    pages = jnp.asarray(np.stack([
        rng.permutation(39)[:n_pages] + 1 for _ in range(batch)]), jnp.int32)
    offsets = jnp.asarray([70, 9, 33][:batch], jnp.int32)
    counts = jnp.asarray([16, 5, 1][:batch], jnp.int32)
    h = jnp.asarray(rng.normal(size=(batch, 16, cfg.d_model)), jnp.float32)
    got, kept, _ = sparse_gqa.dense_step(
        lw, cache, (), h, pages, offsets, counts, cfg=cfg, form="step")

    q_index, _, write, read, key_valid = lm._block_geometry(
        pages, offsets, counts, 16, page)

    def by_rows(rows):
        kv = cache["kv"].at[write].set(jnp.pad(
            rows, [(0, 0), (0, 0), (0, width - rows.shape[-1])]))
        return kv[read], key_valid, {"kv": kv}

    want, kept_rows = sparse_gqa.dense_layer(lw, h, cfg, q_index, by_rows)
    np.testing.assert_array_equal(kept["kv"], kept_rows["kv"])
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    text = jax.jit(lambda *a: sparse_gqa.dense_step(
        lw, *a, cfg=cfg, form="step")[0]).lower(
        cache, (), h, pages, offsets, counts).as_text()
    # one slice of [page, width] a table entry, none of [1, width]
    assert f"slice_sizes = array<i64: 1, {page}, {width}>" in text
    assert f"slice_sizes = array<i64: 1, {width}>" not in text


def test_four_shares_add_up_to_the_uncut_reference_layer():
    """Four chips of two experts each (``expert_offset`` 0, 2, 4, 6 here; 0,
    16, 32, 48 at the published size): the routed parts of the four shares,
    with attention and the norms counted ONCE, add up to the reference's
    uncut expert layer; each share alone is the reference's share alone."""
    cfg = config()
    lw = seeded_params(cfg)["layers"][1]
    assert lm.layer_kinds(cfg)[1] == "E" and "b_r" not in lw
    h = jax.random.normal(jax.random.key(4), (1, 96, 64))
    x = lm.rms_norm(h, lw["norm2"], cfg.rms_norm_eps)[0]
    valid = jnp.ones((96,), bool)
    idx, w = lm.moe_router(x, lw, cfg)
    pub = published(cfg)
    want_idx, want_w = ref.route(x, lw, pub)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, atol=1e-6)
    total, unheld = 0.0, 0
    for share in range(4):
        part = dataclasses.replace(cfg, experts_held=2,
                                   expert_offset=2 * share)
        cut = slice(2 * share, 2 * share + 2)
        held = {**lw, **{k: lw[k][cut] for k in ("we1", "we3", "we2")}}
        y, counters = lm.moe_experts(x, idx, w, valid, held, part)
        total, unheld = total + y, unheld + int(counters[2])
        np.testing.assert_allclose(
            y, ref.experts(x, held, published(part)),
            atol=TOL, rtol=0)
    want = ref.experts(x, lw, pub)
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    assert unheld == 96 * 2 * 3   # every pick is held by exactly one share
    got, _ = lm.expert_layer(lw, h, cfg, valid[None])
    np.testing.assert_allclose(
        got[0], ref.sub_block(h[0], lw, pub, "experts"), atol=TOL, rtol=0)


@pytest.mark.parametrize("change, message", [
    (dict(sliding_window=0), "a 'W' layer needs sliding_window >= 1"),
    (dict(attention_rope=False, rope_parameters=()),
     "a 'W' layer needs sliding_window >= 1"),
    (dict(layer_pattern="AEAEAEAE"), "and no other layer takes one"),
    (dict(index_kv_tile=3), "whole pages of 8"),
    (dict(layer_pattern="AEAEAEAE", sliding_window=0, attention_rope=False),
     "rope_parameters scale the rotary angles"),
    (dict(layer_pattern="WEWEWEAX"), "'W' \\(window attention\\)"),
    (dict(layer_pattern="", attention_kind="mla"),
     "sliding_window to its 'W' layers"),
])
def test_config_says_what_the_window_letter_needs(change, message):
    with pytest.raises(ValueError, match=message):
        config(**change)


def test_letter_shapes_scopes_and_ring_layout():
    cfg = config()
    assert lm.layer_kinds(cfg) == tuple("WEWEWEAE")
    assert lm.layer_shapes(cfg, "W") == lm.layer_shapes(cfg, "A")
    assert set(lm.layer_shapes(cfg, "W")) == {"norm1", "w_q", "w_k", "w_v",
                                              "w_o"}
    assert lm.scopes(cfg) == (
        "gqa_proj", "win_attn", "gqa_attn", "moe_router", "moe_experts",
        "moe_shared", "head_topk")
    assert ssm.STATEFUL == ("S", "C", "W")
    assert ssm.state_layout(cfg, "W") == {"ring": ((8, 128), jnp.float32)}
    pub = published(cfg)
    assert pub["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert pub["rope_parameters"]["full_attention"] == YARN
    assert ref.parts(pub) == ["sliding_attention", "experts"] * 3 + [
        "full_attention", "experts"]
    # the accepted patterns' scopes are what they were
    assert lm.scopes(conv_tiny.config())[:5] == (
        "conv_proj", "conv_mix", "ffn_dense", "gqa_proj", "gqa_attn")


# ---------------------------------------------------------------------------
# the session cache: extend == full forward
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = config()
    params = seeded_params(cfg)
    serving = LatentServing(params, cfg)
    assert serving.warmup(4) == 9
    info = serving.info()
    assert info["buckets"] == [
        "1x16@32:step", "1x16@64:step", "1x16@96:step", "4x16@32:step",
        "4x16@64:step", "4x16@96:step", "1x32@32:band", "1x32@64:band",
        "1x32@96:band"]
    assert info["path"] == "device-window-kv-cache"
    assert info["cache_row_widths"] == {"kv": 128}
    # ONE full layer's rows and the token id a token; three window layers'
    # rings of 8 rows a session, whatever its length
    assert info["cache_bytes_per_token"] == 128 * 4 + 4
    assert info["state_bytes_per_session"] == 3 * 8 * 128 * 4
    assert info["state_slots"] == 6 and info["sliding_window"] == 8
    assert serving.cache[0]["ring"].shape == (7, 8, 128)
    assert serving.cache[6]["kv"].shape == (73 * 8, 128)
    yield serving, params, cfg
    serving.close()


def test_a_miss_in_pieces_then_turns_from_the_ring(served, sessions):
    """A 70-token miss runs as pieces of 32, 32 and a 6-token tail in the
    short form (the lock offered between them), then turns of 3, 5 and 16
    tokens read the ring the pieces left, nine windows into the session."""
    serving, params, cfg = served
    before = _dispatched()
    pieces = _counter("pio_seq_prefill_chunks_total")
    short = _counter("pio_seq_launches_total", block="short")
    long = _counter("pio_seq_launches_total", block="long")
    tokens = sessions[0]
    for n in (70, 73, 78, 94):
        assert_answers(serving, params, cfg, [("a", tokens[:n])])
    assert _grew(before) == {"1x32@32": 1, "1x32@64": 1, "1x16@96": 4}
    assert _counter("pio_seq_prefill_chunks_total") - pieces == 2
    # ONE launch a short dispatch of the eight letters; a piece's chain is
    # embed and a launch a letter, no head (its tail answers)
    assert _counter("pio_seq_launches_total", block="short") - short == 4
    assert _counter("pio_seq_launches_total", block="long") - long == 2 * 9


@pytest.mark.parametrize("n", [1, 5, 8])
def test_a_session_under_the_window_is_served_as_a_full_layer_would(
        served, sessions, n):
    """Shorter than the window, a session's ring holds every row it has:
    the ``W`` layers see what ``A`` layers with plain angles would."""
    serving, params, cfg = served
    before = _dispatched()
    assert_answers(serving, params, cfg, [(f"cold{n}", sessions[1, :n])])
    assert _grew(before) == {"1x16@32": 1}
    for more in (1, 3):   # across the window's edge for n = 5 and 8
        assert_answers(serving, params, cfg,
                       [(f"cold{n}", sessions[1, :n + more])])


def test_turns_past_the_rings_wrap_in_batches_of_unequal_lengths(
        served, sessions):
    """Three sessions, one under the window, one across it, one eleven
    windows long, grow in ONE dispatch of four rows, six times: the short
    ones pass the ring's wrap (position p lives at ring row p % 8) several
    times over; the padding row lands in slot 0."""
    serving, params, cfg = served
    lengths = {"b1": 3, "b2": 7, "b3": 58}
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 2, :n]) for i, (k, n) in enumerate(lengths.items())])
    for turn in range(1, 7):
        before = _dispatched()
        assert_answers(serving, params, cfg, [
            (k, sessions[i + 2, :n + turn * g])
            for (i, (k, n)), g in zip(enumerate(lengths.items()), (1, 4, 6))])
        assert _grew(before) == {
            "4x16@64" if 58 + turn * 6 <= 64 else "4x16@96": 1}
    _, ring = serving.session_state("b2", 0)
    assert ring["ring"].shape == (8, 128)
    tokens, _ = serving.session_state("b2", 0)
    assert len(tokens) == 31                       # 7 + 6 x 4: three wraps


def test_the_ring_holds_the_rows_of_a_whole_miss(served, sessions):
    """The ring a miss and three turns left is the ring of one whole miss
    over the same tokens, row for row: position p at ring row p % 8, keys
    rotated at the tokens' positions in the session."""
    serving, params, cfg = served
    tokens = sessions[8, :61]
    for n in (41, 47, 60, 61):
        assert_answers(serving, params, cfg, [("ring", tokens[:n])])
    _, grown = serving.session_state("ring", 0)
    assert_answers(serving, params, cfg, [("ring_whole", tokens)])
    _, whole = serving.session_state("ring_whole", 0)
    np.testing.assert_allclose(grown["ring"], whole["ring"], atol=1e-5)
    # rows 53..60 of the session: row of position 56 at ring row 0
    pub = published(cfg)
    lw = params["layers"][0]
    x = ref.rms_norm(ref.embed(params, tokens), lw["norm1"], cfg.rms_norm_eps)
    f, m = ref.rotary(pub, "sliding_attention")
    k = ref.rope(ref.mm(x, lw["w_k"]).reshape(61, 2, 16), f, m).reshape(61, 32)
    np.testing.assert_allclose(whole["ring"][0, :32], k[56], atol=1e-5)
    np.testing.assert_allclose(whole["ring"][5, :32], k[53], atol=1e-5)


def test_window_counters_count_ring_rows_against_whole_sessions(
        served, sessions):
    serving, params, cfg = served
    assert_answers(serving, params, cfg, [("w1", sessions[9, :50]),
                                          ("w2", sessions[10, :4])])
    held = _counter("pio_seq_window_rows_held_total")
    unwindowed = _counter("pio_seq_window_rows_unwindowed_total")
    assert_answers(serving, params, cfg, [("w1", sessions[9, :53]),
                                          ("w2", sessions[10, :6])])
    # w1 reads its ring's 8 rows and 3 of its own, w2 its 4 and 2
    assert _counter("pio_seq_window_rows_held_total") - held == 11 + 6
    assert _counter("pio_seq_window_rows_unwindowed_total") \
        - unwindowed == 53 + 6


def test_a_diverging_list_restarts_from_zero(served, sessions):
    """The ring stands at one position: a list that diverges inside what is
    cached, and the same list again, are computed from position 0."""
    serving, params, cfg = served
    tokens = sessions[5, :60].copy()
    assert_answers(serving, params, cfg, [("d", tokens[:50])])
    restarts = _counter("pio_seq_state_restarts_total")
    reused = _counter("pio_seq_tokens_reused_total")
    tokens = tokens.copy()      # (the table keeps the array it was given)
    tokens[45] = 7
    assert_answers(serving, params, cfg, [("d", tokens[:55])])
    assert_answers(serving, params, cfg, [("d", tokens[:55])])
    assert _counter("pio_seq_state_restarts_total") - restarts == 2
    assert _counter("pio_seq_tokens_reused_total") == reused
    assert_answers(serving, params, cfg, [("d", tokens[:60])])
    assert _counter("pio_seq_tokens_reused_total") - reused == 55


def test_eviction_frees_pages_and_ring_and_a_reused_slot_sees_nothing_old(
        served, sessions):
    """Six slots: a seventh session evicts the least recently used one and
    takes its slot and pages together; what the ring held is no key of the
    new session's (positions before its offset 0 are none); the evicted
    session comes back as a miss."""
    serving, params, cfg = served
    for i in range(6):
        assert_answers(serving, params, cfg, [(f"e{i}", sessions[i, :60])])
    assert not serving._free_slots
    victim = serving._sessions["e0"]
    slot, pages = victim.slot, list(victim.pages)
    evicted = _counter("pio_seq_state_evictions_total")
    assert _samples("pio_seq_state_slots")[(("state", "used"),)] == 6
    assert np.asarray(serving.cache[0]["ring"][slot]).any()
    free = len(serving._free)
    assert_answers(serving, params, cfg, [("new", sessions[9, :2])])
    assert "e0" not in serving._sessions
    assert serving._sessions["new"].slot == slot
    assert len(serving._free) == free + len(pages) - 1
    assert _counter("pio_seq_state_evictions_total") - evicted == 1
    assert_answers(serving, params, cfg, [("new", sessions[9, :5])])
    reused = _counter("pio_seq_tokens_reused_total")
    assert_answers(serving, params, cfg, [("e0", sessions[0, :62])])
    assert _counter("pio_seq_tokens_reused_total") == reused   # a miss again


def test_programs_scopes_and_what_a_bucket_shares(served):
    serving, _, _ = served
    scopes = serving.device_scopes()
    short = [b for b in serving.ladder() if b[1] == serving.blocks[0]]
    assert set(scopes) == (
        {f"jit_seq_turn_b{b}_t{t}_c{c}" for b, t, c in short}
        | {f"jit_seq_{kind}_b1_t32_c{c}" for kind in ("gqa", "head")
           for c in (32, 64, 96)}
        | {"jit_seq_win_b1_t32", "jit_seq_moe_b1_t32"})
    want = {"win": {"gqa_proj", "win_attn"}, "gqa": {"gqa_proj", "gqa_attn"},
            "moe": {"moe_router", "moe_experts"}, "head": {"head_topk"}}
    want["turn"] = set().union(*want.values())
    for module, found in scopes.items():
        assert set(found.values()) == want[module.split("_")[2]], module
    assert set(serving._exe[1, 32, 64]) == {"embed", "W", "A", "E", "head"}
    assert set(serving._shared) == {("W", 1, 32), ("E", 1, 32)}


# ---------------------------------------------------------------------------
# the benchmark's controls at this size
# ---------------------------------------------------------------------------

def _asked(serving, sessions):
    """Four sessions, each a miss in pieces and three turns of 2 items: the
    answers after the last turn."""
    out = []
    for i in range(4):
        for n in (40, 42, 44, 46):
            scores, items = serving.extend([(f"k{i}", sessions[i, :n])])
        out.append((scores[0], items[0]))
    return out


@pytest.mark.parametrize("control", ["sound", "float8", "no_window",
                                     "no_yarn"])
def test_the_controls_fail_the_tiny_limits(sessions, control):
    """The program with its matrices rounded through float8_e4m3fn, with a
    window as long as the longest session, and with plain angles on the full
    layer, against the reference of the configuration as it stands: each
    leaves the tolerance the sound program keeps."""
    cfg = config()
    params = seeded_params(cfg)
    run, served_cfg = params, cfg
    if control == "float8":
        run = jax.tree.map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim > 1 else a, params)
    if control == "no_window":
        served_cfg = dataclasses.replace(cfg, sliding_window=cfg.max_len)
    if control == "no_yarn":
        served_cfg = dataclasses.replace(cfg, rope_parameters=())
    serving = LatentServing(run, served_cfg)
    serving.batches = (1,)
    for bucket in [(1, 16, 64), (1, 32, 32), (1, 32, 64)]:
        serving._exe[bucket] = serving._compile(*bucket)
    gaps = []
    for i, (scores, items) in enumerate(_asked(serving, sessions)):
        logits = reference_logits(params, cfg, sessions[i, :46])
        gaps.append(np.abs(scores - logits[items]).max())
    serving.close()
    assert (max(gaps) <= TOL) == (control == "sound"), gaps
    if control != "sound":
        assert min(gaps) > 10 * TOL, gaps


# ---------------------------------------------------------------------------
# the normal path: run_train -> persist -> QueryServer -> POST /queries.json
# ---------------------------------------------------------------------------

def test_train_persist_deploy_query_through_the_query_server(
        tmp_path, monkeypatch):
    """``fit`` trains a toy instance of the pattern, orbax persists it, a
    QueryServer restores and warms it, and a session grown over three posts
    past its window is answered from its ring as the reference answers the
    whole list."""
    from aiohttp.test_utils import TestClient, TestServer

    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data import Event
    from incubator_predictionio_tpu.data.storage import App, Storage
    from incubator_predictionio_tpu.data.storage import registry
    from incubator_predictionio_tpu.data.storage.base import EngineInstance
    from incubator_predictionio_tpu.models.transformer import TransformerModel
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
    app_id = storage.get_meta_data_apps().insert(App(0, "win-seq"))
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
        "id": "win", "version": "1", "engineFactory": factory,
        "datasource": {"params": {"appName": "win-seq", "maxLen": 32}},
        "algorithms": [{"name": "transformer", "params": {
            "appName": "win-seq", "maxLen": 32, "dModel": 32, "nHeads": 2,
            "nLayers": 6, "epochs": 3, "batchSize": 16, "seed": 1,
            "attentionKind": "gqa", "layerPattern": "WEWEAE",
            "numKeyValueHeads": 1, "headDim": 16, "attentionRope": True,
            "ropeTheta": 1e4, "slidingWindow": 4, "indexKvTile": 4,
            "ropeParameters": {
                "full_attention": {**YARN,
                                   "original_max_position_embeddings": 8},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 1e4}},
            "rmsNormEps": 1e-6, "routerScoring": "softmax",
            "nRoutedExperts": 8, "numExpertsPerTok": 2,
            "moeIntermediateSize": 16, "nSharedExperts": 0, "tieHead": False,
            "cachePage": 8, "cacheTokens": 512, "stateSlots": 5}}],
    }
    path = os.path.join(home, "engine.json")
    with open(path, "w") as f:
        json.dump(variant, f)
    engine = SequentialEngine().apply()
    instance_id = run_train(
        engine, engine.engine_params_from_variant(variant),
        EngineInstance(
            id="", status="INIT", start_time=dt.datetime.now(dt.timezone.utc),
            end_time=None, engine_id="win", engine_version="1",
            engine_variant=os.path.abspath(path), engine_factory=factory),
        storage=storage, ctx=MeshContext.create())
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
            status = await (await client.get("/")).json()
        finally:
            await client.close()
            await server.shutdown()
        return model, answers, status

    model, answers, status = asyncio.run(drive())
    assert isinstance(model, TransformerModel) and model.config.latent
    assert model.config.layer_pattern == "WEWEAE"
    assert model.config.sliding_window == 4
    assert dict(model.config.rope_parameters)["factor"] == 4
    info = status["servingPaths"][0]
    assert info["path"] == "device-window-kv-cache"
    assert info["state_slots"] == 5 and info["sliding_window"] == 4
    assert info["state_bytes_per_session"] == 2 * 4 * 128 * 4
    assert info["cache_bytes_per_token"] == 128 * 4 + 4
    for n, body in zip((8, 10, 12, 12), answers):
        tokens = np.asarray([model.item_map[i] for i in session[:n]], np.int32)
        want_s, want_i = masked_reference(model.params, model.config, tokens, 5)
        inv = model.item_map.inverse()
        assert [r["item"] for r in body["itemScores"]] == \
            [inv[int(t)] for t in want_i]
        np.testing.assert_allclose(
            [r["score"] for r in body["itemScores"]], want_s, atol=TOL)
    storage.close()
