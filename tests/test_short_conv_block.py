"""The gated-short-convolution / rotary grouped-query / dense + routed-expert
layer pattern (models/short_conv.py's ``C`` and ``D`` letters composed with
models/sparse_gqa.py's plain attention, here with per-head q/k norms and
rotary positions, and models/latent_moe.py's experts) held to its plain
reference (benchmarks/reference/conv_gqa_moe_ref.py) at a small size on the
CPU, float32 weights: the pattern as ``forward`` runs it, the convolution's
carry across blocks and past padding, the chip's share of the experts, and
the session cache's serve path (a miss, then turns from the carry in batches
of 1 and 4, sessions of 1 and 2 tokens, a padded block, eviction and slot
reuse), the two controls of the benchmark's comparison, and the whole normal
path (``run_train`` → orbax persist → ``QueryServer`` → ``POST
/queries.json``).

Tolerance: both sides compute in float32 at ``highest`` precision and differ
in the order of sums (a block's shifted adds against the reference's token by
token scan, grouped against dense experts); logits of unit scale agree to a
few 1e-6, ``TOL`` = 1e-4.
"""

from __future__ import annotations

import asyncio
import dataclasses
import datetime as dt
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import conv_gqa_moe_ref as ref
from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models import short_conv, sparse_gqa
from incubator_predictionio_tpu.models import state_space as ssm
from incubator_predictionio_tpu.obs.metrics import REGISTRY, parse_prometheus_text
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.serving.latent_cache import TOP_K, LatentServing
from tests.fixtures import ssm_tiny
from tests.fixtures.conv_tiny import (
    config,
    masked_reference,
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
    got = lm._mm(h, params["item_emb"].T)         # the tied head
    for r, n in enumerate(lengths):
        want = reference_logits(params, cfg, sessions[r, :n])
        assert np.abs(want).max() > 0.3   # logits of unit scale, not zeros
        np.testing.assert_allclose(got[r, -1], want, atol=TOL, rtol=0)


def _conv_inputs(cfg, b, t, seed=3):
    lw = seeded_params(cfg, seed)["layers"][0]
    h = jax.random.normal(jax.random.key(seed), (b, t, cfg.d_model))
    return lw, h


@pytest.mark.parametrize("cut", [1, 2, 5, 15])
def test_a_block_continues_from_what_the_last_one_carried(cut):
    """A block of 16 in one piece, and in two with the first piece's last two
    inputs carried: the same outputs, the same rows carried on."""
    cfg = config()
    lw, h = _conv_inputs(cfg, 2, 16)
    valid = jnp.ones((2, 16), bool)
    full = jnp.full((2,), 16)
    whole, kept = short_conv.gated_conv(lw, h, cfg, valid, full)
    first, carried = short_conv.gated_conv(
        lw, h[:, :cut], cfg, valid[:, :cut], jnp.full((2,), cut))
    rest, kept2 = short_conv.gated_conv(
        lw, h[:, cut:], cfg, valid[:, cut:], jnp.full((2,), 16 - cut),
        carried)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), whole,
                               atol=1e-6, rtol=0)
    np.testing.assert_allclose(kept2, kept, atol=1e-6, rtol=0)
    assert kept.shape == (2, 2, 64) and np.abs(kept).max() > 0.1


@pytest.mark.parametrize("real", [0, 1, 2, 5, 16])
def test_padding_never_enters_the_carry(real):
    """Of a block of 16 only ``real`` tokens are a session's: the rows
    carried on are the last two REAL inputs (what was carried in where the
    block has fewer), whatever the padding positions hold."""
    cfg = config()
    lw, h = _conv_inputs(cfg, 1, 16)
    before = jax.random.normal(jax.random.key(9), (1, 2, 64))
    counts = jnp.array([real])
    valid = jnp.arange(16)[None] < counts[:, None]
    _, kept = short_conv.gated_conv(lw, h, cfg, valid, counts, before)
    _, want = short_conv.gated_conv(
        lw, h[:, :max(real, 1)], cfg, valid[:, :max(real, 1)], counts, before)
    np.testing.assert_allclose(kept, want, atol=1e-6, rtol=0)
    if real == 0:
        np.testing.assert_array_equal(kept, before)
    if real == 1:
        np.testing.assert_array_equal(kept[:, 0], before[:, 1])
    noisy = h.at[:, real:].set(1e3)
    np.testing.assert_array_equal(
        short_conv.gated_conv(lw, noisy, cfg, valid, counts, before)[1], kept)


def test_the_convolution_is_the_references_token_by_token_one():
    cfg = config()
    lw, h = _conv_inputs(cfg, 1, 24)
    got, _ = short_conv.gated_conv(lw, h, cfg, jnp.ones((1, 24), bool))
    pub = short_conv.published(cfg)
    want = ref.sub_block(h[0], lw, pub, "conv")
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=0)
    # the taps are causal: v_t = w0 u_(t-2) + w1 u_(t-1) + w2 u_t
    x = ref.rms_norm(h[0], lw["norm1"], cfg.rms_norm_eps)
    u, gate = ref.conv_inputs(x, lw)
    v3 = lw["conv_w"][0] * u[1] + lw["conv_w"][1] * u[2] \
        + lw["conv_w"][2] * u[3]
    np.testing.assert_allclose(
        want[3] - h[0, 3], ref.mm(gate[3] * v3, lw["w_out"]), atol=1e-5)


def test_attention_letter_norms_each_head_and_rotates_by_position():
    """The ``A`` letter with ``qk_norm`` and ``attention_rope`` against the
    reference's attention, and positions matter: the same block asked at
    another offset keeps other keys."""
    cfg = config()
    lw = seeded_params(cfg)["layers"][4]
    assert lm.layer_kinds(cfg)[4] == "A" and "norm_qh" in lw
    h = jax.random.normal(jax.random.key(2), (1, 24, 64))
    q_index = jnp.arange(24)[None]
    ctx = ssm.block_context(jnp.ones((1, 24), bool), jnp.float32)
    got, _ = sparse_gqa.dense_layer(lw, h, cfg, q_index, ctx)
    want = ref.sub_block(h[0], lw, short_conv.published(cfg),
                         "full_attention")
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=0)
    kept = []
    for pos in (q_index, q_index + 7):
        sparse_gqa.dense_layer(
            lw, h, cfg, q_index,
            lambda rows: kept.append(rows) or ctx(rows), pos)
    width = cfg.n_kv_heads * cfg.head_dim
    assert np.abs(kept[0] - kept[1])[..., :width].max() > 0.1     # the keys
    np.testing.assert_array_equal(kept[0][..., width:], kept[1][..., width:])
    # a plain pattern's attention is what it was: no norm, no rotation
    plain = ssm_tiny.config()
    assert set(sparse_gqa.dense_shapes(plain)) == {"w_q", "w_k", "w_v", "w_o"}
    assert set(sparse_gqa.dense_shapes(cfg)) == {
        "norm_qh", "norm_kh", "w_q", "w_k", "w_v", "w_o"}


def test_router_has_a_selection_bias_and_no_shared_expert():
    cfg = config()
    lw = seeded_params(cfg)["layers"][5]
    assert "ws1" not in lw and "b_r" in lw and lw["we3"].shape == (8, 64, 32)
    x = jax.random.normal(jax.random.key(1), (40, 64))
    idx, w = lm.moe_router(x, lw, cfg)
    want_idx, want_w = ref.route(x, lw, short_conv.published(cfg))
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)   # scaling 1
    flat = dataclasses.replace(cfg)   # the bias moves picks, not weights
    unbiased, _ = lm.moe_router(x, {**lw, "b_r": jnp.zeros(8)}, flat)
    assert (np.sort(idx, -1) != np.sort(unbiased, -1)).any()


def test_two_shares_add_up_to_the_uncut_reference_layer():
    """Two chips of four experts each: the routed parts of the two shares
    (``expert_offset`` 0 and 4 here, 0 and 16 at the published size), with
    the convolution, attention, dense layers and norms counted ONCE, add up
    to the reference's uncut expert layer; each share alone is the
    reference's share alone."""
    cfg = config()
    lw = seeded_params(cfg)["layers"][3 + 2]
    assert lm.layer_kinds(cfg)[5] == "E"
    h = jax.random.normal(jax.random.key(4), (1, 96, 64))
    x = lm.rms_norm(h, lw["norm2"], cfg.rms_norm_eps)[0]
    valid = jnp.ones((96,), bool)
    idx, w = lm.moe_router(x, lw, cfg)
    total, unheld = 0.0, 0
    for share in range(2):
        part = dataclasses.replace(cfg, experts_held=4,
                                   expert_offset=4 * share)
        cut = slice(4 * share, 4 * share + 4)
        held = {**lw, **{k: lw[k][cut] for k in ("we1", "we3", "we2")}}
        y, counters = lm.moe_experts(x, idx, w, valid, held, part)
        total, unheld = total + y, unheld + int(counters[4])
        np.testing.assert_allclose(
            y, ref.experts(x, held, short_conv.published(part)),
            atol=TOL, rtol=0)
    want = ref.experts(x, lw, short_conv.published(cfg))
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    assert unheld == 96 * 2   # every pick is held by exactly one share
    # and the layer around it: the residual and the norm once
    got, _ = lm.expert_layer(lw, h, cfg, valid[None])
    np.testing.assert_allclose(got[0], h[0] + want, atol=TOL, rtol=0)
    np.testing.assert_allclose(
        got[0], ref.sub_block(h[0], lw, short_conv.published(cfg), "experts"),
        atol=TOL, rtol=0)


@pytest.mark.parametrize("change, message", [
    (dict(layer_pattern="CDCDAECX"), "one of 'S'"),
    (dict(layer_pattern="CDCDAE"), "n_layers=8 of them"),
    (dict(conv_kernel=1), "a 'C' layer needs conv_kernel >= 2"),
    (dict(intermediate_size=0), "a 'D' layer needs an intermediate_size"),
    (dict(head_dim=15), "an even head_dim"),
    (dict(layer_pattern="", attention_kind="mla", rope_parameters=(("a", 1),)),
     "belong to the 'A' and 'D' layers of a layer_pattern"),
    (dict(n_kv_heads=3), "n_kv_heads dividing n_heads"),
])
def test_config_says_what_the_new_letters_need(change, message):
    with pytest.raises(ValueError, match=message):
        config(**change)


def test_letters_shapes_scopes_and_state_layouts():
    cfg = config()
    assert lm.layer_kinds(cfg) == tuple("CDCDAECE")
    assert set(lm.layer_shapes(cfg, "C")) == {"norm1", "w_in", "conv_w",
                                              "w_out"}
    assert lm.layer_shapes(cfg, "C")["w_in"][0] == (64, 192)
    assert lm.layer_shapes(cfg, "C")["conv_w"] == ((3, 64), True)
    assert lm.layer_shapes(cfg, "D")["w1"][0] == (64, 96)
    assert lm.scopes(cfg) == (
        "conv_proj", "conv_mix", "ffn_dense", "gqa_proj", "gqa_attn",
        "moe_router", "moe_experts", "moe_shared", "head_topk")
    # a slot is sized from the kind's own layout: the carry alone here, the
    # recurrent state and its convolution's rows for a state-space layer
    assert ssm.state_layout(cfg, "C") == {"conv": (2 * 64, jnp.float32)}
    other = ssm_tiny.config()
    assert set(ssm.state_layout(other)) == {"state", "conv"}
    assert lm.scopes(other)[:5] == (
        "ssm_proj", "ssm_conv", "ssm_scan", "gqa_proj", "gqa_attn")
    pub = short_conv.published(cfg)
    assert pub["layer_types"] == ["conv", "conv", "full_attention", "conv"]
    assert pub["num_dense_layers"] == 2 and pub["conv_L_cache"] == 3
    assert ref.parts(pub) == [("conv", "dense"), ("conv", "dense"),
                              ("full_attention", "experts"),
                              ("conv", "experts")]


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
    # one attention layer's rows and the token id; three convolutions' carries
    assert info["cache_bytes_per_token"] == 128 * 4 + 4
    assert info["state_bytes_per_session"] == 3 * 2 * 64 * 4
    assert info["state_slots"] == 6 and info["layer_pattern"] == "CDCDAECE"
    yield serving, params, cfg
    serving.close()


def test_a_miss_then_turns_from_the_carry(served, sessions):
    """A 70-token miss in the long form, then turns of 3, 5 and 16 tokens
    from the carry it left; the slot counters count the carry's slots as
    they count a recurrent state's."""
    serving, params, cfg = served
    before = _dispatched()
    scan = _counter("pio_seq_state_tokens_total", form="scan")
    step = _counter("pio_seq_state_tokens_total", form="step")
    alone = _counter("pio_seq_state_step_sessions_total")
    short = _counter("pio_seq_launches_total", block="short")
    long = _counter("pio_seq_launches_total", block="long")
    tokens = sessions[0]
    for n in (70, 73, 78, 94):
        assert_answers(serving, params, cfg, [("a", tokens[:n])])
    assert _grew(before) == {"1x96@96": 1, "1x16@96": 3}
    assert _counter("pio_seq_state_tokens_total", form="scan") - scan == 70
    assert _counter("pio_seq_state_tokens_total", form="step") - step == 24
    assert _counter("pio_seq_state_step_sessions_total") - alone == 3
    # ONE launch a short dispatch of this pattern's eight letters; a long
    # block's chain is embed, a launch a letter and the head
    assert _counter("pio_seq_launches_total", block="short") - short == 3
    assert _counter("pio_seq_launches_total", block="long") - long == 8 + 2


@pytest.mark.parametrize("n", [1, 2, 3, 11])
def test_a_cold_session_of_a_few_items_runs_in_the_short_form(
        served, sessions, n):
    """Sessions of 1 and 2 tokens carry zero rows in front of their own."""
    serving, params, cfg = served
    before = _dispatched()
    assert_answers(serving, params, cfg, [(f"cold{n}", sessions[1, :n])])
    assert _grew(before) == {"1x16@24": 1}
    _, kept = serving.session_state(f"cold{n}", 0)
    rows = kept["conv"].reshape(2, 64)
    assert not rows[:max(2 - n, 0)].any() and rows[-1].any()
    for more in (1, 2):
        assert_answers(serving, params, cfg,
                       [(f"cold{n}", sessions[1, :n + more])])


def test_a_batch_of_mixed_lengths_with_a_padding_row(served, sessions):
    """Three sessions of different lengths grow by 1, 4 and 9 items in ONE
    dispatch of four rows: the padding row lands in slot 0 and page 0 and
    leaves slot 0's carry zeros."""
    serving, params, cfg = served
    lengths = {"b1": 20, "b2": 40, "b3": 33}
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 2, :n]) for i, (k, n) in enumerate(lengths.items())])
    before = _dispatched()
    assert_answers(serving, params, cfg, [
        (k, sessions[i + 2, :n + g])
        for (i, (k, n)), g in zip(enumerate(lengths.items()), (1, 4, 9))])
    assert _grew(before) == {"4x16@48": 1}     # 21, 44 and 42 items
    assert not np.asarray(serving.cache[0]["conv"][0]).any()
    # four at once, two of them sessions of 1 and 2 tokens
    before = _dispatched()
    assert_answers(serving, params, cfg, [
        ("b1", sessions[2, :22]), ("tiny1", sessions[6, :1]),
        ("tiny2", sessions[7, :2]), ("b3", sessions[4, :45])])
    assert _grew(before) == {"4x16@48": 1}


def test_rotary_positions_continue_across_turns(served, sessions):
    """The key/value rows a miss and two turns left are the rows of one
    whole miss over the same tokens: a turn's keys are rotated at the
    tokens' indices in the session, not in the block."""
    serving, params, cfg = served
    tokens = sessions[8, :60]
    for n in (41, 47, 60):
        assert_answers(serving, params, cfg, [("rope", tokens[:n])])
    pages = list(serving._sessions["rope"].pages)
    grown = np.concatenate([np.asarray(serving.cache[4]["kv"])[
        p * 8:(p + 1) * 8] for p in pages])[:60]
    assert_answers(serving, params, cfg, [("rope_whole", tokens)])
    pages = list(serving._sessions["rope_whole"].pages)
    whole = np.concatenate([np.asarray(serving.cache[4]["kv"])[
        p * 8:(p + 1) * 8] for p in pages])[:60]
    np.testing.assert_allclose(grown, whole, atol=1e-5, rtol=0)
    # ... and those keys do turn with the position
    assert np.abs(whole[41, :32] - whole[0, :32]).max() > 0.1


def test_the_carry_a_session_holds_is_the_references(served, sessions):
    """A miss and two turns, then the first layer's slot read back: the
    tokens the carry stands at and the reference's last two inputs."""
    serving, params, cfg = served
    for n in (40, 43, 51):
        assert_answers(serving, params, cfg, [("st", sessions[11, :n])])
    tokens, kept = serving.session_state("st", 0)
    np.testing.assert_array_equal(tokens, sessions[11, :51])
    padded = np.ones(cfg.max_len, np.int32)
    padded[:51] = tokens
    want = ref.first_carry(params, params["layers"][0], padded, 51,
                           short_conv.published(cfg), None)
    np.testing.assert_allclose(kept["conv"].reshape(want.shape), want,
                               atol=1e-6, rtol=0)
    assert np.abs(want).max() > 10 * TOL
    assert serving.session_state("nobody", 0) is None


def test_eviction_frees_pages_and_slot_and_a_reused_slot_starts_from_zeros(
        served, sessions):
    """Six slots: a seventh session evicts the least recently used one and
    takes its slot, whose old carry it must not see; a turn after the
    eviction, on the reused slot, and the evicted session coming back as a
    miss all give the reference's answer."""
    serving, params, cfg = served
    for i in range(6):
        assert_answers(serving, params, cfg, [(f"e{i}", sessions[i, :60])])
    assert not serving._free_slots
    victim = serving._sessions["e0"].slot
    evicted = _counter("pio_seq_state_evictions_total")
    assert _samples("pio_seq_state_slots")[(("state", "used"),)] == 6
    assert _samples("pio_seq_state_slots")[(("state", "capacity"),)] == 6
    assert np.asarray(serving.cache[0]["conv"][victim]).any()
    assert_answers(serving, params, cfg, [("new", sessions[9, :1])])
    assert "e0" not in serving._sessions
    assert serving._sessions["new"].slot == victim
    assert _counter("pio_seq_state_evictions_total") - evicted == 1
    assert_answers(serving, params, cfg, [("new", sessions[9, :3])])
    reused = _counter("pio_seq_tokens_reused_total")
    assert_answers(serving, params, cfg, [("e0", sessions[0, :62])])
    assert _counter("pio_seq_tokens_reused_total") == reused   # a miss again
    restarts = _counter("pio_seq_state_restarts_total")
    assert_answers(serving, params, cfg, [("e0", sessions[0, :62])])
    assert _counter("pio_seq_state_restarts_total") - restarts == 1


def test_programs_scopes_and_what_a_bucket_shares(served):
    serving, _, _ = served
    scopes = serving.device_scopes()
    short = [b for b in serving.ladder() if b[1] == serving.blocks[0]]
    assert set(scopes) == (
        {f"jit_seq_turn_b{b}_t{t}_c{c}" for b, t, c in short}
        | {"jit_seq_gqa_b1_t96_c96", "jit_seq_head_b1_t96_c96",
           "jit_seq_conv_b1_t96", "jit_seq_ffn_b1_t96", "jit_seq_moe_b1_t96"})
    want = {"conv": {"conv_proj", "conv_mix"}, "ffn": {"ffn_dense"},
            "gqa": {"gqa_proj", "gqa_attn"},
            "moe": {"moe_router", "moe_experts"}, "head": {"head_topk"}}
    want["turn"] = set().union(*want.values())
    for module, found in scopes.items():
        assert set(found.values()) == want[module.split("_")[2]], module
    assert set(serving._exe[1, 96, 96]) == {"embed", "C", "D", "A", "E",
                                            "head"}
    assert set(serving._shared) == {("C", 1, 96), ("D", 1, 96), ("E", 1, 96)}


# ---------------------------------------------------------------------------
# the benchmark's two controls at this size
# ---------------------------------------------------------------------------

def _asked(serving, sessions):
    """Four sessions, each a miss and three turns of 2 items: the answers
    after the last turn."""
    out = []
    for i in range(4):
        for n in (30, 32, 34, 36):
            scores, items = serving.extend([(f"k{i}", sessions[i, :n])])
        out.append((scores[0], items[0]))
    return out


@pytest.mark.parametrize("control", ["sound", "float8", "zero_carry"])
def test_the_controls_fail_the_tiny_limits(sessions, control, monkeypatch):
    """The program with its matrices rounded through float8_e4m3fn, and the
    program that starts every turn from a zero carry, against the reference
    of the configuration as it stands: both leave the tolerance the sound
    program keeps."""
    cfg = config()
    params = seeded_params(cfg)
    run = params
    if control == "float8":
        run = jax.tree.map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim > 1 else a, params)
    if control == "zero_carry":
        monkeypatch.setattr(
            short_conv, "slot_rows", lambda kept, slots: jnp.zeros(
                (slots.shape[0], kept.shape[1]), kept.dtype))
    serving = LatentServing(run, cfg)
    serving.batches = (1,)
    # (a function and a jit of its own: a step the other tests traced is
    # found again by its identity, with the slot read it had then)
    monkeypatch.setattr(
        "incubator_predictionio_tpu.serving.latent_cache._traced_once",
        lambda step: jax.jit(lambda *a, **k: step(*a, **k),
                             static_argnames=("cfg", "form")))
    for bucket in [(1, 16, 48), (1, 96, 96)]:
        serving._exe[bucket] = serving._compile(*bucket)
    gaps = []
    for i, (scores, items) in enumerate(_asked(serving, sessions)):
        logits = reference_logits(params, cfg, sessions[i, :36])
        gaps.append(np.abs(scores - logits[items]).max())
    serving.close()
    assert (max(gaps) <= TOL) == (control == "sound"), gaps
    if control != "sound":
        assert min(gaps) > 10 * TOL, gaps


# ---------------------------------------------------------------------------
# what the accepted configurations' programs are
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("program, digest", [
    ("turn",
     "1042fb88302298b2d9ba657f0ee3a023b8d4f4438f6f90728b00f2c99dc84196"),
    ("S", "7c692e4bbcc4947bac8a6736e0e035a8bdcef818bbe20d26bbf55e486852e063"),
    ("A", "c2c9c6a83ca329440eda418c6d5c47359afe430232d3372f06d3d80eb13b64a7"),
    ("E", "76452e00dde4284c3a833773dfd6549bded34c0560bb1887730b62e5f35c5e80"),
])
def test_the_state_space_pattern_lowers_to_the_parents_programs(program,
                                                                digest):
    """ISSUE 38 gave the pattern two more letters, the ``A`` letter two
    options and the session table a slot sized by kind, and had to leave the
    accepted pattern's programs alone: the lowered text of the state-space
    pattern's ``S`` and ``E`` long-block layer programs is commit 4a4fc15's
    (the latent and the sparse-index block's digests are pinned in their own
    test files). The ``A`` layer program and the turn program that holds it
    are PR 46's: ``dense_step`` reads a session's context a page at a time
    (one slice a page of the table, not one a row), a deliberate change to
    every pattern's ``A`` layers; nothing else of them moved
    (``test_window_block.py`` holds the read to the row-wise one's values)."""
    cfg = ssm_tiny.config()
    serving = LatentServing(ssm_tiny.seeded_params(cfg), cfg)
    serving.batches = (1, 4)
    text = serving._lower_turn(4, 16, 48, TOP_K).as_text() \
        if program == "turn" else serving._lower(1, 96, 96)[program].as_text()
    serving.close()
    assert hashlib.sha256(text.encode()).hexdigest() == digest, (
        f"the digest was taken under jax 0.9.0 and this is jax "
        f"{jax.__version__}: after a JAX upgrade, or a deliberate change to "
        f"the pattern, pin the new digest")


# ---------------------------------------------------------------------------
# the normal path: run_train -> persist -> QueryServer -> POST /queries.json
# ---------------------------------------------------------------------------

def test_train_persist_deploy_query_through_the_query_server(
        tmp_path, monkeypatch):
    """``fit`` trains a toy instance of the pattern, orbax persists it, a
    QueryServer restores and warms it, and a session grown over three posts
    is answered from its carry as the reference answers the whole list."""
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
    app_id = storage.get_meta_data_apps().insert(App(0, "conv-seq"))
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
        "id": "conv", "version": "1", "engineFactory": factory,
        "datasource": {"params": {"appName": "conv-seq", "maxLen": 32}},
        "algorithms": [{"name": "transformer", "params": {
            "appName": "conv-seq", "maxLen": 32, "dModel": 32, "nHeads": 2,
            "nLayers": 6, "epochs": 3, "batchSize": 16, "seed": 1,
            "attentionKind": "gqa", "layerPattern": "CDAECE",
            "numKeyValueHeads": 1, "headDim": 16, "qkNorm": True,
            "attentionRope": True, "ropeTheta": 1e6, "convKernel": 3,
            "intermediateSize": 48, "rmsNormEps": 1e-5, "nRoutedExperts": 8,
            "numExpertsPerTok": 2, "moeIntermediateSize": 16,
            "nSharedExperts": 0, "cachePage": 8, "cacheTokens": 512,
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
            end_time=None, engine_id="conv", engine_version="1",
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
    assert model.config.layer_pattern == "CDAECE"
    assert model.config.qk_norm and model.config.attention_rope
    assert [sorted(lw)[0] for lw in model.params["layers"]] == [
        "conv_w", "norm1", "norm1", "b_r", "conv_w", "b_r"]
    assert "norm_qh" in model.params["layers"][2]
    info = status["servingPaths"][0]
    assert info["path"] == "device-state-kv-cache"
    assert info["state_slots"] == 5
    assert info["state_bytes_per_session"] == 2 * 2 * 32 * 4
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
