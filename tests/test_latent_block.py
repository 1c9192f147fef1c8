"""The latent-attention / routed-expert block (models/latent_moe.py) held to
its plain reference (models/reference/mla_moe.py) at a small size with the
published ratios, on the CPU, float32 weights: the block as ``fit`` trains
it, the chip's share of the experts, the latent cache's extend path in both
attention forms and every bucket, and the whole normal path
(``run_train`` → persist → ``QueryServer`` → ``POST /queries.json``).

Tolerances: both sides compute in float32 at ``highest`` precision and differ
only in the order of sums (grouped against dense experts, absorbed against
up-projected attention, chunked softmax): logits agree to a few 1e-6 at a
scale of 0.5. ``TOL`` = 5e-5 leaves a decade of room and is two decades under
what a bfloat16 accumulation (1e-2) or one dropped expert pick (1e-1) costs,
which the last test of the section shows.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import datetime as dt
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.models.reference import mla_moe as ref
from incubator_predictionio_tpu.models.transformer import (
    TransformerConfig,
    TransformerModel,
    TransformerRecommender,
    _jit_init_fn,
)
from incubator_predictionio_tpu.obs import trace
from incubator_predictionio_tpu.obs.metrics import REGISTRY, parse_prometheus_text
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.serving.latent_cache import (
    TOP_K,
    LatentServing,
)

TOL = 5e-5
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}


def config(**over) -> TransformerConfig:
    base = dict(
        vocab_size=512, max_len=256, d_model=64, n_heads=4, n_layers=3,
        attention_kind="mla", q_lora_rank=16, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        rope_parameters=tuple(sorted(ROPE.items())), n_routed_experts=16,
        experts_per_token=4, moe_intermediate_size=32, n_shared_experts=1,
        tie_head=False, cache_page=16, cache_tokens=6 * 256)
    base.update(over)
    return TransformerConfig(**base)


def seeded_params(cfg: TransformerConfig, seed: int = 0) -> dict:
    """Random weights with a small nonzero router bias (so that the
    selection-only path runs)."""
    params = lm.init_params(jax.random.key(seed), cfg)
    for i, lw in enumerate(params["layers"]):
        lw["b_r"] = 0.05 * jax.random.normal(
            jax.random.key(1000 + i), lw["b_r"].shape)
    return params


_REFERENCE: dict = {}


def reference_logits(params, cfg, tokens):
    """The reference's logits after the last of ``tokens``. The reference is
    causal, so one jitted full forward over the session padded to ``max_len``
    serves every length (eagerly it compiles each operation per length)."""
    key = (id(params), cfg)
    if key not in _REFERENCE:
        pub = lm.published(cfg)
        _REFERENCE[key] = jax.jit(lambda p, t: ref.forward(p, t, pub))
    padded = np.ones(cfg.max_len, np.int32)
    padded[:len(tokens)] = tokens
    return np.array(_REFERENCE[key](params, padded)[len(tokens) - 1])


def masked_reference(params, cfg, tokens, k=TOP_K):
    """The reference's answer to one session: top-k of the last position's
    logits with padding and the session's own items masked."""
    logits = reference_logits(params, cfg, tokens)
    logits[0] = -np.inf
    logits[np.asarray(tokens)] = -np.inf
    top = np.argsort(-logits, kind="stable")[:k]
    return logits[top], top


def assert_answers(serving, params, cfg, requests, tol=TOL):
    scores, items = serving.extend(requests)
    for (_, tokens), s, i in zip(requests, scores, items):
        want_s, want_i = masked_reference(params, cfg, tokens)
        np.testing.assert_array_equal(i, want_i)
        np.testing.assert_allclose(s, want_s, atol=tol, rtol=0)


@pytest.fixture(scope="module")
def sessions():
    rng = np.random.default_rng(5)
    return rng.integers(1, 512, (8, 256)).astype(np.int32)


# ---------------------------------------------------------------------------
# the equations
# ---------------------------------------------------------------------------

def test_yarn_frequencies_and_softmax_scale_by_hand():
    """rope dim 64, theta 10000, original context 8192: a pair turns 32
    times at dim 12.88 and once at 24.92, so pairs 0-12 keep their frequency,
    pairs 25-31 are divided by the factor 128 and 13-24 blend linearly."""
    inv = ref.yarn_inv_freq(ROPE, 64)
    base = 10000.0 ** (-np.arange(32) / 32.0)
    assert math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(10000))) == 12
    assert math.ceil(64 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(10000))) == 25
    np.testing.assert_allclose(inv[:13], base[:13], rtol=1e-6)
    np.testing.assert_allclose(inv[25:], base[25:] / 128, rtol=1e-6)
    ramp = (18 - 12) / 13
    np.testing.assert_allclose(
        inv[18], base[18] * ((1 - ramp) + ramp / 128), rtol=1e-6)
    # scale = 128^-0.5 * m^2, m = 0.1 * 1 * ln(128) + 1 = 1.48520
    m = 0.1 * math.log(128) + 1
    assert m == pytest.approx(1.48520, abs=1e-5)
    cfg = lm.published(config(qk_nope_head_dim=64, qk_rope_head_dim=64))
    assert ref.softmax_scale(cfg) == pytest.approx(0.194969, abs=1e-6)
    assert ref.rope_amplitude(ROPE) == 1.0
    # the long-context query factor is the identity below position 8192
    pos = jnp.asarray([0, 4095, 8191, 8192, 20000])
    np.testing.assert_allclose(
        ref.query_scaling(cfg, pos),
        [1, 1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(3)], rtol=1e-6)


def test_block_forward_and_logits_match_the_reference(sessions):
    cfg = config()
    params = seeded_params(cfg)
    row = np.zeros((2, 256), np.int32)
    row[0, -100:], row[1, -256:] = sessions[0, :100], sessions[1]
    h = lm.forward(params, jnp.asarray(row),
                   jnp.asarray(lm.real_positions(row)), cfg)
    full = jax.jit(lambda p, t: ref.forward(p, t, lm.published(cfg)))
    for r, n in ((0, 100), (1, 256)):
        got = lm._mm(h[r, -n:], params["head"].T)
        padded = np.ones(256, np.int32)
        padded[:n] = row[r, -n:]
        np.testing.assert_allclose(got, full(params, padded)[:n], atol=TOL,
                                   rtol=0)


def test_fit_loss_and_gradients_match_the_reference(sessions):
    """One epoch over one batch: ``fit`` reports the loss of its initial
    parameters, which the reference computes from the same parameters; the
    gradients of the two losses agree leaf by leaf."""
    cfg = config(max_len=32, cache_page=16, n_layers=2, epochs=1,
                 batch_size=8, learning_rate=1e-3, seed=3, n_routed_experts=8,
                 experts_per_token=2)
    rows = np.zeros((4, 33), np.int32)
    for i, n in enumerate((33, 20, 2, 9)):
        rows[i, -n:] = sessions[i, :n]
    model = TransformerRecommender(cfg).fit(MeshContext.create(), rows, None)
    init = _jit_init_fn(dataclasses.replace(cfg, seed=0))(
        jax.random.key(cfg.seed))
    pub = lm.published(cfg)
    ref_loss = jax.jit(lambda p: ref.loss(p, rows, pub))
    assert model.final_loss == pytest.approx(float(ref_loss(init)), abs=TOL)
    assert all(isinstance(a, jax.Array)
               for a in jax.tree.leaves(model.params))  # never left the device

    tokens, targets = rows[:, :-1], rows[:, 1:]
    weights = ((targets != 0) & (tokens != 0)).astype(np.float32)

    def program_loss(p):
        h = lm.forward(p, jnp.asarray(tokens),
                       jnp.asarray(lm.real_positions(tokens)), cfg)
        return lm.xent_sum(
            h.reshape(-1, cfg.d_model), p["head"],
            jnp.asarray(targets).reshape(-1),
            jnp.asarray(weights).reshape(-1)) / weights.sum()

    got = jax.jit(jax.grad(program_loss))(init)
    want = jax.jit(jax.grad(lambda p: ref.loss(p, rows, pub)))(init)
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = want
        for key in path:
            w = w[key.key if hasattr(key, "key") else key.idx]
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-3,
                                   err_msg=jax.tree_util.keystr(path))


def _latent_kind():
    cfg = config()
    return (cfg, seeded_params(cfg)["layers"][0], ref.experts, lm.published,
            lambda x, lw: lm.moe_shared(x, lw))


def _sparse_kind():
    """The sparse-index block's expert half: the same ``moe_experts``, a
    softmax router, no shared expert."""
    from benchmarks.reference import gqa_sparse_moe_ref
    from incubator_predictionio_tpu.models import sparse_gqa
    from tests.fixtures import sparse_tiny

    cfg = sparse_tiny.config(n_routed_experts=16, experts_per_token=4)
    return (cfg, sparse_tiny.seeded_params(cfg)["layers"][0],
            gqa_sparse_moe_ref.experts, sparse_gqa.published,
            lambda x, lw: 0.0)


@pytest.mark.parametrize("kind", [_latent_kind, _sparse_kind],
                         ids=["mla", "gqa_sparse"])
def test_four_shares_add_up_to_the_uncut_layer(sessions, kind):
    """Four chips of four experts each: the routed parts of the four shares
    plus what every chip computes alike (ONE shared expert, where the block
    has one) are the uncut reference's expert layer."""
    cfg, lw, experts, published, shared = kind()
    x = jax.random.normal(jax.random.key(7), (96, cfg.d_model))
    want = experts(x, lw, published(cfg))
    valid = jnp.ones(96, bool)
    total = shared(x, lw)
    unheld = 0
    for share in range(4):
        part = dataclasses.replace(cfg, experts_held=4, expert_offset=4 * share)
        mine = {**lw, **{k: lw[k][4 * share:4 * share + 4]
                         for k in ("we1", "we3", "we2")}}
        idx, w = lm.moe_router(x, mine, part)
        y, counters = lm.moe_experts(x, idx, w, valid, mine, part)
        total = total + y
        unheld += int(counters[4])
        # the share alone is the reference's share alone
        np.testing.assert_allclose(
            y + shared(x, lw), experts(x, mine, published(part)),
            atol=TOL, rtol=0)
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    assert unheld == 3 * 96 * 4  # every pick is held by exactly one share


def test_a_token_with_no_held_pick_adds_only_the_shared_expert(sessions):
    cfg = config(experts_held=2, expert_offset=14)
    lw = seeded_params(cfg)["layers"][1]
    lw = {**lw, **{k: lw[k][:2] for k in ("we1", "we3", "we2")}}
    x = jax.random.normal(jax.random.key(8), (64, cfg.d_model))
    idx, w = lm.moe_router(x, lw, cfg)
    nowhere = np.flatnonzero((np.asarray(idx) < 14).all(-1))
    assert len(nowhere) > 8
    y, counters = lm.moe_experts(x, idx, w, jnp.ones(64, bool), lw, cfg)
    assert not np.asarray(y)[nowhere].any()
    np.testing.assert_allclose(
        y + lm.moe_shared(x, lw), ref.experts(x, lw, lm.published(cfg)),
        atol=TOL, rtol=0)
    assert int(counters[:2].sum() + counters[2]) == 64 * 4


def test_no_token_is_dropped_at_a_load_eight_times_uneven():
    """The selection bias sends every token to expert 3 of 64: it takes a
    quarter of all picks, sixteen times an even share, and every one is
    computed."""
    cfg = config(n_routed_experts=64)
    lw = dict(seeded_params(cfg)["layers"][0])
    lw["b_r"] = lw["b_r"].at[3].set(10.0)
    x = jax.random.normal(jax.random.key(9), (200, cfg.d_model))
    idx, w = lm.moe_router(x, lw, cfg)
    y, counters = lm.moe_experts(x, idx, w, jnp.ones(200, bool), lw, cfg)
    loads = np.asarray(counters[:64])
    assert loads[3] == 200 and loads.sum() == 800
    assert loads[3] >= 8 * np.delete(loads, 3).mean()
    np.testing.assert_allclose(
        y + lm.moe_shared(x, lw), ref.experts(x, lw, lm.published(cfg)),
        atol=TOL, rtol=0)


def test_the_tolerance_catches_a_dropped_pick_and_bfloat16_sums():
    cfg = config()
    lw = seeded_params(cfg)["layers"][0]
    x = jax.random.normal(jax.random.key(10), (64, cfg.d_model))
    want = ref.experts(x, lw, lm.published(cfg))
    idx, w = lm.moe_router(x, lw, cfg)
    dropped = w.at[:, 3].set(0.0)        # each token's fourth pick
    y, _ = lm.moe_experts(x, idx, dropped, jnp.ones(64, bool), lw, cfg)
    assert np.abs(np.asarray(y + lm.moe_shared(x, lw)) - want).max() > 100 * TOL
    low = {k: v.astype(jnp.bfloat16) if v.ndim > 1 and k != "w_r" else v
           for k, v in lw.items()}
    y, _ = lm.moe_experts(x, idx, w, jnp.ones(64, bool), low, cfg)
    assert np.abs(np.asarray(y + lm.moe_shared(x, low)) - want).max() > 20 * TOL


# ---------------------------------------------------------------------------
# the latent cache: extend == full forward
# ---------------------------------------------------------------------------

#: the short half of the ladder at max_len 256, page 16, max_batch 8
SHORT_BUCKETS = [(1, 16, 64), (1, 16, 128), (1, 16, 256), (4, 16, 64),
                 (4, 16, 128), (4, 16, 256), (8, 16, 256)]


@pytest.fixture(scope="module")
def served():
    """One chip's share (experts 4-7 of 16) behind the latent cache; the
    ladder at max_len 256 is 1x16 / 4x16 absorbed over a context of 64, 128
    or the whole length, 8x16 over the whole length alone, and up-projected
    1x128 over 128 or 256 and 1x256."""
    cfg = config(experts_held=4, expert_offset=4)
    params = seeded_params(cfg)   # four experts a layer: the share's own
    serving = LatentServing(params, cfg)
    assert serving.warmup(8) == len(SHORT_BUCKETS) + 3
    assert serving.info()["buckets"] == [
        f"{LatentServing.label(*b)}:absorbed" for b in SHORT_BUCKETS] + [
        "1x128@128:up", "1x128@256:up", "1x256@256:up"]
    yield serving, params, cfg
    serving.close()


def _dispatched() -> dict:
    fam = parse_prometheus_text(REGISTRY.expose()).get(
        "pio_seq_dispatches_total", {"samples": []})
    return {labels["bucket"]: value for _, labels, value in fam["samples"]}


@pytest.mark.parametrize("first, growth", [
    (40, (1, 7, 16)),        # miss in 1x128@128, three absorbed extensions
    (200, (17, 20, 19)),     # miss in 1x256, three extensions in 1x128@256
    (3, (100, 5, 148)),      # miss absorbed; 1x128@128, 1x16@128, 1x256
], ids=["absorbed", "up", "mixed"])
def test_miss_then_three_extensions_equal_the_full_forward(
        served, sessions, first, growth):
    serving, params, cfg = served
    key = f"grow-{first}"
    before = _dispatched()
    n = first
    assert_answers(serving, params, cfg, [(key, sessions[0, :n])])
    for g in growth:
        n += g
        assert_answers(serving, params, cfg, [(key, sessions[0, :n])])
    after = _dispatched()
    used = {b for b in after if after[b] > before.get(b, 0)}
    assert used == {
        "absorbed": {"1x128@128", "1x16@64"},   # 41, 48, 64 of 64 rows
        "up": {"1x256@256", "1x128@256"},
        "mixed": {"1x16@64", "1x128@128", "1x16@128", "1x256@256"}}[
        "absorbed" if first == 40 else "up" if first == 200 else "mixed"]


def _fills(ask, s):
    """A session that fills the 64-row context exactly: no invalid key of
    the context stands for the padding item (``head_step``'s own column)."""
    ask([("a", s[0, :60])])
    return [ask([("a", s[0, :64])])]


def _crosses(ask, s):
    """A session that crosses from the 64-row context into the 128-row one
    between two turns: the rows the first wrote are the second's context."""
    ask([("a", s[1, :60])])
    return [ask([("a", s[1, :64])]), ask([("a", s[1, :70])])]


def _pair(ask, s):
    """Two sessions a dispatch: the longer one sets the context."""
    ask([("a", s[2, :30])]), ask([("b", s[3, :100])])
    return [ask([("a", s[2, :33]), ("b", s[3, :105])])]


def _tail(ask, s):
    """A block cut into a piece of 128 and a tail of 12, which runs as a
    turn over what the piece cached (the play shortens the block ladder: the
    latent block's own holds every block whole)."""
    return [ask([("a", s[4, :140])])]


@pytest.mark.parametrize("play, natural, reused, blocks", [
    (_fills, ["1x128@128", "1x16@64"], 60, None),
    (_crosses, ["1x128@128", "1x16@64", "1x16@128"], 60 + 64, None),
    (_pair, ["1x128@128", "1x128@128", "4x16@128"], 30 + 100, None),
    (_tail, ["1x128@128", "1x16@256"], 0, (16, 128)),
], ids=["fills", "crosses", "pair", "tail"])
def test_every_short_bucket_that_holds_a_group_answers_alike(
        served, sessions, monkeypatch, play, natural, reused, blocks):
    """The rows a smaller context leaves out are rows the mask had zeroed:
    the same sessions through the whole-length 4x16 bucket (the parent's
    only one), through the buckets the plan picks and through every other
    short bucket that holds them give one answer, the reference's."""
    serving, params, cfg = served
    if blocks:
        monkeypatch.setattr(serving, "blocks", blocks)
    asked, runs = [], []

    def ask(requests):
        asked.append([tokens for _, tokens in requests])
        return serving.extend(
            [(f"{play.__name__}{len(runs)}-{key}", tokens)
             for key, tokens in requests])

    def run(bucket=None):
        """The play with fresh session keys; every short dispatch in
        ``bucket`` (default: the plan's own choice)."""
        runs.append(bucket)
        del asked[:]
        if bucket:   # shadows the method
            serving._short_bucket = lambda sessions, longest: bucket
        try:
            return play(ask, sessions)
        finally:
            vars(serving).pop("_short_bucket", None)

    def extends():
        return [s["attrs"]["bucket"] for s in trace.TRACES.spans()
                if s["name"] == "seq.batch.extend"]

    want = run((4, 16, 256))
    r0 = REGISTRY.get("pio_seq_tokens_reused_total").value
    before = len(extends())
    got = run()
    assert REGISTRY.get("pio_seq_tokens_reused_total").value - r0 == reused
    assert extends()[before:] == natural
    answered = asked[-len(got):]
    for batch, (scores, items) in zip(answered, got):
        for tokens, s, i in zip(batch, scores, items):
            want_s, want_i = masked_reference(params, cfg, tokens)
            np.testing.assert_array_equal(i, want_i)
            np.testing.assert_allclose(s, want_s, atol=TOL, rtol=0)
    holds = [b for b in SHORT_BUCKETS
             if b[0] >= max(map(len, asked))
             and b[2] >= max(len(t) for batch in asked for t in batch)]
    assert len(holds) >= 3
    for bucket, answers in [(None, got)] + [(b, run(b)) for b in holds]:
        for (ws, wi), (gs, gi) in zip(want, answers):
            np.testing.assert_array_equal(gi, wi, err_msg=str(bucket))
            np.testing.assert_allclose(gs, ws, atol=TOL, rtol=0,
                                       err_msg=str(bucket))
    # the next tests count free pages
    for key in [k for k in serving._sessions if k.startswith(play.__name__)]:
        serving._free.extend(serving._sessions.pop(key).pages)


def test_a_batch_of_warm_cold_and_repeated_sessions(served, sessions):
    """Eight short blocks share the 8x16@256 bucket; a session asked twice in
    one batch is extended in two rounds; a keyless session leaves nothing."""
    serving, params, cfg = served
    warm = [(f"w{i}", sessions[i, :30 + i]) for i in range(6)]
    assert_answers(serving, params, cfg, warm)
    free = len(serving._free)
    batch = [(k, sessions[i, :34 + 2 * i]) for i, (k, _) in enumerate(warm)]
    batch += [("w0", sessions[0, :40]), (None, sessions[7, :9]),
              (None, sessions[6, :150])]
    assert_answers(serving, params, cfg, batch)
    # w0, w1 and w2 crossed into a third page; the keyless sessions' pages
    # came back
    assert len(serving._free) == free - 3


def test_partial_prefix_eviction_and_reentry_answer_as_a_miss(served, sessions):
    serving, params, cfg = served
    reused = lambda: REGISTRY.get("pio_seq_tokens_reused_total").value  # noqa: E731
    assert_answers(serving, params, cfg, [("p", sessions[2, :120])])
    # the application rewrote the session's tail: 80 tokens are reused
    edited = np.concatenate([sessions[2, :80], sessions[3, :50]])
    r0 = reused()
    assert_answers(serving, params, cfg, [("p", edited)])
    assert reused() - r0 == 80
    # a shorter list than what is cached: all but its last token is reused
    r0 = reused()
    assert_answers(serving, params, cfg, [("p", edited[:60])])
    assert reused() - r0 == 59
    # fill the cache until "p" is evicted, then come back
    evicted = REGISTRY.get("pio_seq_cache_evictions_total").value
    for i in range(8):
        assert_answers(serving, params, cfg, [(f"fill{i}", sessions[i])])
    assert REGISTRY.get("pio_seq_cache_evictions_total").value > evicted
    assert "p" not in serving._sessions
    r0 = reused()
    assert_answers(serving, params, cfg, [("p", edited[:60])])
    assert reused() == r0
    used = sum(len(s.pages) for s in serving._sessions.values())
    assert used + len(serving._free) == serving.capacity_tokens // 16


def test_a_stale_prefix_would_be_caught(served, sessions):
    """The comparison that everything above rests on is not vacuous: the same
    session answered from another session's cached prefix fails it."""
    serving, params, cfg = served
    serving.extend([("s", sessions[4, :90])])
    other = np.concatenate([sessions[5, :90], sessions[4, 90:100]])
    serving._sessions["s"].tokens = other[:90]      # lie about what is held
    with pytest.raises(AssertionError):
        assert_answers(serving, params, cfg, [("s", other)])
    serving._sessions.pop("s")


def test_expert_counters_are_read_when_metrics_are(served, sessions):
    serving, params, cfg = served

    def families():
        return parse_prometheus_text(REGISTRY.expose())

    def total(fam, name):
        return sum(v for _, _, v in fam.get(name, {"samples": []})["samples"])

    before = families()
    serving.extend([(None, sessions[1, :50])])
    after = families()
    held = total(after, "pio_moe_expert_tokens_total") \
        - total(before, "pio_moe_expert_tokens_total")
    unheld = total(after, "pio_moe_tokens_unheld_total") \
        - total(before, "pio_moe_tokens_unheld_total")
    assert held + unheld == 50 * 4 * cfg.n_layers
    was = {tuple(sorted(l.items())): v for _, l, v in before.get(
        "pio_moe_expert_tokens_total", {"samples": []})["samples"]}
    labels = {tuple(sorted(l.items())) for _, l, v in
              after["pio_moe_expert_tokens_total"]["samples"]
              if v != was.get(tuple(sorted(l.items())), 0)}   # that moved
    assert labels and labels <= {(("expert", str(e)), ("layer", str(layer)))
                      for e in range(4, 8) for layer in range(3)}
    assert total(after, "pio_moe_experts_touched_total") \
        > total(before, "pio_moe_experts_touched_total")
    gauge = {l["state"]: v for _, l, v in
             after["pio_seq_cache_tokens"]["samples"]}
    assert gauge["capacity"] == serving.capacity_tokens
    assert 0 < gauge["used"] <= gauge["capacity"]


# ---------------------------------------------------------------------------
# names the device trace and the benchmark's readers match
# ---------------------------------------------------------------------------

def test_spans_lie_on_the_profilers_timeline_and_scopes_in_the_programs(
        served, sessions, tmp_path):
    """A recorded profiler trace of one batch (a short block and a long one)
    holds its ``seq.*`` spans as ``pio.*`` events: the lock, the match, and
    under each ``seq.batch.extend`` its stage, launch and wait, named by the
    dispatch's kind; the compiled programs (the short block's one turn
    program a bucket, a longer block's layer and head) carry the six named
    scopes, by which ``device_scopes`` tells a device trace's operations
    apart."""
    import glob

    serving, params, cfg = served
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serving.extend([("t1", sessions[0, :12]), ("t2", sessions[1, :140])])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    names = [e.name for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("pio.seq.")]
    parts = ("stage", "launch", "wait")
    assert sorted(names) == sorted(
        ["pio.seq.batch.lock", "pio.seq.batch.match"]
        + 2 * ["pio.seq.batch.extend"]
        + [f"pio.seq.{kind}.{part}" for kind in ("turn", "miss")
           for part in parts])
    # the call's own spans are the ring's newest (a full ring keeps its
    # length, so they are counted from its end)
    spans = [s for s in trace.TRACES.spans()
             if s["name"].startswith("seq.")][-10:]
    assert [s["name"] for s in spans] == [
        "seq.batch.lock", "seq.batch.match",
        *(f"seq.turn.{part}" for part in parts), "seq.batch.extend",
        *(f"seq.miss.{part}" for part in parts), "seq.batch.extend"]
    assert spans[0]["attrs"] == {"ahead": 0}
    match = spans[1]
    assert match["attrs"] == {"sessions": 2, "hits": 0, "misses": 2,
                              "reused": 0}
    extends = [s for s in spans if s["name"] == "seq.batch.extend"]
    assert [s["attrs"] for s in extends] == [
        {"bucket": "1x16@64", "tokens": 12, "form": "absorbed"},
        {"bucket": "1x256@256", "tokens": 140, "form": "up"}]
    # stage, launch and wait hang under their dispatch and cover it
    own = trace.self_seconds(spans)
    for kind, parent in zip(("turn", "miss"), extends):
        kids = [s for s in spans if s["name"].startswith(f"seq.{kind}.")]
        assert all(s["parentId"] == parent["spanId"] for s in kids)
        assert own[parent["spanId"]] <= 0.05 * parent["durationSec"]
        assert kids[0]["attrs"] == {"sessions": 1}
        # the short block is one program, a longer one a launch a layer
        assert kids[1]["attrs"] == {
            "launches": 1 if kind == "turn" else cfg.n_layers + 2}

    scopes = serving.device_scopes()
    assert set(scopes) == {
        f"jit_seq_turn_b{b}_t{t}_c{c}" for b, t, c in SHORT_BUCKETS} | {
        f"jit_seq_{kind}_b{b}_t{t}_c{c}" for kind in ("layer", "head")
        for b, t, c in [(1, 128, 128), (1, 128, 256), (1, 256, 256)]}
    layer = {"mla_proj", "mla_attn", "moe_router", "moe_experts", "moe_shared"}
    for module, found in scopes.items():
        want = {"head_topk"} if "_head_" in module else layer \
            if "_layer_" in module else layer | {"head_topk"}
        assert set(found.values()) == want, module
    assert set(serving._exe[4, 16, 256]) == {"turn"}
    text = serving._exe[4, 16, 256]["turn"][TOP_K].as_text()
    assert re.search(r"HloModule jit_seq_turn_b4_t16_c256\b", text)
    text = serving._exe[1, 128, 256]["layer"].as_text()
    assert re.search(r"HloModule jit_seq_layer_b1_t128_c256\b", text)
    # the block's scope list is what it was before the sparse-index block
    # came to share this module
    assert lm.scopes(cfg) == lm.SCOPES == (
        "mla_proj", "mla_attn", "moe_router", "moe_experts", "moe_shared",
        "head_topk")


@pytest.mark.parametrize("bucket, digest", [
    ((4, 16, 256),
     "b515fe347c07554a8cfe7f231c5a949a400e1d1b34d54f104b0342292bb31d2e"),
    ((1, 128, 256),
     "dc3c9934591449568dc16b153d613c3f9ae844f68b06601856ccc63110d5ea9c"),
], ids=["absorbed", "up"])
def test_the_latent_layer_lowers_to_the_parents_program(served, bucket, digest):
    """ISSUE 30 made ``layer_apply`` compose the attention half from the
    config and the cache a mapping of row kinds, and had to leave the latent
    block's programs alone: the layer's lowered text (no debug info: line
    numbers move) is commit 7398cde's, but for the name of its cache result
    (``result[1]`` there, ``result[1]['latent']`` now)."""
    serving, _, _ = served
    text = serving._lower(*bucket)["layer"].as_text().replace(
        "result[1]['latent']", "result[1]")
    assert hashlib.sha256(text.encode()).hexdigest() == digest, (
        f"the digest was taken under jax 0.9.0 and this is jax "
        f"{jax.__version__}: after a JAX upgrade, or a deliberate change to "
        f"the latent block, pin the new digest")


# ---------------------------------------------------------------------------
# the normal path: run_train -> persist -> QueryServer -> POST /queries.json
# ---------------------------------------------------------------------------

def test_train_persist_deploy_query_through_the_query_server(
        tmp_path, monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data import Event
    from incubator_predictionio_tpu.data.storage import App, Storage, registry
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
    # (the DataSource reads through the process's Storage: this one,
    # whatever an earlier test file of this worker left there)
    monkeypatch.setattr(registry, "_storage_singleton", storage)
    app_id = storage.get_meta_data_apps().insert(App(0, "latent-seq"))
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
        "id": "latent", "version": "1", "engineFactory": factory,
        "datasource": {"params": {"appName": "latent-seq", "maxLen": 32}},
        "algorithms": [{"name": "transformer", "params": {
            "appName": "latent-seq", "maxLen": 32, "dModel": 32, "nHeads": 2,
            "nLayers": 2, "epochs": 3, "batchSize": 16, "seed": 1,
            "attentionKind": "mla", "qLoraRank": 16, "kvLoraRank": 8,
            "qkNopeHeadDim": 8, "qkRopeHeadDim": 8, "vHeadDim": 16,
            "ropeParameters": ROPE, "nRoutedExperts": 8,
            "numExpertsPerTok": 2, "moeIntermediateSize": 16,
            "nSharedExperts": 1, "tieHead": False, "cachePage": 8,
            "cacheTokens": 512}}],
    }
    path = os.path.join(home, "engine.json")
    with open(path, "w") as f:
        json.dump(variant, f)
    engine = SequentialEngine().apply()
    instance_id = run_train(
        engine, engine.engine_params_from_variant(variant),
        EngineInstance(
            id="", status="INIT", start_time=dt.datetime.now(dt.timezone.utc),
            end_time=None, engine_id="latent", engine_version="1",
            engine_variant=os.path.abspath(path), engine_factory=factory),
        storage=storage, ctx=MeshContext.create())
    # persisted through the SPI: an orbax checkpoint and a sidecar, no pickle
    # of the weights in MODELDATA
    saved = os.path.join(home, "device_models", f"{instance_id}_0")
    assert os.path.exists(os.path.join(saved, "sidecar.pkl"))
    assert os.path.getsize(os.path.join(saved, "sidecar.pkl")) < 20_000

    session = [f"i{(5 + 3 * j) % 40}" for j in range(12)]

    async def drive():
        trace.TRACES.clear()
        server = QueryServer(
            ServerConfig(engine_variant=path, max_batch=8),
            storage=storage, ctx=MeshContext.create())
        deploy = [s["name"] for s in trace.TRACES.spans()]
        model = server.deployed.models[0]
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            answers = []
            for n in (8, 10, 12):   # a miss, then two turns of the session
                resp = await client.post("/queries.json", json={
                    "user": "visitor", "recent_items": session[:n], "num": 5})
                answers.append(await resp.json())
            cold = await (await client.post("/queries.json", json={
                "recent_items": ["nope"], "num": 3})).json()
            stored = await (await client.post("/queries.json", json={
                "user": "u3", "num": 4})).json()
            status = await (await client.get("/")).json()
        finally:
            await client.close()
            await server.shutdown()
        return deploy, model, answers, cold, stored, status

    deploy, model, answers, cold, stored, status = asyncio.run(drive())
    for name in ("deploy.load", "deploy.restore", "deploy.cache",
                 "deploy.warmup", "deploy.warmup.bucket"):
        assert name in deploy, name
    assert isinstance(model, TransformerModel) and model.config.latent
    info = status["servingPaths"][0]
    assert info["path"] == "device-latent-cache"
    assert info["experts_held"] == info["n_routed_experts"] == 8
    assert info["cache_capacity_tokens"] >= 512 - 8
    assert info["buckets"] == [
        "1x16@16:absorbed", "1x16@32:absorbed", "4x16@16:absorbed",
        "4x16@32:absorbed", "8x16@32:absorbed", "1x32@32:up"]
    pub = lm.published(model.config)
    for n, body in zip((8, 10, 12), answers):
        tokens = np.asarray([model.item_map[i] for i in session[:n]], np.int32)
        want_s, want_i = masked_reference(model.params, model.config, tokens, 5)
        inv = model.item_map.inverse()
        assert [r["item"] for r in body["itemScores"]] == \
            [inv[int(t)] for t in want_i]
        np.testing.assert_allclose(
            [r["score"] for r in body["itemScores"]], want_s, atol=TOL)
    assert cold["itemScores"] == []
    assert len(stored["itemScores"]) == 4   # read from the event store
    assert pub["experts_held"] == 8
    storage.close()
