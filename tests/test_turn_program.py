"""The short block's ONE program a bucket (``serving/latent_cache.py``
``turn_step``: embed, every layer, head + top-k in one executable) held to
the same steps called a layer at a time, for the three blocks the sequence
template serves (latent, sparse-index, the state-space layer pattern), on the
CPU at the tiny sizes of the blocks' own test files.

Both sides run the same functions in the same order in float32; what can
differ is what the compiler fuses across a layer boundary, so floats are held
to ``TOL`` (the blocks' own tolerance against their references is 5e-5 to
1e-4) and every integer (top-k items, token cache, expert counters) exactly.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import numpy as np
import pytest

from incubator_predictionio_tpu.models import latent_moe as lm
from incubator_predictionio_tpu.obs import trace
from incubator_predictionio_tpu.obs.metrics import REGISTRY, parse_prometheus_text
from incubator_predictionio_tpu.serving.latent_cache import (
    CONTEXT_FREE,
    TOP_K,
    TURN_KEPT,
    LatentServing,
    split_operands,
    turn_step,
)

TOL = 2e-6
BLOCKS = ("latent", "sparse", "pattern")


def _fixture(name: str):
    if name == "latent":
        from tests.test_latent_block import config, seeded_params

        return config(experts_held=4, expert_offset=4), seeded_params
    if name == "sparse":
        from tests.fixtures.sparse_tiny import config, seeded_params
    else:
        from tests.fixtures.ssm_tiny import config, seeded_params
    return config(), seeded_params


@pytest.fixture(scope="module", params=BLOCKS)
def served(request):
    """Each block behind the cache with blocks of 16 and 32, so that a miss
    longer than 32 tokens is cut and its tail runs as a turn."""
    cfg, seeded_params = _fixture(request.param)
    params = seeded_params(cfg)
    serving = LatentServing(params, cfg)
    serving.shapes = dataclasses.replace(serving.shapes, blocks=(16, 32))
    serving.blocks = serving.shapes.blocks
    serving.warmup(4)
    yield serving, params, cfg
    serving.close()


@pytest.fixture(scope="module")
def sessions():
    return np.random.default_rng(11).integers(1, 512, (8, 96)).astype(np.int32)


def _counter(name: str, **labels) -> float:
    fam = parse_prometheus_text(REGISTRY.expose()).get(name, {"samples": []})
    return sum(v for _, lab, v in fam["samples"]
               if all(lab.get(k) == w for k, w in labels.items()))


class _LayerAtATime:
    """Stands in front of a serving's turn programs: before each runs, the
    same dispatch is computed by ``embed_step``, each layer's own step and
    ``head_step`` as jitted programs of their own (the long blocks' chain),
    on the same operands and the same cache; after it, the two are compared.
    (The CPU backend donates nothing, so the operands outlive the call.)"""

    def __init__(self, serving):
        self.serving, self.inner = serving, serving._at_k
        self.seen, self._jits = [], {}

    def _chain(self, form, k):
        if (form, k) not in self._jits:
            cfg = self.serving.cfg
            self._jits[form, k] = (
                jax.jit(lambda *a: lm.embed_step(*a, page=cfg.cache_page)),
                {kind: jax.jit(
                    (lambda step: lambda *a: step(*a, cfg=cfg, form=form))(
                        lm.step_of(kind, cfg)))
                 for kind in dict.fromkeys(self.serving.kinds)},
                jax.jit(lambda *a: lm.head_step(*a, cfg=cfg, k=k)))
        return self._jits[form, k]

    def __call__(self, batch, block, ctx, k):
        exe = self.inner(batch, block, ctx, k)
        if block != self.serving.blocks[0]:
            return exe
        embed, layer, head = self._chain(self.serving.form(block), k)

        def both(item_emb, tok_cache, layers, caches, counters, norm_f,
                 head_w, operands):
            tokens, pages, offsets, counts, slots = split_operands(
                operands, block)
            h, want_toks = embed(item_emb, tok_cache, tokens, pages, offsets,
                                 counts)
            want_caches, want_counters = [], []
            for kind, lw, cache, count in zip(
                    self.serving.kinds, layers, caches, counters):
                h, cache, count = layer[kind](
                    lw, cache, count, h,
                    slots if kind in CONTEXT_FREE else pages, offsets, counts)
                want_caches.append(cache)
                want_counters.append(count)
            want = head(norm_f, head_w, want_toks, h, pages, offsets, counts)
            got = exe(item_emb, tok_cache, layers, caches, counters, norm_f,
                      head_w, operands)
            (values, items), toks, new_caches, new_counters = got
            n = int((counts > 0).sum())       # padding rows answer nothing
            np.testing.assert_array_equal(items[:n], want[1][:n])
            np.testing.assert_allclose(values[:n], want[0][:n], atol=TOL,
                                       rtol=0)
            np.testing.assert_array_equal(toks, want_toks)
            assert jax.tree.structure(new_caches) \
                == jax.tree.structure(want_caches)
            for a, b in zip(jax.tree.leaves(new_caches),
                            jax.tree.leaves(want_caches)):
                np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
            for a, b in zip(jax.tree.leaves(new_counters),
                            jax.tree.leaves(want_counters)):
                np.testing.assert_array_equal(a, b)
            self.seen.append((self.serving.label(batch, block, ctx), n))
            return got

        return both


def test_turns_through_one_program_equal_the_steps_a_layer_at_a_time(
        served, sessions, monkeypatch):
    """A short miss, a lone turn, a group of four, a cut block's tail and a
    session sent again shorter (a stateful pattern restarts it from zeros):
    every short dispatch's top-k, token cache, cache rows / state slots and
    expert counters are those of the chain on the same operands; the long
    pieces in between run the chain itself."""
    serving, params, cfg = served
    beside = _LayerAtATime(serving)
    monkeypatch.setattr(serving, "_at_k", beside)
    launches = {b: _counter("pio_seq_launches_total", block=b)
                for b in ("short", "long")}

    serving.extend([("cold", sessions[0, :11])])               # a short miss
    serving.extend([("cold", sessions[0, :14])])               # a lone turn
    group = [(f"g{i}", sessions[i + 1, :n])
             for i, n in enumerate((20, 27, 31, 18))]
    serving.extend(group)                                      # four misses
    serving.extend([(key, sessions[i + 1, :len(t) + g]) for (i, (key, t)), g
                    in zip(enumerate(group), (1, 4, 9, 16))])  # a group of 4
    serving.extend([("cut", sessions[5, :75])])     # 32 + 32 + a tail of 11
    serving.extend([("cut", sessions[5, :80])])
    restarts = _counter("pio_seq_state_restarts_total")
    serving.extend([("cut", sessions[5, :9])])      # shorter: sent again
    # (the sparse-index block's turns go one session a dispatch)
    four = [("4x16", 4)] if serving.batches[-1] >= 4 else 4 * [("1x16", 1)]
    assert [(label.split("@")[0], n) for label, n in beside.seen] == [
        ("1x16", 1), ("1x16", 1), *four, ("1x16", 1), ("1x16", 1),
        ("1x16", 1)]
    if serving.n_slots:
        assert _counter("pio_seq_state_restarts_total") - restarts == 1
        # what the table says the session's state is, is the chain's slot
        tokens, state = serving.session_state("cut", 0)
        np.testing.assert_array_equal(tokens, sessions[5, :9])
        sess = serving._sessions["cut"]
        for name, row in state.items():
            np.testing.assert_array_equal(
                row, np.asarray(serving.cache[0][name][sess.slot]))
    # launches: one a short dispatch; embed + a layer each + the head where
    # a piece answers, for the four misses and the cut block's two pieces
    # (the latent block reads 20-31 tokens as 32-blocks too)
    n = len(serving.kinds)
    assert _counter("pio_seq_launches_total", block="short") \
        - launches["short"] == len(beside.seen)
    assert _counter("pio_seq_launches_total", block="long") \
        - launches["long"] == 4 * (n + 2) + 2 * (n + 1)


def test_a_larger_num_compiles_the_buckets_turn_program_at_its_own_k(
        served, sessions):
    serving, params, cfg = served
    scores, items = serving.extend([("k", sessions[6, :13])], num=40)
    assert scores.shape == (1, 64) and items.shape == (1, 64)
    bucket = next(b for b in serving._exe if b[:2] == (1, 16)
                  and b[2] >= 13)
    assert set(serving._exe[bucket]["turn"]) == {TOP_K, 64}
    want, _ = serving.extend([("k2", sessions[6, :13])])
    np.testing.assert_allclose(scores[0, :TOP_K], want[0], atol=TOL, rtol=0)


def test_every_short_bucket_is_one_program_with_every_scope(served):
    """``device_scopes()`` publishes ``jit_seq_turn_b…`` for every short
    bucket, with every named scope of the block (the layers' and the
    head's), and still the long buckets' chain; a short bucket holds no
    executable a layer."""
    serving, _, cfg = served
    scopes = serving.device_scopes()
    short = [b for b in serving.ladder() if b[1] == serving.blocks[0]]
    long = [b for b in serving.ladder() if b[1] != serving.blocks[0]]
    assert short and long
    # (the sparse-index block has no shared expert to run)
    runs = set(lm.scopes(cfg)) - (
        set() if cfg.n_shared_experts else {"moe_shared"})
    for bucket in short:
        assert set(serving._exe[bucket]) == {"turn"}
        found = scopes["jit_" + serving.program("turn", *bucket)]
        assert set(found.values()) == runs, bucket
        text = serving._exe[bucket]["turn"][TOP_K].as_text()
        assert re.search(
            rf"HloModule jit_seq_turn_b{bucket[0]}_t16_c{bucket[2]}\b", text)
    for bucket in long:
        assert "turn" not in serving._exe[bucket]
        for kind in (*dict.fromkeys(serving.kinds), "head"):
            assert "jit_" + serving.program(kind, *bucket) in scopes
    assert len(scopes) == len(short) + len({
        serving.program(kind, *b) for b in long
        for kind in (*dict.fromkeys(serving.kinds), "head")})
    # nothing of the short block is compiled a layer: no such name is left
    assert not [m for m in scopes
                if re.search(r"_t16(_c\d+)?$", m) and "_turn_" not in m]


def test_the_turn_program_asks_for_every_kept_array_in_place(served):
    """Lowered for a backend that donates (the TPU; the CPU's lowering asks
    for nothing): the token cache, every cache array and every counter are
    donated, each to an output of its own shape, and nothing else is. (That
    the chip's compiler then holds them in place, with no cache-sized copy,
    is ``tests/test_tpu_compile.py``'s, at the visitor cell's widths.)"""
    serving, params, cfg = served
    batch, block, ctx = next(iter(serving.ladder()))

    def seq_turn(*args):
        return turn_step(*args, cfg=cfg, form=serving.form(block), k=TOP_K)

    ints = [np.zeros(shape, np.int32) for shape in (
        (batch, block), (batch, ctx // cfg.cache_page), (batch,), (batch,),
        (batch,))]
    args = (params["item_emb"], serving.tok_cache, params["layers"],
            serving.cache, serving.counters, params["norm_f"],
            lm.head_matrix(params), *ints)
    text = jax.jit(seq_turn, donate_argnums=TURN_KEPT).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    main = re.search(
        r"func\.func public @main\((.*?)\) ->", text, re.S).group(1)
    donated = re.findall(
        r"%arg\d+: (tensor<[^>]*>) \{[^}]*(?:tf\.aliasing_output|"
        r"jax\.buffer_donor)[^}]*\}", main)
    kept = [serving.tok_cache, *jax.tree.leaves(serving.cache),
            *jax.tree.leaves(serving.counters)]
    assert len(donated) == len(kept)
    assert sorted(donated) == sorted(
        "tensor<" + "x".join(map(str, a.shape)) + "x"
        + {"int32": "i32", "float32": "f32"}[str(a.dtype)] + ">"
        for a in kept)
    # and on this backend the serving's own lowering donates nothing
    assert jax.default_backend() == "cpu"
    own = serving._lower_turn(batch, block, ctx, TOP_K).as_text()
    assert "tf.aliasing_output" not in own and "jax.buffer_donor" not in own


def test_a_short_dispatch_is_one_launch_in_its_span(served, sessions):
    serving, _, _ = served
    trace.TRACES.clear()
    serving.extend([("sp", sessions[7, :40])])
    serving.extend([("sp", sessions[7, :43])])
    launch = {s["name"]: s["attrs"] for s in trace.TRACES.spans()
              if s["name"].endswith(".launch")}
    assert launch["seq.turn.launch"] == {"launches": 1}
    # 40 tokens: a piece of 32 without a head, then the tail as a turn
    assert launch["seq.miss.launch"] == {"launches": len(serving.kinds) + 1}
