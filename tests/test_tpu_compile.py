"""The TPU's own compiler, without the chip: the device leg's executables at
the benchmark's widths (476,002 items × rank 128: 690 partitions in blocks of
4,096 rows, nprobe 26, serve_k 128) and the sparse-index block's layer
executables at the lifelong cell's (hidden 2048, 32 / 4 heads of 128,
indexer 16 x 64 top-2048, 128 experts top-8 of width 768, 720,896 cache rows)
compile for a described v5e, as do the latent block's at the Mistral cell's
(hidden 4096, 32 heads over a 256 + 64 latent row, 32 of 128 experts top-4 of
width 2048, 786,560 cache rows) in its smallest and its largest turn bucket,
and the state-space pattern's three layer kinds at the visitor cell's (hidden
2688, 64 state-space heads of 64 x 128, 32 / 2 heads of 128, 64 of 128 relu2
experts top-6 of width 1856, 257 state slots, 262,272 cache rows), and that
cell's whole turn program (``latent_cache.turn_step``: embed, the 14 layers,
head + top-k in one executable) in a lone turn's bucket, and the
short-convolution pattern's at the feed cell's (hidden 2048, 18 convolution
layers of 3 taps, 6 rotary 32 / 8-head attention layers of 64, 2 dense parts
of 7168, 16 of 32 experts top-4 of width 1792, 2,049 carry slots, 327,808
cache rows): its whole 48-sub-block turn program in the widest turn bucket and
each letter's step over the longest block, and the window / full attention
pattern's at the histories cell's (hidden 2304, 21 window layers of 1,024
keys and 7 full layers of 32 / 4 heads of 128, 16 of 64 experts top-8 of
width 896, 33 rings of 1,024 rows a window layer, 278,656 cache rows): its
whole 56-sub-block turn program in the widest turn bucket and each letter's
step over a 2,048-token piece against the longest context.
The index build's clustering programs (``ops/retrieval.py`` ``ivf_*``)
compile at the train cell's shapes (a 65,536-row sample and 100,000 rows of
128 + the bias column, 316 partitions) and the two-stage serve cell's
(476,002 rows, 690 partitions) with the score block never held whole.
What the
Pallas interpreter accepts, Mosaic can still refuse (tiling, scoped memory);
that has to fail here and not on the chip. Nothing runs, so nothing here is
a time or a result.

All such compiles live in THIS file, and the topology is described inside a
fixture: only the worker that is given this file loads the TPU's library.
"""

import contextlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_predictionio_tpu.ops import retrieval

N_ITEMS, RANK, PARTITIONS, BLOCK, CENTROIDS = 476_002, 128, 690, 4096, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _chip_kernels():
    """The code asks the backend which grouped matmul the routed experts
    run (``latent_moe.expert_form``): this process's is the CPU, the
    programs compiled here are the chip's."""
    from incubator_predictionio_tpu.models import latent_moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent_moe, "kernel_backend", lambda: "mosaic")
        yield


def _expert_kernels(text: str) -> list:
    """The Pallas calls of a compiled program's text, every one of them
    under the scope the experts' roofline reads; no ``ragged_dot`` is left."""
    calls = re.findall(r'^.*custom_call_target="tpu_custom_call".*$', text,
                       re.M)
    assert all("/moe_experts/" in call for call in calls)
    assert "ragged-dot" not in text
    return calls


def _rerank_args(one_chip, bucket, row_mask):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = (PARTITIONS, 1, BLOCK)
    return [
        s((bucket, CENTROIDS), jnp.float32), s((bucket, RANK), jnp.int8),
        s((bucket,), jnp.float32), s((bucket,), jnp.float32),
        s((), jnp.float32), s((PARTITIONS,), jnp.int32),
        s((PARTITIONS, BLOCK, RANK), jnp.int8), s(blocks, jnp.float32),
        s(blocks, jnp.float32), s(blocks, jnp.int32), s(blocks, jnp.float32),
        s((bucket, N_ITEMS), jnp.float32) if row_mask else None,
    ]


@pytest.mark.parametrize("bucket, row_mask", [(8, False), (64, False),
                                              (8, True)])
def test_two_stage_rerank_compiles_for_v5e(one_chip, bucket, row_mask):
    compiled = retrieval.two_stage_rerank.lower(
        *_rerank_args(one_chip, bucket, row_mask), nprobe=26, k=128).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "rerank_members_quantized" in text
    # the probed blocks are read by the kernel's pipeline: the program holds
    # no copy of the candidate rows (26 x 4096 x 128 B a query)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < bucket * 26 * BLOCK * RANK // 4


def test_quantize_user_rows_compiles_for_v5e(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = retrieval.quantize_user_rows.lower(
        s((8,), jnp.int32), s((4_201_696, RANK), jnp.bfloat16),
        s((4_201_696,), jnp.float32)).compile()
    # a row gather, not a copy of the tower (the fused [U, 129] float32
    # layout made one: 10 ms a call on the chip)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- the sparse-index block's layer executables (serving/latent_cache.py) ----------------

def _sparse_cfg():
    from incubator_predictionio_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=151_936, max_len=32_768, d_model=2048, n_heads=32,
        n_layers=4, attention_kind="gqa_sparse", n_kv_heads=4, head_dim=128,
        rope_theta=1e7, index_n_heads=16, index_head_dim=64, index_topk=2048,
        router_scoring="softmax", n_routed_experts=128, experts_per_token=8,
        moe_intermediate_size=768, tie_head=False, weight_dtype="bfloat16",
        cache_page=128, cache_tokens=720_896)


@pytest.mark.parametrize("batch, block, form", [
    (1, 16, "select"),      # the largest turn bucket of the block's ladder
    (1, 2048, "chunk"),     # a piece of a miss
])
def test_sparse_index_layer_compiles_for_v5e_at_context_32768(
        one_chip, batch, block, form):
    """The layer executable over the whole 32,768-row context, with the cell's
    own cache (22 x 32,768 tokens in two kinds of row) as its argument: it
    fits the chip beside the 6.25 GB of weights, donates the cache instead of
    copying it, and holds no gathered or scored array larger than the ladder
    was sized for."""
    from incubator_predictionio_tpu.models import latent_moe, sparse_gqa

    cfg = _sparse_cfg()
    ladder = sparse_gqa.serve_shapes(cfg)
    assert (ladder.batches, ladder.blocks) == ((1,), (16, 2048))
    assert ladder.short_contexts == (4096, 8192, 16384, 32768)
    assert (batch, block) in ((ladder.batches[-1], ladder.blocks[0]),
                              (1, ladder.blocks[-1]))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, ctx = jnp.bfloat16, cfg.max_len
    rows = (cfg.cache_tokens // cfg.cache_page + 1) * cfg.cache_page
    lw = {k: s(shape, jnp.float32 if f32 else bf16)
          for k, (shape, f32) in latent_moe.layer_shapes(cfg).items()}
    cache = {kind: s((rows, width), bf16)
             for kind, width in sparse_gqa.row_layout(cfg).items()}
    assert {k: v.shape[1] for k, v in cache.items()} == {
        "kv": 1024, "idx": 128}

    def layer(lw, cache, counters, h, pages, offsets, counts):
        return latent_moe.layer_step(lw, cache, counters, h, pages, offsets,
                                     counts, cfg=cfg, form=form)

    with _chip_kernels():
        compiled = jax.jit(layer, donate_argnums=(1, 2, 3)).lower(
            lw, cache, s((130,), jnp.int32),
            s((batch, block, 2048), jnp.float32),
            s((batch, ctx // cfg.cache_page), jnp.int32),
            s((batch,), jnp.int32), s((batch,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = rows * (1024 + 128) * 2
    assert mem.alias_size_in_bytes >= cache_bytes       # donated, not copied
    # weights of a layer + both caches + temporaries: well inside 16 GB
    # beside the other three layers' 3.75 GB and the 1.25 GB of embeddings
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + 3 * 1.26e9 + 1.25e9 + 3 * cache_bytes < 15.5e9
    text = compiled.as_text()
    for scope in ("gqa_proj", "idx_score", "idx_select", "sparse_attn",
                  "moe_router", "moe_experts"):
        assert f"/{scope}/" in text, scope
    assert len(_expert_kernels(text)) == 3


# -- the latent block's layer in a lone turn's bucket and in the parent's one ------

def _latent_cfg():
    """The Mistral cell's block, from the cell's own configuration file."""
    from incubator_predictionio_tpu.models.transformer import TransformerConfig

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmarks", "configs", "seq-mistral-small4-ep4.json")
    with open(path) as f:
        c = json.load(f)
    return TransformerConfig(
        vocab_size=c["vocab_size"], max_len=c["serve"]["max_len"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_layers=c["num_hidden_layers"], attention_kind="mla",
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_parameters=tuple(sorted(c["rope_parameters"].items())),
        n_routed_experts=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        experts_held=c["experts_held"], tie_head=False,
        weight_dtype="bfloat16", cache_page=c["serve"]["cache_page"],
        cache_tokens=c["serve"]["cache_tokens"])


@pytest.fixture(scope="module")
def latent_turn_layers(one_chip):
    """The absorbed-form layer at ``1x16@1024`` and ``4x16@4096``, compiled
    with the cell's own cache as the donated argument."""
    from incubator_predictionio_tpu.models import latent_moe

    cfg = _latent_cfg()
    ladder = latent_moe.serve_shapes(cfg)
    assert ladder.batches == (1, 4, 16, 64) and ladder.blocks[0] == 16
    assert ladder.contexts(1) == ladder.contexts(4) == (1024, 2048, 4096)
    assert ladder.contexts(16) == ladder.contexts(64) == (4096,)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16 = jnp.bfloat16
    rows = (cfg.cache_tokens // cfg.cache_page + 1) * cfg.cache_page
    assert rows == 786_560
    lw = {k: s(shape, jnp.float32 if f32 else bf16)
          for k, (shape, f32) in latent_moe.layer_shapes(cfg).items()}
    cache = {"latent": s((rows, latent_moe.cache_width(cfg)), bf16)}

    def layer(lw, cache, counters, h, pages, offsets, counts):
        return latent_moe.layer_step(lw, cache, counters, h, pages, offsets,
                                     counts, cfg=cfg, form=ladder.short_form)

    assert (cfg.d_model, cache["latent"].shape[1]) == (4096, 384)
    with _chip_kernels():
        return rows, {
            (batch, ctx): jax.jit(layer, donate_argnums=(1, 2, 3)).lower(
                lw, cache, s((34,), jnp.int32),
                s((batch, 16, 4096), jnp.float32),
                s((batch, ctx // cfg.cache_page), jnp.int32),
                s((batch,), jnp.int32), s((batch,), jnp.int32)).compile()
            for batch, ctx in ((1, 1024), (4, 4096))}


@pytest.mark.parametrize("batch, ctx", [(1, 1024), (4, 4096)])
def test_latent_turn_layer_compiles_for_v5e(latent_turn_layers, batch, ctx):
    rows, compiled = latent_turn_layers
    cache_bytes = rows * 384 * 2
    mem = compiled[batch, ctx].memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes       # donated, not copied
    # a layer's 1.72 GB of weights + the cache + temporaries beside the
    # other five layers and 0.54 GB of embeddings
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + 5 * 1.72e9 + 0.54e9 + 5 * cache_bytes < 15.5e9
    text = compiled[batch, ctx].as_text()
    for scope in ("mla_proj", "mla_attn", "moe_router", "moe_experts",
                  "moe_shared"):
        assert f"/{scope}/" in text, scope
    assert len(_expert_kernels(text)) == 3


def _scope_array_bytes(text: str, scope: str, but_rows: int) -> int:
    """Bytes of every array an instruction under ``scope`` produces (fast
    memory included: ``temp_size_in_bytes`` counts only what spills out of
    it), but for those as long as the cache."""
    width = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    total = 0
    for dtype, dims in re.findall(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]+)\][^\n]*"
            rf'op_name="[^"]*/{scope}/', text, re.M):
        dims = [int(d) for d in dims.split(",")]
        if dims[0] != but_rows:
            total += math.prod(dims) * width[dtype]
    return total


def test_a_lone_turns_bucket_holds_a_tenth_of_the_widest_ones_attention_arrays(
        latent_turn_layers):
    """What the context bucket is for: the gathered rows, the float32 scores
    and the probabilities of ``1x16@1024`` against ``4x16@4096``'s."""
    rows, compiled = latent_turn_layers
    small, large = (
        _scope_array_bytes(compiled[b].as_text(), "mla_attn", rows)
        for b in ((1, 1024), (4, 4096)))
    assert 1e6 < small < large / 10
    # the cache itself stays where it is in both
    assert all(c.memory_analysis().temp_size_in_bytes < 16e6
               for c in compiled.values())


# -- the state-space pattern's layer kinds at the visitor cell's widths ------------------

def _pattern_cfg():
    """The Nemotron cell's stack, from the cell's own configuration file, as
    ``benchmarks/engines/seeded_ssm.algorithm_params`` binds it."""
    from incubator_predictionio_tpu.models.transformer import TransformerConfig

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmarks", "configs", "seq-nemotron3-nano-ep2.json")
    with open(path) as f:
        c = json.load(f)
    return TransformerConfig(
        vocab_size=c["vocab_size"], max_len=c["serve"]["max_len"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_layers=c["num_hidden_layers"], attention_kind="gqa",
        layer_pattern=c["hybrid_override_pattern"].translate(
            str.maketrans("M*", "SA")),
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], ssm_groups=c["n_groups"],
        conv_kernel=c["conv_kernel"], ssm_chunk=c["chunk_size"],
        rms_norm_eps=c["layer_norm_epsilon"],
        n_routed_experts=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        shared_intermediate_size=c["moe_shared_expert_intermediate_size"],
        expert_activation=c["mlp_hidden_act"],
        routed_scaling_factor=c["routed_scaling_factor"],
        experts_held=c["experts_held"], tie_head=False,
        weight_dtype="bfloat16", cache_page=c["serve"]["cache_page"],
        cache_tokens=c["serve"]["cache_tokens"],
        state_slots=c["serve"]["state_slots"])


PATTERN_BUCKETS = [(1, 16, 512), (16, 16, 2048), (1, 2048, 2048)]


@pytest.fixture(scope="module")
def pattern_layers(one_chip):
    """Each layer kind's serving step in a lone turn's bucket, the widest
    batch of turns and the longest block, compiled with the cell's own state
    and cache as the donated arguments."""
    from incubator_predictionio_tpu.models import latent_moe, state_space

    cfg = _pattern_cfg()
    assert cfg.layer_pattern == "SESESAESESESAE"
    ladder = state_space.serve_shapes(cfg)
    assert ladder.path == "device-state-kv-cache"
    assert ladder.blocks == (16, 128, 512, 1024, 1536, 2048)
    assert ladder.contexts(1) == ladder.contexts(4) == (512, 1024, 2048)
    assert ladder.contexts(16) == (2048,)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = (cfg.cache_tokens // cfg.cache_page + 1) * cfg.cache_page
    kept = {
        "S": {name: s((cfg.state_slots + 1, n), dt)
              for name, (n, dt) in state_space.state_layout(cfg).items()},
        "A": {"kv": s((rows, 512), jnp.bfloat16)}, "E": {}}
    assert kept["S"]["state"].shape == (257, 64 * 64 * 128)
    assert kept["S"]["conv"].shape == (257, 3 * 6144)
    counters = {"S": (), "A": (), "E": s((66,), jnp.int32)}
    out = {}
    with _chip_kernels():
        for batch, block, ctx in PATTERN_BUCKETS:
            for kind in "SAE":
                lw = {k: s(shape, jnp.float32 if f32 else jnp.bfloat16)
                      for k, (shape, f32) in latent_moe.layer_shapes(
                          cfg, kind).items()}
                step = latent_moe.step_of(kind, cfg)
                own = s((batch, ctx // cfg.cache_page) if kind == "A"
                        else (batch,), jnp.int32)
                out[kind, batch, block] = jax.jit(
                    lambda lw, cache, counters, h, own, offsets, counts,
                    step=step: step(lw, cache, counters, h, own, offsets,
                                    counts, cfg=cfg, form=""),
                    donate_argnums=(1, 2, 3)).lower(
                    lw, kept[kind], counters[kind],
                    s((batch, block, cfg.d_model), jnp.float32), own,
                    s((batch,), jnp.int32), s((batch,), jnp.int32)).compile()
    return out


@pytest.mark.parametrize("batch, block, ctx", PATTERN_BUCKETS)
@pytest.mark.parametrize("kind", ["S", "A", "E"])
def test_pattern_layer_compiles_for_v5e(pattern_layers, kind, batch, block,
                                        ctx):
    compiled = pattern_layers[kind, batch, block]
    mem = compiled.memory_analysis()
    kept = {"S": 257 * (64 * 64 * 128 * 4 + 3 * 6144 * 2),
            "A": 262_272 * 512 * 2, "E": 0}[kind]
    assert mem.alias_size_in_bytes >= kept        # donated, not copied
    # beside 9.3 GB of weights, 3.29 GB of states and 0.54 GB of rows
    assert mem.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    scopes = {"S": ("ssm_proj", "ssm_conv", "ssm_scan"),
              "A": ("gqa_proj", "gqa_attn"),
              "E": ("moe_router", "moe_experts", "moe_shared")}[kind]
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    if kind == "E":
        # the routed experts' two grouped matmuls are the Pallas kernel at
        # every block size, under the scope the trace's readers sum
        calls = re.findall(r'^.*custom_call_target="tpu_custom_call".*$',
                           text, re.M)
        assert len(calls) == 2, len(calls)
        assert all("/moe_experts/" in call for call in calls)
        # ... and so does all that prepares them (the visits' arithmetic)
        names = re.findall(r'op_name="([^"]*grouped_matmul[^"]*)"', text)
        assert names and all("/moe_experts/" in name for name in names)
        assert "ragged-dot" not in text
        # no held expert runs on a token that did not pick it: no
        # [64, N, 1920] activation
        assert not re.search(r"f32\[64,\d+,1920\]", text)
        # the routed experts' matrices are stored lane-aligned ([64, 2688,
        # 1920] and [64, 1920, 2688]): no copy of all 64 in front of the
        # kernel, in whatever layout it asks for (at [64, 2688, 1856] the
        # compiler made one of 638 MB at every call)
        assert not re.search(
            r"bf16\[64,(?:2688|1920),\d+\][^\n]* copy\(", text)


# -- the visitor cell's turn program: a short dispatch as ONE executable ----------------

@pytest.fixture(scope="module")
def pattern_turn(one_chip):
    """``turn_step`` at ``1x16@512`` with the cell's 14 layers of weights,
    its 8 cache and 12 state arrays, its 6 counters and its token cache, the
    kept ones donated as ``LatentServing._lower_turn`` donates them."""
    from incubator_predictionio_tpu.models import latent_moe, state_space
    from incubator_predictionio_tpu.serving.latent_cache import (
        TURN_KEPT,
        turn_step,
    )

    cfg = _pattern_cfg()
    kinds = latent_moe.layer_kinds(cfg)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = (cfg.cache_tokens // cfg.cache_page + 1) * cfg.cache_page
    kept = {
        "S": {name: s((cfg.state_slots + 1, n), dt)
              for name, (n, dt) in state_space.state_layout(cfg).items()},
        "A": {"kv": s((rows, 512), jnp.bfloat16)}, "E": {}}
    counters = {"S": (), "A": (), "E": s((66,), jnp.int32)}
    layers = [{k: s(shape, jnp.float32 if f32 else jnp.bfloat16)
               for k, (shape, f32) in latent_moe.layer_shapes(
                   cfg, kind).items()} for kind in kinds]
    emb = s((cfg.vocab_size, cfg.d_model), jnp.bfloat16)
    batch, block, ctx = 1, 16, 512

    def seq_turn_b1_t16_c512(*args):
        return turn_step(*args, cfg=cfg, form="step", k=16)

    with _chip_kernels():
        compiled = jax.jit(
            seq_turn_b1_t16_c512, donate_argnums=TURN_KEPT).lower(
            emb, s((rows,), jnp.int32), layers, [kept[k] for k in kinds],
            [counters[k] for k in kinds], s((cfg.d_model,), jnp.float32),
            emb, s((batch, block), jnp.int32),
            s((batch, ctx // cfg.cache_page), jnp.int32),
            s((batch,), jnp.int32), s((batch,), jnp.int32),
            s((batch,), jnp.int32)).compile()
    return cfg, kinds, rows, compiled


def test_pattern_turn_program_keeps_every_cache_in_place(pattern_turn):
    cfg, kinds, rows, compiled = pattern_turn
    mem = compiled.memory_analysis()
    kept = kinds.count("S") * 257 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) \
        + kinds.count("A") * rows * 512 * 2 + rows * 4 \
        + kinds.count("E") * 66 * 4
    assert kept > 3.8e9
    assert mem.alias_size_in_bytes >= kept       # all donated, none copied
    # 14 layers' temporaries are no more than a layer's own programs held
    assert mem.temp_size_in_bytes < 64e6
    text = compiled.as_text()
    # no instruction copies an array as long as the states or the rows
    assert not re.search(
        rf"= \w+\[(?:257|{rows}),[\d,]*\]\S* copy(?:-start)?\(", text)
    assert re.search(r"HloModule jit_seq_turn_b1_t16_c512\b", text)


def test_pattern_turn_program_carries_every_scope(pattern_turn):
    """The rooflines' readers find the turn program's operations by the
    same named scopes as the chain's; the routed experts' twelve grouped
    matmuls (two a layer, six expert layers) are the Pallas kernel, under
    ``moe_experts``."""
    from incubator_predictionio_tpu.models import latent_moe

    cfg, kinds, _, compiled = pattern_turn
    text = compiled.as_text()
    for scope in latent_moe.scopes(cfg):
        assert f"/{scope}/" in text, scope
    calls = re.findall(r'^.*custom_call_target="tpu_custom_call".*$', text,
                       re.M)
    assert len(calls) == 2 * kinds.count("E")
    assert all("/moe_experts/" in call for call in calls)
    assert "ragged-dot" not in text


def _latent_turn():
    """The Mistral cell's turn program at ``1x16@1024``: ``(cfg, a layer's
    cache widths by row kind, its counters, the short form, the context)``."""
    from incubator_predictionio_tpu.models import latent_moe

    cfg = _latent_cfg()
    return cfg, {"latent": latent_moe.cache_width(cfg)}, 34, "absorbed", 1024


def _sparse_turn():
    """The lifelong cell's turn program at ``1x16@4096``, the smallest
    context bucket of its ladder."""
    from incubator_predictionio_tpu.models import sparse_gqa

    cfg = _sparse_cfg()
    return cfg, sparse_gqa.row_layout(cfg), 130, "select", 4096


@pytest.mark.parametrize("bucket", [_latent_turn, _sparse_turn],
                         ids=["latent", "sparse_index"])
def test_latent_turn_programs_grouped_matmuls_read_as_the_experts(
        one_chip, bucket):
    """The Mistral and the lifelong cells' turn programs: the routed
    experts' grouped matmuls (three a layer) are the Pallas kernel of
    ``ops/grouped_matmul.py`` at these widths too (multiples of 256 lanes:
    XLA's own ``ragged_dot`` until PR 40), every call under ``moe_experts``,
    and ``instruction_scopes`` books them there: the experts' roofline reads
    them through that map."""
    from incubator_predictionio_tpu.models import latent_moe
    from incubator_predictionio_tpu.serving.latent_cache import (
        TURN_KEPT,
        instruction_scopes,
        turn_step,
    )

    cfg, layout, n_counters, form, ctx = bucket()

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16 = jnp.bfloat16
    rows = (cfg.cache_tokens // cfg.cache_page + 1) * cfg.cache_page
    layers = [{k: s(shape, jnp.float32 if f32 else bf16)
               for k, (shape, f32) in latent_moe.layer_shapes(cfg).items()}
              for _ in range(cfg.n_layers)]
    caches = [{kind: s((rows, width), bf16) for kind, width in layout.items()}
              for _ in range(cfg.n_layers)]
    emb = s((cfg.vocab_size, cfg.d_model), bf16)

    def seq_turn(*args):
        return turn_step(*args, cfg=cfg, form=form, k=16)

    with _chip_kernels():
        compiled = jax.jit(seq_turn, donate_argnums=TURN_KEPT).lower(
            emb, s((rows,), jnp.int32), layers, caches,
            [s((n_counters,), jnp.int32)] * cfg.n_layers,
            s((cfg.d_model,), jnp.float32), emb, s((1, 16), jnp.int32),
            s((1, ctx // cfg.cache_page), jnp.int32), s((1,), jnp.int32),
            s((1,), jnp.int32), s((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    # every layer's cache donated, not copied
    assert mem.alias_size_in_bytes >= \
        cfg.n_layers * rows * sum(layout.values()) * 2
    assert mem.temp_size_in_bytes < 64e6
    text = compiled.as_text()
    assert len(_expert_kernels(text)) == 3 * cfg.n_layers
    found = instruction_scopes(text, latent_moe.scopes(cfg))
    kernels = [name for name in found if name.startswith("grouped_matmul")]
    assert len(kernels) == 3 * cfg.n_layers
    assert all(found[name] == "moe_experts" for name in kernels)
    # (the sparse-index block has no shared expert)
    assert set(found.values()) == set(latent_moe.scopes(cfg)) - (
        set() if cfg.n_shared_experts else {"moe_shared"})


# -- the feed cell's stack: the short-convolution pattern at its full depth --------------

def _feed_cfg():
    """The LFM2 cell's stack, from the cell's own configuration file, as
    ``benchmarks/engines/seeded_conv.algorithm_params`` binds it."""
    from benchmarks.engines import seeded_conv
    from incubator_predictionio_tpu.utils.params import params_from_json

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmarks", "configs", "seq-lfm2-8b-a1b-ep2.json")
    with open(path) as f:
        c = json.load(f)
    algo = seeded_conv.SeededShortConvAlgorithm(params_from_json(
        seeded_conv.SeededShortConvParams,
        seeded_conv.algorithm_params(c, 1)))
    return algo.model_config(c["vocab_size"])


def _feed_arguments(one_chip, cfg):
    from incubator_predictionio_tpu.models import latent_moe, state_space

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = (cfg.cache_tokens // cfg.cache_page + 1) * cfg.cache_page
    kept = {
        "C": {name: s((cfg.state_slots + 1, n), dt) for name, (n, dt)
              in state_space.state_layout(cfg, "C").items()},
        "A": {"kv": s((rows, 1024), jnp.bfloat16)}, "D": {}, "E": {}}
    counters = {"C": (), "D": (), "A": (), "E": s((18,), jnp.int32)}
    layers = {kind: {k: s(shape, jnp.float32 if f32 else jnp.bfloat16)
                     for k, (shape, f32) in latent_moe.layer_shapes(
                         cfg, kind).items()} for kind in "CDAE"}
    return s, rows, kept, counters, layers


@pytest.fixture(scope="module")
def feed_turn(one_chip):
    """``turn_step`` at ``16x16@4096``, the widest turn bucket, with the
    cell's 48 sub-blocks of weights, its 6 key/value caches, 18 carry arrays
    and 22 counters, the kept ones donated."""
    from incubator_predictionio_tpu.models import latent_moe
    from incubator_predictionio_tpu.serving.latent_cache import (
        TURN_KEPT,
        turn_step,
    )

    cfg = _feed_cfg()
    kinds = latent_moe.layer_kinds(cfg)
    s, rows, kept, counters, layers = _feed_arguments(one_chip, cfg)
    emb = s((cfg.vocab_size, cfg.d_model), jnp.bfloat16)
    batch, block, ctx = 16, 16, 4096

    def seq_turn_b16_t16_c4096(*args):
        return turn_step(*args, cfg=cfg, form="step", k=16)

    with _chip_kernels():
        compiled = jax.jit(
            seq_turn_b16_t16_c4096, donate_argnums=TURN_KEPT).lower(
            emb, s((rows,), jnp.int32), [layers[k] for k in kinds],
            [kept[k] for k in kinds], [counters[k] for k in kinds],
            s((cfg.d_model,), jnp.float32), emb,
            s((batch, block), jnp.int32),
            s((batch, ctx // cfg.cache_page), jnp.int32),
            s((batch,), jnp.int32), s((batch,), jnp.int32),
            s((batch,), jnp.int32)).compile()
    return cfg, kinds, rows, compiled


def test_feed_turn_program_holds_the_whole_depth_in_place(feed_turn):
    from incubator_predictionio_tpu.models import latent_moe
    from incubator_predictionio_tpu.serving.latent_cache import (
        instruction_scopes,
    )

    cfg, kinds, rows, compiled = feed_turn
    assert "".join(kinds) == "CDCDAE" + "CECECEAE" * 4 + "CECEAECECE"
    assert (kinds.count("C"), kinds.count("A"), kinds.count("D"),
            kinds.count("E")) == (18, 6, 2, 22)
    mem = compiled.memory_analysis()
    caches = 6 * rows * 1024 * 2 + 18 * (cfg.state_slots + 1) * 4096 * 2
    assert mem.alias_size_in_bytes >= caches          # donated, not copied
    # 8.93 GB of weights + 4.33 GB of rows and carries come in; beside them
    # the widest turn's temporaries stay under half a gigabyte
    assert 13.4e9 < mem.argument_size_in_bytes < 13.7e9
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    found = instruction_scopes(text, latent_moe.scopes(cfg))
    assert set(found.values()) == {
        "conv_proj", "conv_mix", "ffn_dense", "gqa_proj", "gqa_attn",
        "moe_router", "moe_experts", "head_topk"}
    # the routed experts' grouped matmuls are the Pallas kernel, three a
    # layer, booked to the experts' scope, and no copy of a layer's 16
    # experts in front of it
    assert len(_expert_kernels(text)) == 3 * 22
    kernels = [name for name in found if name.startswith("grouped_matmul")]
    assert len(kernels) == 3 * 22
    assert all(found[name] == "moe_experts" for name in kernels)
    assert not re.search(r"bf16\[16,(?:2048|1792),\d+\][^\n]* copy\(", text)
    # what makes a convolution's scopes read under their bytes' floor in a
    # trace: its weights are copied to fast memory ahead of their use
    assert re.search(r"bf16\[2048,6144\]\{[^}]*S\(1\)\}", text)


@pytest.mark.parametrize("kind", ["C", "D", "A", "E"])
def test_feed_long_block_letters_compile_for_v5e(one_chip, kind):
    """Each letter's serving step over the longest block (one session of
    4,096 items), its cache donated."""
    from incubator_predictionio_tpu.models import latent_moe

    cfg = _feed_cfg()
    s, rows, kept, counters, layers = _feed_arguments(one_chip, cfg)
    step = latent_moe.step_of(kind, cfg)
    own = s((1, 4096 // cfg.cache_page) if kind == "A" else (1,), jnp.int32)
    with _chip_kernels():
        compiled = jax.jit(
            lambda lw, cache, counters, h, own, offsets, counts: step(
                lw, cache, counters, h, own, offsets, counts, cfg=cfg,
                form="scan"), donate_argnums=(1, 2, 3)).lower(
            layers[kind], kept[kind], counters[kind],
            s((1, 4096, cfg.d_model), jnp.float32), own, s((1,), jnp.int32),
            s((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    held = {"C": (cfg.state_slots + 1) * 4096 * 2, "A": rows * 1024 * 2,
            "D": 0, "E": 0}[kind]
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    for scope in {"C": ("conv_proj", "conv_mix"), "D": ("ffn_dense",),
                  "A": ("gqa_proj", "gqa_attn"),
                  "E": ("moe_router", "moe_experts")}[kind]:
        assert f"/{scope}/" in text, scope
    # 16,384 sorted picks through the kernel, three matrices
    assert len(_expert_kernels(text)) == (3 if kind == "E" else 0)


# -- the window / full attention pattern at the histories cell's widths -----------

def _histories_cfg():
    """The Mellum cell's stack, from the cell's own configuration file, as
    ``benchmarks/engines/seeded_window.algorithm_params`` binds it."""
    from benchmarks.engines import seeded_window
    from incubator_predictionio_tpu.utils.params import params_from_json

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmarks", "configs", "seq-mellum2-12b-ep4.json")
    with open(path) as f:
        c = json.load(f)
    algo = seeded_window.SeededWindowAlgorithm(params_from_json(
        seeded_window.SeededWindowParams,
        seeded_window.algorithm_params(c, 1)))
    return algo.model_config(c["vocab_size"])


def _histories_arguments(one_chip, cfg):
    from incubator_predictionio_tpu.models import latent_moe

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = (cfg.cache_tokens // cfg.cache_page + 1) * cfg.cache_page
    ring = (cfg.state_slots + 1) * cfg.sliding_window
    kept = {"W": {"ring": s((cfg.state_slots + 1, cfg.sliding_window, 1024),
                             jnp.bfloat16)},
            "A": {"kv": s((rows, 1024), jnp.bfloat16)}, "E": {}}
    counters = {"W": (), "A": (), "E": s((18,), jnp.int32)}
    layers = {kind: {k: s(shape, jnp.float32 if f32 else jnp.bfloat16)
                     for k, (shape, f32) in latent_moe.layer_shapes(
                         cfg, kind).items()} for kind in "WAE"}
    return s, rows, ring, kept, counters, layers


@pytest.fixture(scope="module")
def histories_turn(one_chip):
    """``turn_step`` at ``8x16@16384``, the widest turn bucket, with the
    cell's 56 sub-blocks of weights, its 7 key/value caches, 21 rings and 28
    counters, the kept ones donated."""
    from incubator_predictionio_tpu.models import latent_moe
    from incubator_predictionio_tpu.serving.latent_cache import (
        TURN_KEPT,
        turn_step,
    )

    cfg = _histories_cfg()
    kinds = latent_moe.layer_kinds(cfg)
    s, rows, ring, kept, counters, layers = _histories_arguments(one_chip, cfg)
    emb = s((cfg.vocab_size, cfg.d_model), jnp.bfloat16)
    batch, block, ctx = 8, 16, 16384

    def seq_turn_b8_t16_c16384(*args):
        return turn_step(*args, cfg=cfg, form="step", k=16)

    with _chip_kernels():
        compiled = jax.jit(
            seq_turn_b8_t16_c16384, donate_argnums=TURN_KEPT).lower(
            emb, s((rows,), jnp.int32), [layers[k] for k in kinds],
            [kept[k] for k in kinds], [counters[k] for k in kinds],
            s((cfg.d_model,), jnp.float32), emb,
            s((batch, block), jnp.int32),
            s((batch, ctx // cfg.cache_page), jnp.int32),
            s((batch,), jnp.int32), s((batch,), jnp.int32),
            s((batch,), jnp.int32)).compile()
    return cfg, kinds, rows, ring, compiled


def test_histories_turn_program_holds_the_whole_depth_in_place(histories_turn):
    from incubator_predictionio_tpu.models import latent_moe
    from incubator_predictionio_tpu.serving.latent_cache import (
        instruction_scopes,
    )

    cfg, kinds, rows, ring, compiled = histories_turn
    assert "".join(kinds) == "WEWEWEAE" * 7
    assert (kinds.count("W"), kinds.count("A"), kinds.count("E")) == (
        21, 7, 28)
    mem = compiled.memory_analysis()
    caches = 7 * rows * 1024 * 2 + 21 * ring * 1024 * 2
    assert mem.alias_size_in_bytes >= caches          # donated, not copied
    # 7.66 GB of weights + 5.45 GB of rows and rings come in; beside them
    # the widest turn's temporaries stay under a gigabyte
    assert 13.0e9 < mem.argument_size_in_bytes < 13.2e9
    assert mem.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    found = instruction_scopes(text, latent_moe.scopes(cfg))
    assert set(found.values()) == {
        "gqa_proj", "win_attn", "gqa_attn", "moe_router", "moe_experts",
        "head_topk"}
    # the routed experts' grouped matmuls are the Pallas kernel, three a
    # layer, booked to the experts' scope, and no copy of a layer's 16
    # experts, of a whole ring array or of a whole page array in front
    assert len(_expert_kernels(text)) == 3 * 28
    kernels = [name for name in found if name.startswith("grouped_matmul")]
    assert len(kernels) == 3 * 28
    assert all(found[name] == "moe_experts" for name in kernels)
    assert not re.search(r"bf16\[16,(?:2304|896),\d+\][^\n]* copy\(", text)
    assert not re.search(
        rf"bf16\[(?:{ring}|{rows}|33,1024),1024\][^\n]* copy\(", text)


@pytest.mark.parametrize("kind", ["W", "A", "E"])
def test_histories_piece_letters_compile_for_v5e(one_chip, kind):
    """Each letter's serving step over a 2,048-token piece (one session
    against the longest context, 16,384 rows), its cache donated."""
    from incubator_predictionio_tpu.models import latent_moe

    cfg = _histories_cfg()
    s, rows, ring, kept, counters, layers = _histories_arguments(one_chip, cfg)
    step = latent_moe.step_of(kind, cfg)
    own = s((1, 16384 // cfg.cache_page) if kind == "A" else (1,), jnp.int32)
    with _chip_kernels():
        compiled = jax.jit(
            lambda lw, cache, counters, h, own, offsets, counts: step(
                lw, cache, counters, h, own, offsets, counts, cfg=cfg,
                form="band"), donate_argnums=(1, 2, 3)).lower(
            layers[kind], kept[kind], counters[kind],
            s((1, 2048, cfg.d_model), jnp.float32), own, s((1,), jnp.int32),
            s((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    held = {"W": ring * 1024 * 2, "A": rows * 1024 * 2, "E": 0}[kind]
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    for scope in {"W": ("gqa_proj", "win_attn"), "A": ("gqa_proj", "gqa_attn"),
                  "E": ("moe_router", "moe_experts")}[kind]:
        assert f"/{scope}/" in text, scope
    if kind == "W":
        # the band: a chunk of 512 queries against the 1,024 keys before
        # its first and its own, never the piece's 3,072 keys whole
        assert re.search(r"f32\[1,4,8,512,1536\]", text)
        assert not re.search(r"f32\[1,4,8,\d+,3072\]", text)
    if kind == "A":
        # 128 queries a chunk at 16,384 keys: 268 MB of float32 scores
        assert re.search(r"f32\[1,4,8,128,16384\]", text)
    # 16,384 sorted picks through the kernel, three matrices
    assert len(_expert_kernels(text)) == (3 if kind == "E" else 0)


# -- the two-tower train loop (models/two_tower.py) ---------------------------

def test_train_epochs_carries_128_lane_tables_for_v5e(one_chip):
    """A tenth of the train cell's tables (100,000 x 129 / 10,000 x 129, 8
    batches of 65,536): the step loop carries each table as embedding +
    bias, so a row is ONE 128-lane tile where the fused ``[rows, 129]``
    carry held two, and the program's temporaries are a fraction of the
    474 MB the fused carry's padded copies took at these shapes."""
    from incubator_predictionio_tpu.models import two_tower

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"ue": s((100_000, RANK + 1)), "ie": s((10_000, RANK + 1))}
    idx, val = s((8, 65_536), jnp.int32), s((8, 65_536))
    compiled = two_tower._train_epochs.lower(
        p, (s((), jnp.int32), dict(p), dict(p)), idx, idx, val, val,
        0.03, 0.5, 2).compile()
    loops = re.findall(r"^\s*%?while[.\d]* = \((.*?)\) while\(",
                       compiled.as_text(), re.M)
    assert len(loops) == 2   # epochs of steps
    for carry in loops:
        for rows in (100_000, 10_000):
            assert carry.count(f"f32[{rows},128]{{1,0:T(8,128)}}") == 3
            assert carry.count(f"f32[{rows}]{{") == 3      # p, m, v: bias
        assert ",129]" not in carry
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 100e6
    # the fused tables come in and go out, donated
    assert mem.alias_size_in_bytes >= 3 * 110_000 * (RANK + 1) * 4


# -- the index build's clustering (serving/ann.build_ivf) ---------------------

#: What a train verb's peak leaves of the chip's 16 GB: the train cell's
#: 1.1M-row trainer reads ``memory_peak_bytes`` 3.744 GB (PERF.md section 5)
HBM_BESIDE_TRAINER = 16e9 - 3.744e9


@pytest.mark.parametrize("program, rows, partitions", [
    ("ivf_assign", 65_536, 316), ("ivf_update", 65_536, 316),
    ("ivf_assign", 100_000, 316), ("ivf_layout", 100_000, 316),
    ("ivf_assign", 65_536, PARTITIONS), ("ivf_update", 65_536, PARTITIONS),
    ("ivf_assign", N_ITEMS, PARTITIONS), ("ivf_layout", N_ITEMS, PARTITIONS),
])
def test_ivf_build_compiles_for_v5e(one_chip, program, rows, partitions):
    """A Lloyd iteration over the 65,536-row sample (assignment, update),
    the catalog's assignment and the member-order layout, at the train
    cell's catalog and the serve cell's: the ``[rows, partitions]`` float32
    score block (126 MB / 1.3 GB) is never held, and the layout's
    temporaries are the gathered rows and their embedding columns (975 MB at
    476,002 rows), so
    either build fits beside the trainer whose table it reads."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table, cent = s((rows, RANK + 1)), s((partitions, RANK + 1))
    compiled = {
        "ivf_assign": lambda: retrieval.ivf_assign.lower(table, cent, n=rows),
        "ivf_update": lambda: retrieval.ivf_update.lower(
            table, s((rows,), jnp.int32), c=partitions),
        "ivf_layout": lambda: retrieval.ivf_layout.lower(
            table, s((rows,), jnp.int32), quantize=True),
    }[program]().compile()
    mem = compiled.memory_analysis()
    if program == "ivf_assign":
        assert mem.temp_size_in_bytes <= 2 * 4 * (
            retrieval.ASSIGN_ROWS * retrieval.CENTROID_BLOCK)
    assert mem.temp_size_in_bytes <= 4.1 * rows * RANK * 4
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BESIDE_TRAINER
    text = compiled.as_text()
    for scope in {"ivf_assign": ("assign",), "ivf_update": ("update",),
                  "ivf_layout": ("gather", "quantize")}[program]:
        assert f"/{scope}/" in text, scope
