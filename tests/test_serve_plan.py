"""The serve plan (serving/plan.py): pure inputs -> the plan, as one table,
and the contract that nothing between ``prepare_for_serving`` and an answer
reads the environment again."""

import os

import numpy as np
import pytest

from incubator_predictionio_tpu.models.two_tower import (
    TwoTowerConfig,
    TwoTowerMF,
    TwoTowerModel,
)
from incubator_predictionio_tpu.serving import ann, plan as serve_plan
from incubator_predictionio_tpu.serving.plan import (
    HOST_SERVE_MAX_ELEMENTS,
    WarmShape,
)

RANK = 127  # 128 table columns a row
SMALL = HOST_SERVE_MAX_ELEMENTS // (RANK + 1)        # the last host catalog
LARGE = SMALL + 1                                    # the first device one
MIN_ITEMS = 100_000                                  # PIO_RETRIEVAL_MIN_ITEMS

_KNOBS = ("PIO_RETRIEVAL_MODE", "PIO_RETRIEVAL_MIN_ITEMS",
          "PIO_RETRIEVAL_NPROBE", "PIO_SHARD_SERVE", "PIO_SHARD_SERVE_SHARDS",
          "PIO_SHARD_HBM_BUDGET")


def _case(id, env=None, settle=None, raises=None, warm=None, **facts):
    """``facts``: arguments of ``resolve`` over the defaults below, then
    ``settle`` (index storage, prepare_device's answer) where the case has
    an index; ``want``: the plan's fields, ``warm``: its warm list at a
    max_batch of 16."""
    want = {k[5:]: facts.pop(k) for k in list(facts) if k.startswith("want_")}
    return pytest.param(env or {}, facts, settle, want, warm, raises, id=id)


_TWO = {"PIO_RETRIEVAL_MODE": "two_stage"}
_EXACT_16 = [WarmShape(b, "exact", True) for b in (1, 2, 4, 8, 16)]

CASES = [
    # -- the full-catalog scorer: catalog size, quantize, kernel backend
    _case("host_at_the_threshold", n_items=SMALL,
          want_scorer="host-numpy", want_path="host-numpy", want_n_shards=0,
          want_two_stage=False, want_pruned=None, want_nprobe=None,
          want_serve_k=128, warm=[]),
    _case("host_ignores_quantize", n_items=SMALL, quantize=True,
          backend="mosaic", want_scorer="host-numpy"),
    _case("device_bf16_over_the_threshold", n_items=LARGE,
          want_scorer="device-bf16", want_path="device-bf16",
          warm=_EXACT_16),
    _case("host_max_elements_of_the_caller", n_items=200, rank=8,
          host_max_elements=0, serve_k=500,
          want_scorer="device-bf16", want_serve_k=200),
    _case("device_int8_mosaic", n_items=LARGE, quantize=True,
          backend="mosaic", want_scorer="device-int8",
          want_path="device-int8-pallas"),
    _case("device_int8_interpret", n_items=LARGE, quantize=True,
          backend="interpret", want_path="device-int8-pallas-interpret"),
    _case("device_int8_no_kernels", n_items=LARGE, quantize=True,
          backend=None, want_scorer="device-int8",
          want_path="device-int8-jnp"),
    # -- pruned or not: the mode on either side of PIO_RETRIEVAL_MIN_ITEMS
    _case("auto_under_min_items", n_items=MIN_ITEMS - 1,
          want_two_stage=False),
    _case("auto_at_min_items", n_items=MIN_ITEMS, want_two_stage=True),
    _case("auto_follows_the_min_items_knob", n_items=500,
          env={"PIO_RETRIEVAL_MIN_ITEMS": "500"}, want_two_stage=True),
    _case("exact_over_min_items_keeps_a_persisted_index_unused",
          n_items=MIN_ITEMS, env={"PIO_RETRIEVAL_MODE": "exact"},
          settle=("int8", True), want_two_stage=False, want_pruned=None,
          want_index="int8", warm=_EXACT_16),
    _case("two_stage_under_min_items_on_the_host", n_items=SMALL, env=_TWO,
          settle=("fp32", False), want_scorer="host-numpy",
          want_two_stage=True, want_pruned="host-routine",
          warm=[WarmShape(1, "two_stage")]),
    _case("two_stage_without_an_index", n_items=LARGE, env=_TWO,
          settle=(None, False), want_two_stage=True, want_pruned=None),
    _case("nprobe_override", n_items=LARGE,
          env={"PIO_RETRIEVAL_NPROBE": "7"}, want_nprobe=7),
    _case("nprobe_zero_is_unset", n_items=LARGE,
          env={"PIO_RETRIEVAL_NPROBE": "0"}, want_nprobe=None),
    # -- the routine of a pruned catalog: what the index is and where
    _case("int8_index_on_the_device", n_items=LARGE, env=_TWO, quantize=True,
          backend="mosaic", settle=("int8", True),
          want_pruned="device-leg", want_index="int8",
          warm=[WarmShape(1, "two_stage"), WarmShape(16, "two_stage")]
          + _EXACT_16),
    _case("int8_index_overlaid_or_too_large_for_the_device", n_items=LARGE,
          env=_TWO, quantize=True, backend="mosaic", settle=("int8", False),
          want_pruned="host-routine",
          warm=[WarmShape(1, "two_stage"), WarmShape(16, "two_stage")]
          + _EXACT_16),
    _case("fp32_index", n_items=LARGE, env=_TWO, quantize=True,
          backend="interpret", settle=("fp32", False),
          want_pruned="host-routine",
          warm=[WarmShape(1, "two_stage")] + _EXACT_16),
    _case("no_kernels_no_device_leg", n_items=LARGE, env=_TWO, backend=None,
          settle=("int8", True), want_pruned="host-routine",
          warm=[WarmShape(1, "two_stage")] + _EXACT_16),
    # -- sharded: forced, auto, off
    _case("sharded_forced_even_when_small", n_items=SMALL,
          env={"PIO_SHARD_SERVE": "1", "PIO_SHARD_SERVE_SHARDS": "4"},
          want_scorer="sharded", want_n_shards=4,
          want_path="sharded-host-numpy", warm=[]),
    _case("sharded_auto_follows_the_restored_layout", n_items=LARGE,
          env={"PIO_SHARD_SERVE_SHARDS": "4"}, layout_shards=4,
          tables_on_device=True, want_scorer="sharded", want_n_shards=4,
          want_path="sharded-device-bf16", warm=_EXACT_16),
    _case("sharded_auto_leaves_a_small_catalog_alone", n_items=SMALL,
          layout_shards=4, tables_on_device=True, want_scorer="host-numpy",
          want_n_shards=0),
    _case("sharded_auto_by_the_memory_budget", n_items=LARGE,
          env={"PIO_SHARD_SERVE_SHARDS": "2", "PIO_SHARD_HBM_BUDGET": "1MiB"},
          want_scorer="sharded", want_n_shards=2),
    _case("sharded_off", n_items=LARGE, env={"PIO_SHARD_SERVE": "0"},
          layout_shards=4, tables_on_device=True, quantize=True,
          backend="mosaic", want_scorer="device-int8", want_n_shards=0),
    _case("sharded_prunes_on_the_host", n_items=LARGE,
          env={**_TWO, "PIO_SHARD_SERVE": "1", "PIO_SHARD_SERVE_SHARDS": "4"},
          backend="mosaic", settle=("int8", True), want_scorer="sharded",
          want_pruned="host-routine",
          warm=[WarmShape(1, "two_stage"), WarmShape(16, "two_stage")]),
    # -- what does not parse still raises
    _case("invalid_retrieval_mode", n_items=LARGE,
          env={"PIO_RETRIEVAL_MODE": "fast"}, raises="PIO_RETRIEVAL_MODE"),
    _case("invalid_shard_serve", n_items=LARGE,
          env={"PIO_SHARD_SERVE": "maybe"}, raises="PIO_SHARD_SERVE"),
]


@pytest.mark.parametrize("env,facts,settle,want,warm,raises", CASES)
def test_resolve(monkeypatch, env, facts, settle, want, warm, raises):
    for knob in _KNOBS:
        monkeypatch.delenv(knob, raising=False)
    for knob, value in env.items():
        monkeypatch.setenv(knob, value)
    facts = {"rank": RANK, "tables_on_device": False, "layout_shards": 1,
             "backend": None, **facts}
    if raises:
        with pytest.raises(ValueError, match=raises):
            serve_plan.resolve(**facts)
        return
    plan = serve_plan.resolve(**facts)
    assert plan.pruned is None and plan.index is None  # before the index
    if settle is not None:
        plan = plan.settle(*settle)
    assert plan.catalog_rows == facts["n_items"]
    assert plan.backend == facts["backend"]
    assert {k: getattr(plan, k) for k in want} == want
    if warm is not None:
        assert plan.warm_shapes(16) == warm


# -- between prepare and the answer nothing reads the environment -----------

def _towers(n_users=96, n_items=1500, rank=16, seed=3):
    rng = np.random.default_rng(seed)
    return TwoTowerModel(
        user_emb=rng.standard_normal((n_users, rank)).astype(np.float32),
        item_emb=rng.standard_normal((n_items, rank)).astype(np.float32),
        user_bias=np.zeros(n_users, np.float32),
        item_bias=np.zeros(n_items, np.float32),
        mean=0.0, config=TwoTowerConfig(rank=rank))


_ROUTINES = {
    # id: (env, prepare arguments, the plan prepare must arrive at)
    "host": ({}, {}, ("host-numpy", None)),
    "device-bf16": ({}, {"host_max_elements": 0}, ("device-bf16", None)),
    "device-int8": ({"PIO_PALLAS_INTERPRET": "1"},
                    {"host_max_elements": 0, "quantize": True},
                    ("device-int8", None)),
    "device-leg": ({"PIO_PALLAS_INTERPRET": "1",
                    "PIO_RETRIEVAL_MODE": "two_stage"},
                   {"host_max_elements": 0, "quantize": True},
                   ("device-int8", "device-leg")),
    "host-routine": ({"PIO_RETRIEVAL_MODE": "two_stage"}, {},
                     ("host-numpy", "host-routine")),
    "sharded": ({"PIO_SHARD_SERVE": "1", "PIO_SHARD_SERVE_SHARDS": "2",
                 "PIO_RETRIEVAL_MODE": "two_stage"}, {},
                ("sharded", "host-routine")),
}


@pytest.mark.parametrize("routine", list(_ROUTINES))
def test_recommend_batch_reads_no_environment(monkeypatch, routine):
    env, prepare, (scorer, pruned) = _ROUTINES[routine]
    for knob in _KNOBS + ("PIO_PALLAS_INTERPRET",):
        monkeypatch.delenv(knob, raising=False)
    for knob, value in env.items():
        monkeypatch.setenv(knob, value)
    model = _towers().prepare_for_serving(serve_k=16, **prepare)
    assert (model._plan.scorer, model._plan.pruned) == (scorer, pruned)
    model.warmup(max_batch=4)  # a deploy compiles before it answers
    users = np.arange(4, dtype=np.int32)
    forms = ({}, {"exact": True},
             {"exclude": np.arange(5),
              "row_mask": np.zeros((4, model.n_items), np.float32)})
    # (the first excluded batch also builds jax's eager add of the mask)
    want = [TwoTowerMF.recommend_batch(model, users, 10, **kw)
            for kw in forms]

    def read(self, key):
        raise AssertionError(f"os.environ[{key!r}] read while serving")

    engaged = ann.DEVICE_RERANK._default().value
    # Mapping.get, os.getenv and ``in`` all come through __getitem__
    monkeypatch.setattr(type(os.environ), "__getitem__", read)
    with pytest.raises(AssertionError):
        os.environ.get("PIO_RETRIEVAL_MODE")
    got = [TwoTowerMF.recommend_batch(model, users, 10, **kw)
           for kw in forms]
    monkeypatch.undo()
    for (idx, scores), (want_idx, want_scores) in zip(got, want):
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_array_equal(scores, want_scores)
    assert not np.isin(got[2][0], np.arange(5)).any()
    assert ann.DEVICE_RERANK._default().value == engaged + (
        routine == "device-leg")
