"""Two-tower matrix factorization — the MLlib ALS replacement.

The reference recommendation template trains Spark MLlib ALS
(tests/pio_tests/engines/recommendation-engine/src/main/scala/ALSAlgorithm.scala:50-93)
producing a MatrixFactorizationModel. Here: embedding towers trained by
minibatch gradient descent on the mesh (the ALX paper, arxiv 2112.02194,
shards exact ALS the same way — we choose SGD because it lets one jit program
serve explicit *and* implicit feedback and fuses into two MXU matmuls per
step).

TPU mapping:
- user/item embedding tables live sharded over the ``model`` axis (row
  sharding, PartitionSpec("model", None)) — the table is the big tensor here,
  and row sharding keeps gather traffic local-ish while XLA inserts the
  all-gathers it needs;
- the rating minibatch is sharded over ``data``; gradient psum rides ICI;
- per-step compute is two gathers + fused dot-products in bfloat16 on the
  MXU, with float32 accumulation for the loss and the adam state;
- scoring a user against the full catalog is one [k] × [k, n_items] matmul +
  ``lax.top_k`` — the serving path stays on-device end to end.

Static shapes: triples padded to a whole number of global batches with
zero-weight rows.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from incubator_predictionio_tpu.obs import profile as _profile
from incubator_predictionio_tpu.obs.trace import span
from incubator_predictionio_tpu.parallel.mesh import (
    MeshContext,
    kernel_backend,
)
from incubator_predictionio_tpu.serving import plan as serve_plan
from incubator_predictionio_tpu.serving.plan import (
    HOST_SERVE_MAX_ELEMENTS,
    serve_bucket,
)

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    rank: int = 32                  # ALS "rank" (ALSAlgorithm.scala params)
    learning_rate: float = 3e-2
    reg: float = 1e-4               # ALS "lambda"
    epochs: int = 20                # ALS "numIterations"
    batch_size: int = 8192          # global batch
    implicit_negatives: int = 0     # >0 → implicit mode with sampled negatives
    seed: int = 0
    # mid-training checkpoint/resume (utils/checkpoint.py); 0 = off
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0       # epochs between checkpoints
    checkpoint_keep: int = 3
    # adam moment STORAGE dtype ("float32" | "bfloat16"): bf16 moments cut
    # the dense-adam HBM traffic from 6 to 4 fp32-equivalent table passes
    # per step (~33% on the bandwidth-bound scaled config); math stays fp32
    # (utils/optim.adam_apply; parity: tests/test_optim_parity.py)
    adam_moments_dtype: str = "float32"
    # model finalize: "host" pulls the trained tables to host numpy (one
    # full-table transfer); "device" keeps them resident as jax
    # Arrays (persisted as sharded orbax checkpoints, served without ever
    # touching host); "auto" picks device for single-process runs whose
    # CATALOG exceeds HOST_SERVE_MAX_ELEMENTS — the same criterion the
    # serving path uses, so device residency and device serving agree
    gather: str = "auto"


@dataclasses.dataclass
class TwoTowerModel:
    """user/item factor tables + biases + global mean.

    Two residency modes:

    - **host** (the reference-shaped path): ``user_emb``/``item_emb``/biases
      are host numpy; pickles into MODELDATA like Kryo blobs do.
    - **device** (``TwoTowerConfig.gather="device"``/big-table auto): the
      fused padded tables stay resident as jax Arrays in ``_tables``
      ({"ue": [nu_p, k+1], "ie": [ni_p, k+1]}, possibly "model"-axis
      sharded); the host fields are ``None`` until :meth:`ensure_host`.
      Persistence goes through sharded orbax checkpoints
      (templates/recommendation.py RecModel.save), never a host gather.
    """

    user_emb: Optional[np.ndarray] = None    # [n_users, k]
    item_emb: Optional[np.ndarray] = None    # [n_items, k]
    user_bias: Optional[np.ndarray] = None   # [n_users]
    item_bias: Optional[np.ndarray] = None   # [n_items]
    mean: float = 0.0
    config: TwoTowerConfig = dataclasses.field(default_factory=TwoTowerConfig)

    _tables = None  # device-resident fused tables (device mode)
    _n_users = 0  # real (unpadded) row counts in device mode
    _n_items = 0
    # the plan's device scorer's catalog: (item_embᵀ bf16, item_bias, zero
    # mask), or int8-quantized (items, scales, bias, mask; pallas kernel)
    _device_items = None
    _device_users = None  # (user_emb bf16, user_bias) — gathered inside jit
    _host_items = None  # small-catalog host fast path (item_embᵀ, item_bias)
    # the serve plan (serving/plan.py): which scorer and which pruned routine
    # answer and the static top-k the executables are compiled for, fixed by
    # prepare_for_serving; None until then. Derived state
    _plan: Optional[serve_plan.ServePlan] = None
    # two-stage retrieval index (serving/ann.py). Unlike the device handles
    # it IS host numpy and rides default pickling, so a persisted model
    # redeploys without re-clustering the catalog
    _ivf = None
    # sharded serving state (sharding/serve.py): per-shard top-k + merge
    # replaces the single-host scorers when the model-axis layout is a win.
    # Derived at prepare time — never serialized (deploy rebuilds it)
    _sharded = None
    # per-shard IVF partitions (one slim-pickling IVFIndex per shard) and
    # the training shard layout — both host-picklable, both persisted so a
    # sharded redeploy skips the per-shard re-cluster
    _shard_ivf = None
    _shard_spec = None

    @property
    def device_resident(self) -> bool:
        return self._tables is not None

    def ensure_host(self) -> "TwoTowerModel":
        """Materialize the host numpy views (one full-table device→host pull
        — the transfer device mode exists to avoid; only consumers that
        genuinely need host arrays, e.g. cosine-similarity model builds or
        default pickling, should ever land here)."""
        if self.user_emb is not None or self._tables is None:
            return self
        from incubator_predictionio_tpu.sharding import shard_metrics

        shard_metrics.FULL_GATHERS.inc()
        k = self.config.rank
        with span("deploy.ensure_host"):
            host = jax.device_get(self._tables)
            self.user_emb = np.ascontiguousarray(host["ue"][: self._n_users, :k])
            self.user_bias = np.ascontiguousarray(host["ue"][: self._n_users, k])
            self.item_emb = np.ascontiguousarray(host["ie"][: self._n_items, :k])
            self.item_bias = np.ascontiguousarray(host["ie"][: self._n_items, k])
        return self

    def __getstate__(self):
        # default pickling (MODELDATA blob) always ships host arrays; device
        # handles and serving buffers never serialize — deploy rebuilds them
        # (the sharded serving state may hold device arrays; its host-only
        # inputs — _shard_ivf, _shard_spec — do persist)
        self.ensure_host()
        return {k: v for k, v in self.__dict__.items()
                if k not in ("_tables", "_device_items", "_device_users",
                             "_host_items", "_sharded", "_plan")}

    def prepare_for_serving(
        self, quantize: bool = False, serve_k: int = 128,
        host_max_elements: Optional[int] = None, build_index: bool = True,
    ) -> "TwoTowerModel":
        """Make serving state resident for the query hot path.

        Catalogs up to :data:`HOST_SERVE_MAX_ELEMENTS` serve from host numpy;
        bigger ones go device-resident, and ``quantize=True`` additionally
        stores the catalog int8 row-quantized and scores through the fused
        Pallas retrieval kernel (ops/retrieval.py) — 4× less HBM for the item
        table and a faster score pass on TPU.

        ``serve_k`` fixes the static top-k the device executables compute:
        queries asking ``num ≤ serve_k`` share ONE executable per batch bucket
        (results sliced host-side), so per-query ``num`` never recompiles.

        When the plan prunes this catalog (``PIO_RETRIEVAL_MODE``,
        serving/plan.py) this also builds — or reuses, when a persisted
        index's build key still matches — the IVF partition the coarse
        stage probes; the exact buffers above stay resident as the fallback
        and recall oracle. ``build_index=False`` opts out — for callers
        (the ecommerce/similarity templates) whose serving path never goes
        through :meth:`TwoTowerMF.recommend_batch` and would pay the
        clustering for nothing.

        ``self._plan`` is final when this returns: warm-up, ``serving_info``
        and every dispatch read it (docs/serving.md "How the serve path is
        chosen")."""
        plan = self._resolve_plan(quantize, serve_k, host_max_elements)
        with span("deploy.quantize", quantize=quantize):
            self._prepare_scoring(plan)
            # the device-to-device slice / cast / quantize is dispatched
            # asynchronously: bill it here, not to the first warm-up bucket
            _profile.fence(self._device_users, self._device_items)
        on_device = False
        if build_index:
            with span("deploy.index"):
                self._prepare_index()
                if self._ivf is not None and plan.wants_device_leg:
                    # towers and kernels are on a device: the index's int8
                    # tables join them, and two-stage retrieval runs as one
                    # device leg (serving/ann.IVFIndex.search_device)
                    on_device = self._ivf.prepare_device()
        live = [i for i in ([self._ivf] if self._sharded is None
                            else self._sharded.ivf or ()) if i is not None]
        index = None if not live else (
            "int8" if any(i.quantized for i in live) else "fp32")
        self._plan = plan.settle(index, on_device)
        return self

    def _resolve_plan(
        self, quantize: bool = False, serve_k: int = 128,
        host_max_elements: Optional[int] = None,
    ) -> serve_plan.ServePlan:
        """:func:`serving.plan.resolve` over this model's facts."""
        from incubator_predictionio_tpu.sharding import serve as shard_serve

        return serve_plan.resolve(
            n_items=self.n_items, rank=self.config.rank,
            tables_on_device=self.device_resident and self.user_emb is None,
            layout_shards=shard_serve.layout_shards_of(self),
            backend=kernel_backend(), quantize=quantize, serve_k=serve_k,
            host_max_elements=host_max_elements)

    def _prepare_index(self) -> None:
        """Build/reuse the two-stage IVF partition (serving/ann.py)."""
        from incubator_predictionio_tpu.serving import ann

        if not ann.two_stage_enabled(self.n_items):
            # keep any persisted index around: flipping the mode knob back
            # shouldn't force a re-cluster on the next prepare
            return
        if self._sharded is not None:
            # composed sharded two-stage: each shard clusters its LOCAL
            # rows (shard-at-a-time pulls — the full item table is never
            # materialized on one host); persisted per-shard indexes are
            # reused when their build keys still match
            self._shard_ivf = self._sharded.ensure_ivf(
                self, persisted=self._shard_ivf)
            return
        from incubator_predictionio_tpu.sharding import serve as shard_serve

        shard_ivf = shard_serve.train_time_shard_ivf(
            self, persisted=self._shard_ivf)
        if shard_ivf is not None:
            # train-time build for a model that will SERVE sharded: the
            # per-shard clustering persists with the model, so redeploys
            # skip the re-cluster — and the full table is never gathered
            self._shard_ivf = shard_ivf
            return
        key = ann.build_key(self.n_items)
        if self._ivf is not None and self._ivf.matches(key):
            if not self._ivf.hydrated:
                # persisted slim (clustering only): one O(N) gather rebuilds
                # the member-order rerank tables — the k-means is skipped
                self._ivf.rehydrate(*self._host_item_table())
            return
        if self.item_emb is None:
            # the build reads the item table where it lives: a
            # device-resident model's fused rows go in as they are, and of
            # the catalog only the finished member-order tables come back
            self._ivf = ann.build_ivf_fused(
                self._tables["ie"], self._n_items, key)
        else:
            self._ivf = ann.build_ivf(self.item_emb, self.item_bias, key=key)

    def _host_item_table(self) -> tuple[np.ndarray, np.ndarray]:
        """Host ``(item_emb, item_bias)`` WITHOUT materializing the full
        host views: ``ensure_host`` would also pull the user table and set
        ``user_emb``, flipping a device-gather model off its
        device-to-device serving-prep fast path for good. The index build
        only needs the item side."""
        if self.item_emb is not None:
            return (np.asarray(self.item_emb, np.float32),
                    np.asarray(self.item_bias, np.float32))
        from incubator_predictionio_tpu.sharding import shard_metrics

        shard_metrics.FULL_GATHERS.inc()
        k = self.config.rank
        host_ie = np.asarray(jax.device_get(self._tables["ie"]))
        return (np.ascontiguousarray(host_ie[: self._n_items, :k],
                                     dtype=np.float32),
                np.ascontiguousarray(host_ie[: self._n_items, k],
                                     dtype=np.float32))

    def _prepare_scoring(self, plan: serve_plan.ServePlan) -> None:
        """Make the plan's full-catalog scorer resident."""
        # re-preparation switches paths cleanly: clear every serving buffer
        # first (a stale _host_items would shadow a requested device path)
        self._plan = None
        self._host_items = None
        self._device_items = None
        self._device_users = None
        self._sharded = None
        quantize = plan.scorer == serve_plan.DEVICE_INT8
        if plan.scorer == serve_plan.SHARDED:
            # per-shard top-k + cross-shard merge straight from the
            # model-axis layout (sharding/serve.py): device-resident models
            # derive the state device-to-device from the sharded tables
            # (the item table never visits the host); host models split
            # into virtual shard blocks (the CPU-parity twin)
            from incubator_predictionio_tpu.sharding.serve import (
                ShardedServing,
            )

            serve_k = plan.serve_k or min(128, self.n_items)
            if plan.tables_on_device:
                self._sharded = ShardedServing.build_device(
                    self._tables, self._n_users, self._n_items,
                    self.config.rank, self.mean, serve_k,
                    min(plan.n_shards, len(jax.devices())))
            else:
                self._sharded = ShardedServing.build_host(
                    np.asarray(self.item_emb, np.float32),
                    np.asarray(self.item_bias, np.float32),
                    self.n_users, self.mean, serve_k, plan.n_shards)
            return
        if plan.scorer == serve_plan.HOST_NUMPY:
            self.ensure_host()  # no-op unless forced device mode on tiny tables
            self._host_items = (
                np.ascontiguousarray(np.asarray(self.item_emb, np.float32).T),
                np.asarray(self.item_bias, np.float32),
            )
            return
        if self.device_resident and self.user_emb is None:
            # device→device: slice/cast the resident fused tables — serving
            # state is derived without a single host round trip (the whole
            # point of gather="device")
            k = self.config.rank
            ue, ie = self._tables["ue"], self._tables["ie"]
            self._device_users = (
                ue[: self._n_users, :k].astype(jnp.bfloat16),
                ue[: self._n_users, k].astype(jnp.float32),
            )
            item_emb = ie[: self._n_items, :k]
            item_bias = ie[: self._n_items, k]
            if quantize:
                from incubator_predictionio_tpu.ops.retrieval import (
                    quantize_catalog_device,
                )

                self._device_items = tuple(
                    quantize_catalog_device(item_emb, item_bias))
            else:
                self._device_items = (
                    item_emb.T.astype(jnp.bfloat16),
                    item_bias.astype(jnp.float32),
                    jnp.zeros(self._n_items, jnp.float32),
                )
            return
        self._device_users = (
            jax.device_put(np.asarray(self.user_emb, np.float32).astype(jnp.bfloat16)),
            jax.device_put(np.asarray(self.user_bias, np.float32)),
        )
        if quantize:
            from incubator_predictionio_tpu.ops.retrieval import (
                pad_catalog,
                quantize_rows,
            )

            items_q, scales = quantize_rows(np.asarray(self.item_emb))
            base_mask = np.zeros(self.n_items, np.float32)
            items_q, scales, bias, mask = pad_catalog(
                items_q, scales, np.asarray(self.item_bias, np.float32), base_mask
            )
            self._device_items = tuple(
                jax.device_put(v) for v in (items_q, scales, bias, mask)
            )
        else:
            self._device_items = (
                jax.device_put(
                    np.ascontiguousarray(
                        np.asarray(self.item_emb, np.float32).T
                    ).astype(jnp.bfloat16)
                ),
                jax.device_put(np.asarray(self.item_bias, np.float32)),
                jax.device_put(np.zeros(self.n_items, np.float32)),
            )

    def warmup(self, max_batch: int = 64) -> int:
        """Pre-compile the serving executable for every batch bucket up to
        ``max_batch`` (deploy-time cost, so no live query ever waits on XLA):
        one ``deploy.warmup.bucket`` span per dispatch shape of the plan's
        warm list (serving/plan.ServePlan.warm_shapes). Returns the number
        of buckets warmed, the pruned path's prime not counted (0 on the
        host fast path — nothing compiles there)."""
        plan = self._plan or self.prepare_for_serving()._plan
        if plan.pruned is not None and self._ivf is not None:
            # the two-stage host routine reads the towers on the host: pull
            # them under their own span (deploy.ensure_host), not inside
            # the first warm-up dispatch or the first filtered live batch
            self.ensure_host()
        shapes = plan.warm_shapes(max_batch)
        k = max(plan.serve_k, 1)
        with span("deploy.warmup", max_batch=max_batch):
            for shape in shapes:
                users = np.zeros(shape.bucket, np.int32)
                with span("deploy.warmup.bucket", bucket=shape.bucket,
                          path=shape.path):
                    if shape.path == "two_stage":
                        TwoTowerMF.recommend_batch(self, users, k)
                        continue
                    # exact=True: under a pruning plan the full-catalog
                    # executables, its fallback, would else compile on the
                    # first live query that needs them
                    TwoTowerMF.recommend_batch(self, users, k, exact=True)
                    if shape.row_mask:
                        # the rule-filtered variant ([b, n] row mask) is a
                        # distinct executable
                        TwoTowerMF.recommend_batch(
                            self, users, k, exact=True, row_mask=np.zeros(
                                (shape.bucket, self.n_items), np.float32))
        return len(shapes) - (plan.pruned is not None)

    @property
    def n_items(self) -> int:
        return self._n_items if self.item_emb is None else self.item_emb.shape[0]

    @property
    def n_users(self) -> int:
        return self._n_users if self.user_emb is None else self.user_emb.shape[0]

    def with_row_updates(
        self,
        user_rows: Optional[dict] = None,
        item_rows: Optional[dict] = None,
    ) -> "TwoTowerModel":
        """A NEW model with the given fused ``[rank+1]`` rows scattered in
        — the streaming delta-apply primitive (docs/streaming.md).

        Build-beside semantics: the receiver is NEVER mutated (it may be
        the live serving model, or the probation-pinned previous one), so
        the tables are copied, rows assigned, and the caller swaps the new
        model in atomically — serving can't observe a half-applied table.

        Two-stage index staleness: item rows that moved are overlaid on
        the IVF index (:meth:`serving.ann.IVFIndex.with_updated_rows`) so
        the pruned path rescopes them with CURRENT values; past
        ``PIO_STREAM_STALE_REBUILD_FRAC`` of the catalog stale, the index
        is re-clustered from the updated table instead.

        Sharded models route each row to its OWNING shard
        (sharding/serve.py) — only that shard's arrays (and its IVF
        overlay) rebuild; a device-resident sharded model never pulls its
        tables to host for a delta."""
        if self._sharded is not None and self.user_emb is None:
            return self._with_row_updates_sharded(user_rows, item_rows)
        self.ensure_host()
        k = self.config.rank
        new = TwoTowerModel(
            user_emb=np.array(self.user_emb, np.float32, copy=True),
            item_emb=np.array(self.item_emb, np.float32, copy=True),
            user_bias=np.array(self.user_bias, np.float32, copy=True),
            item_bias=np.array(self.item_bias, np.float32, copy=True),
            mean=self.mean,
            config=self.config,
        )

        def scatter(emb, bias, rows, n):
            for idx, row in rows.items():
                idx = int(idx)
                if not (0 <= idx < n):
                    raise ValueError(f"delta row index {idx} outside "
                                     f"[0, {n})")
                row = np.asarray(row, np.float32)
                if row.shape != (k + 1,):
                    raise ValueError(
                        f"delta row shape {row.shape} != ({k + 1},)")
                emb[idx] = row[:k]
                bias[idx] = row[k]

        if user_rows:
            scatter(new.user_emb, new.user_bias, user_rows, new.n_users)
        if item_rows:
            scatter(new.item_emb, new.item_bias, item_rows, new.n_items)
        if self._ivf is not None:
            if item_rows:
                new._ivf = self._updated_index(new, item_rows)
            else:
                new._ivf = self._ivf  # shared read-only: nothing moved
        if self._sharded is not None:
            # host-block sharded serving: route the rows to their owning
            # shard's blocks/IVF overlay; untouched shards stay shared.
            # _shard_ivf only follows when serving actually carries per-
            # shard indexes — with two-stage currently off the persisted
            # clustering must survive for a later mode flip
            new._sharded = self._sharded.with_row_updates(
                user_rows or {}, item_rows or {})
            new._shard_ivf = (new._sharded.ivf
                              if new._sharded.ivf is not None
                              else self._shard_ivf)
            new._shard_spec = self._shard_spec
            new._plan = self._plan
        return new

    def _with_row_updates_sharded(
        self,
        user_rows: Optional[dict] = None,
        item_rows: Optional[dict] = None,
    ) -> "TwoTowerModel":
        """Build-beside delta apply for a device-resident sharded model:
        rows scatter into copies of the sharded tables ON DEVICE (XLA
        routes each row to its owner — batch-sized traffic only) and the
        serving state updates through the owning shard; the receiver keeps
        serving its own arrays untouched."""
        import jax.numpy as jnp

        from incubator_predictionio_tpu.sharding.serve import _set_rows_fn

        new = TwoTowerModel(mean=self.mean, config=self.config)
        new._n_users, new._n_items = self._n_users, self._n_items
        new._plan = self._plan
        new._shard_spec = self._shard_spec
        new._sharded = self._sharded.with_row_updates(
            user_rows or {}, item_rows or {})
        if self._tables is not None:
            # keep the persistable tables coherent with what serving
            # answers (a later save/pickle must not resurrect old rows).
            # No re-validation here: ShardedServing.with_row_updates above
            # already range/width-checked every row — one checker, one
            # error message
            tables = dict(self._tables)
            for name, rows_dict in (("ue", user_rows), ("ie", item_rows)):
                if not rows_dict:
                    continue
                ids, rows = _stacked_rows(rows_dict)
                tables[name] = _set_rows_fn()(
                    tables[name], jnp.asarray(ids, jnp.int32),
                    jnp.asarray(rows))
            new._tables = tables
        if item_rows and new._tables is not None:
            # past the staleness threshold a shard re-clusters from the
            # UPDATED tables (the overlay must not grow without bound)
            new._sharded.rebuild_stale_ivf(new)
        new._shard_ivf = (new._sharded.ivf if new._sharded.ivf is not None
                          else self._shard_ivf)
        if self._ivf is not None:
            # a persisted whole-catalog index survives for a later
            # retrieval/sharding mode flip — with the moved rows overlaid
            # so an in-process flip never serves pre-delta embeddings
            # (the host path's _updated_index semantics, minus its
            # rebuild-past-threshold branch, which needs host towers)
            if item_rows:
                ids, rows = _stacked_rows(item_rows)
                k = self.config.rank
                new._ivf = self._ivf.with_updated_rows(
                    ids, rows[:, :k], rows[:, k])
            else:
                new._ivf = self._ivf
        return new

    def _updated_index(self, new: "TwoTowerModel", item_rows: dict):
        """Overlay the moved item rows on the shared IVF index, or rebuild
        past the staleness threshold."""
        import os as _os

        from incubator_predictionio_tpu.serving import ann

        ids, rows = _stacked_rows(item_rows)
        k = self.config.rank
        overlaid = self._ivf.with_updated_rows(ids, rows[:, :k], rows[:, k])
        frac = float(_os.environ.get("PIO_STREAM_STALE_REBUILD_FRAC", "0.25"))
        if overlaid.stale_fraction > frac and (
                self._plan or self._resolve_plan()).two_stage:
            return ann.build_ivf(
                np.asarray(new.item_emb, np.float32),
                np.asarray(new.item_bias, np.float32),
                key=ann.build_key(new.n_items))
        return overlaid

    def serving_info(self) -> dict:
        """Which serving path this model runs (status-page observability):
        the serve plan, as prepare fixed it."""
        plan = self._plan
        if self._ivf is not None:
            index = self._ivf.stats()
        elif self._sharded is not None and self._sharded.ivf:
            index = [i.stats() if i is not None else None
                     for i in self._sharded.ivf]
        else:
            index = None
        pruned = plan.pruned if plan is not None else None
        return {"path": plan.path if plan is not None else "unprepared",
                "serve_k": plan.serve_k if plan is not None else 0,
                "catalog_rows": self.n_items,
                "retrieval_mode": "two_stage" if pruned else "exact",
                "pruned": pruned,
                "sharding": (self._sharded.info()
                             if self._sharded is not None else None),
                "index": index}

    def shard_info(self) -> dict:
        """Shard layout for ``pio-tpu shards``: the live serving layout
        when sharded serving is active, else the training-layout record
        (or the single-chip plan) plus what the current simulated HBM
        budget implies."""
        from incubator_predictionio_tpu.sharding.table import (
            ShardSpec,
            hbm_budget,
            requires_sharding,
        )

        k = self.config.rank
        if self._sharded is not None:
            info = self._sharded.info()
            info["sharded"] = True
            return info
        spec = self._shard_spec or {
            "ue": ShardSpec("ue", self.n_users, k + 1, 1),
            "ie": ShardSpec("ie", self.n_items, k + 1, 1),
        }
        return {
            "sharded": False,
            "n_shards": spec["ie"].n_shards,
            "items": spec["ie"].to_dict(),
            "users": spec["ue"].to_dict(),
            "hbm_budget": hbm_budget(),
            "requires_sharding": requires_sharding(
                self.n_items, k + 1, self.config.adam_moments_dtype),
        }


def _stacked_rows(rows: dict) -> tuple[np.ndarray, np.ndarray]:
    """A delta's ``{row index: fused row}`` as ``(ids ascending, [n, k+1])``."""
    ids = np.asarray(sorted(int(i) for i in rows), np.int64)
    return ids, np.stack([np.asarray(rows[int(i)], np.float32) for i in ids])


class TwoTowerMF:
    def __init__(self, config: TwoTowerConfig = TwoTowerConfig()):
        self.config = config

    def fit(
        self,
        ctx: MeshContext,
        users: np.ndarray,     # [n] int32 user indices
        items: np.ndarray,     # [n] int32 item indices
        ratings: np.ndarray,   # [n] float32
        n_users: int,
        n_items: int,
        rows_are_local: bool = False,
    ) -> TwoTowerModel:
        """``rows_are_local=True``: the given triples are only THIS process's
        entity-disjoint shard (indices already global); batches are assembled
        per process and joined into global arrays via
        ``make_array_from_process_local_data`` — host memory is data/P per
        process instead of a full replica (reference counterpart: RDD
        partition reads, PEvents.scala:38)."""
        cfg = self.config
        n = len(users)
        if not (len(items) == len(ratings) == n):
            raise ValueError("users/items/ratings must be equal length")

        # the fit's phases are spans (obs/trace.py): train.fit.order|h2d|
        # init|compute|gather land in /metrics, /profile.json, the trace
        # ring and the profiler's timeline; model.timings reads them back.
        # A fit orders twice: the host fixes each batch's composition (the
        # first train.fit.order), the device sorts within the batches once
        # they are staged (the second, which carries n_batches and batch)
        if rows_are_local and ctx.process_count > 1:
            with span("train.fit.h2d") as sp_h2d:
                ub, ib, rb, wb, mean = self._stage_local(
                    ctx, users, items, ratings)
                jax.block_until_ready((ub, ib, rb, wb))
            t_stage = sp_h2d.duration
        else:
            with span("train.fit.order") as sp_order:
                mean = float(ratings.mean()) if n else 0.0
                global_batch = ctx.pad_to_batch_multiple(
                    min(cfg.batch_size, max(n, 1)))
                n_batches = max(1, (n + global_batch - 1) // global_batch)
                n_pad = n_batches * global_batch
                # the rng's two draws, in this order, fix which triples
                # share a batch: rng.permutation(n), which IS arange +
                # shuffle, here in place in the one buffer (a 160 MB result
                # copied into a second one costs more than the shuffle),
                # then the padding, the tail of the last batch
                rng = np.random.default_rng(cfg.seed)
                order = np.arange(n_pad)
                rng.shuffle(order[:n])
                order[n:] = rng.integers(0, max(n, 1), n_pad - n)

            with span("train.fit.h2d") as sp_h2d:
                blocks = _stage_blocks(
                    ctx, order, global_batch, np.asarray(users, np.int32),
                    np.asarray(items, np.int32),
                    ratings.astype(np.float32) - mean)
                # phase fence: staging transfers (h2d) must bill to this
                # span, not to whichever later one first blocks on the batches
                jax.block_until_ready(blocks)
            with span("train.fit.order", n_batches=n_batches,
                      batch=global_batch) as sp_sort:
                ub, ib, rb, wb = _order_blocks(ctx, blocks, n, global_batch)
                jax.block_until_ready((ub, ib, rb, wb))
            t_stage = sp_order.duration + sp_h2d.duration + sp_sort.duration
        with span("train.fit.init") as sp_init:
            key = jax.random.key(cfg.seed)
            ku, ki = jax.random.split(key)
            scale = 1.0 / np.sqrt(cfg.rank)
            # biases live as the LAST COLUMN of each table: the fused row is
            # the stored and served layout (checkpoint, deploy, row updates,
            # the serving gathers fetch vector + bias in one row). What the
            # step loop carries is _carry_cols' choice; it splits and joins
            # inside _train_epochs and nothing here sees it.
            #
            # The tables materialize through ShardedTable (sharding/table.py):
            # rows padded to the model-axis multiple and row-sharded via
            # NamedSharding, init ON DEVICE with per-shard keys directly into
            # that layout (a 1M×129 table round-tripped through the host costs
            # ~GB of transfer for pure noise), and PIO_SHARD_HBM_BUDGET
            # enforced per shard — the simulated stand-in for a real chip's
            # OOM, so a CPU dryrun can prove the doesn't-fit-one-chip case.
            from incubator_predictionio_tpu.sharding.table import ShardedTable

            ut = ShardedTable.init_train(
                ctx, "ue", n_users, cfg.rank, ku, scale, cfg.adam_moments_dtype)
            it = ShardedTable.init_train(
                ctx, "ie", n_items, cfg.rank, ki, scale, cfg.adam_moments_dtype)
            params = {"ue": ut.array, "ie": it.array}
            # jitted init: multi-process-safe (optimizer state inherits the
            # params' global shardings instead of materializing host-side)
            from incubator_predictionio_tpu.utils.optim import adam_tree_init

            opt_state = adam_tree_init(params, cfg.adam_moments_dtype)

            from incubator_predictionio_tpu.utils.checkpoint import checkpointed_epochs

            # phase fence: on-device table/moment init bills to init
            jax.block_until_ready((params, opt_state))
        cols = _carry_cols(cfg.rank)
        with span("train.fit.compute", carry_cols=cols,
                  carry_pad_pct=round(_carry_pad_pct(cols), 1)) as sp_compute:
            # distributed members checkpoint by owned slice and fence-check at
            # every chunk boundary (DistContext.dist_hooks); a plain ctx has no
            # hooks and trains exactly as before
            dist = getattr(ctx, "dist_hooks", None)
            params, opt_state, loss = checkpointed_epochs(
                cfg.checkpoint_dir, cfg.checkpoint_every, cfg.checkpoint_keep,
                cfg.epochs, params, opt_state, ctx.mesh,
                lambda p, o, n: _train_epochs(
                    p, o, ub, ib, rb, wb, cfg.learning_rate, cfg.reg, n
                ),
                factory=None if dist is None else dist.checkpointer_factory,
                on_chunk=None if dist is None else dist.on_chunk,
            )
            if loss is None:
                loss = np.inf
            else:
                loss = float(loss)  # blocks: the train schedule is done here
        with span("train.fit.gather") as sp_gather:
            # auto keys on the CATALOG size — the same criterion
            # prepare_for_serving uses to pick host vs device serving. Keying on
            # user+item would keep a user-heavy/small-catalog model on device
            # only for deploy to take the host serving path and pay the full
            # user-table pull anyway (plus a pointless giant checkpoint)
            # UNPADDED count: prepare_for_serving's host-path check keys on
            # n_items, so keying auto on the padded ni_p would leave catalogs in
            # the padding band device-resident (orbax checkpoint and all) only
            # for deploy to take the host path anyway (round-4 advisor finding)
            item_elems = n_items * (cfg.rank + 1)
            keep_device = cfg.gather == "device" or (
                cfg.gather == "auto" and item_elems > HOST_SERVE_MAX_ELEMENTS)
            if keep_device and ctx.process_count > 1:
                # persistence is primary-only (core_workflow.py) but an orbax
                # save of process-spanning arrays would need every process —
                # multi-process runs keep the collective host gather
                keep_device = False
            if keep_device:
                # device-resident finalize: the trained tables never leave HBM.
                # block_until_ready only drains the train schedule — the
                # full-table device→host transfer is gone entirely
                jax.block_until_ready(params)
                model = TwoTowerModel(mean=mean, config=cfg)
                model._tables = {"ue": params["ue"], "ie": params["ie"]}
                model._n_users = n_users
                model._n_items = n_items
                # layout record: what `pio-tpu shards` and sharded serving read
                model._shard_spec = {"ue": ut.spec, "ie": it.spec}
            else:
                # host gather (collective when multi-process); this transfer
                # can dwarf the train loop for big tables, so the phases are
                # reported separately on the model
                host = ctx.host_gather(params)
                model = TwoTowerModel(
                    user_emb=host["ue"][:n_users, :cfg.rank],
                    item_emb=host["ie"][:n_items, :cfg.rank],
                    user_bias=host["ue"][:n_users, cfg.rank],
                    item_bias=host["ie"][:n_items, cfg.rank],
                    mean=mean,
                    config=cfg,
                )
        model.final_loss = float(loss)
        # exactly these four keys (the benchmark sums them): stage = every
        # order + h2d span of the fit; full precision lives in the spans
        model.timings = {
            "stage_sec": round(t_stage, 4),
            "init_sec": round(sp_init.duration, 4),
            "train_sec": round(sp_compute.duration, 4),
            "gather_sec": round(sp_gather.duration, 4),
        }
        n_b, g_batch = int(ub.shape[0]), int(ub.shape[1])
        n_params = (n_users + n_items) * (cfg.rank + 1)
        # placement as the owning process sees it: table rows per device
        # (trained params alias the init layout, so ut/it still describe it)
        logger.info(
            "two-tower fit: final loss %.6f after %d epochs of %d steps/epoch "
            "at batch %d; timings %s; table shards %s",
            loss, cfg.epochs, n_b, g_batch, model.timings, json.dumps({
                name: {str(s.device.id): int(s.data.shape[0])
                       for s in params[name].addressable_shards}
                for name in ("ue", "ie")}))
        # the analytic-flops MFU gauge (docs/observability.md "Profiling")
        _profile.record_training_step(
            cfg.epochs * n_b * (12 * cfg.rank * g_batch + 12 * n_params),
            sp_compute.duration)
        return model

    def _stage_local(self, ctx: MeshContext, users, items, ratings):
        """Per-process batch staging for entity-sharded input rows."""
        cfg = self.config
        n_local = len(users)
        procs = ctx.process_count
        # one metadata exchange: (row count, rating sum) per process
        stats = ctx.allgather_obj(
            (n_local, float(np.asarray(ratings, np.float64).sum())))
        n_global = sum(s[0] for s in stats)
        mean = (sum(s[1] for s in stats) / n_global) if n_global else 0.0
        global_batch = ctx.pad_to_batch_multiple(
            min(cfg.batch_size, max(n_global, 1)))
        if global_batch % procs:
            raise ValueError(
                f"global batch {global_batch} not divisible by "
                f"{procs} processes")
        b_local = global_batch // procs
        n_batches = max(
            1, max((s[0] + b_local - 1) // b_local for s in stats))
        n_pad = n_batches * b_local
        rng = np.random.default_rng(cfg.seed + ctx.process_index)
        if n_local:
            order = np.concatenate([
                rng.permutation(n_local),
                rng.integers(0, n_local, n_pad - n_local),
            ])
        else:
            order = np.zeros(n_pad, np.int64)  # all-padding shard
            users = np.zeros(1, np.int32)
            items = np.zeros(1, np.int32)
            ratings = np.zeros(1, np.float32)
        w = np.concatenate([
            np.ones(n_local, np.float32),
            np.zeros(n_pad - n_local, np.float32),
        ])

        order, w = _sort_batches_by_entity(
            order, w, np.asarray(users, np.int32), n_batches, b_local)

        def stage(a, dtype):
            a = np.asarray(a, dtype)[order].reshape(n_batches, b_local)
            return ctx.put_local_batches(a)

        return (
            stage(users, np.int32),
            stage(items, np.int32),
            stage(np.asarray(ratings, np.float32) - mean, np.float32),
            ctx.put_local_batches(w.reshape(n_batches, b_local)),
            mean,
        )

    # -- scoring ----------------------------------------------------------
    @staticmethod
    def recommend(
        model: TwoTowerModel,
        user_idx: int,
        num: int,
        exclude: Optional[np.ndarray] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-``num`` (item indices, scores) for one user.

        ``exclude`` masks item indices (seen items / blacklist) with -inf
        before top-k — the static-shape answer to dynamic filtered candidate
        sets (SURVEY §7 hard part #4)."""
        idx, scores = TwoTowerMF.recommend_batch(
            model, np.asarray([user_idx], np.int32), num, exclude
        )
        return idx[0], scores[0]

    @staticmethod
    def recommend_batch(
        model: TwoTowerModel,
        user_idx: np.ndarray,
        num: int,
        exclude: Optional[np.ndarray] = None,
        row_mask: Optional[np.ndarray] = None,
        exact: bool = False,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized top-k over the full catalog for a batch of users.

        Which routine answers is the model's serve plan (serving/plan.py)
        and the one thing only the batch knows: whether it carries a rule
        filter. ``exact=True`` skips a plan's pruned stage: the full-catalog
        scorer answers (warm-up, the recall oracle of streaming/guard.py).

        Shape discipline (the serving hot path): the user batch is padded to
        a :data:`SERVE_BUCKETS` bucket and the top-k size is the model's
        static ``serve_k`` whenever ``num`` fits under it — so the whole
        query mix shares a handful of pre-warmed executables. The user-row
        gather happens ON DEVICE (indices in, [bucket, k] out) — no
        full-table host round-trip per call.

        ``exclude`` masks one shared item-index set for the whole batch;
        ``row_mask`` is the rule-filtered form — a ``[b, n_items]`` f32
        additive mask (0 keep / -inf drop) giving EVERY query its own
        filter set in the same single dispatch (ops/retrieval.py carries it
        through the Pallas kernel on the quantized path)."""
        from incubator_predictionio_tpu.utils import jitstats

        num = min(num, model.n_items)  # k cannot exceed the catalog
        if num <= 0:
            # degenerate query — every path (serial, grouped, device)
            # answers empty; never hand a non-positive k to top-k
            return (np.zeros((len(user_idx), 0), np.int64),
                    np.zeros((len(user_idx), 0), np.float32))
        plan = model._plan or model.prepare_for_serving()._plan
        if row_mask is not None and row_mask.shape != (len(user_idx), model.n_items):
            raise ValueError(
                f"row_mask shape {row_mask.shape} != "
                f"(batch, n_items) {(len(user_idx), model.n_items)}")
        pruned = plan.pruned is not None and not exact
        if plan.scorer == serve_plan.SHARDED:
            # sharded layout (sharding/serve.py): the per-shard IVF prune +
            # merge-rerank when pruned (any shard under-covering sends the
            # batch on), else per-shard exact top-k + cross-shard merge
            sh = model._sharded
            res = sh.search_ivf(
                *sh.user_rows(model, user_idx), num, exclude=exclude,
                row_mask=row_mask, nprobe=plan.nprobe,
                backend=plan.backend) if pruned else None
            return res if res is not None else sh.search_exact(
                model, user_idx, num, exclude=exclude, row_mask=row_mask)
        if pruned:
            res = _recommend_batch_two_stage(
                model, plan, user_idx, num, exclude, row_mask)
            if res is not None:
                return res
            # fewer candidates than num survived the probe — the exact
            # path below answers (pio_retrieval_fallback_total counts it)
        if plan.scorer == serve_plan.HOST_NUMPY:
            return _recommend_batch_host(model, user_idx, num, exclude, row_mask)
        b = len(user_idx)
        bucket = serve_bucket(max(b, 1))
        k = plan.serve_k if 0 < num <= plan.serve_k else num
        quantized = plan.scorer == serve_plan.DEVICE_INT8
        # the int8 executable gets its own jitstats name so `pio-tpu status`
        # top-compiles attributes quantized-kernel compiles distinctly from
        # the bf16 exact scorer (utils/jitstats.executable_name)
        path = "two_tower_topk_int8" if quantized else "two_tower_topk"
        # pad → jitted call → device_get: the exact path's whole device leg
        with span("retrieval.batch.device", bucket=bucket, k=k, path=path):
            uidx = np.zeros(bucket, np.int32)
            uidx[:b] = np.asarray(user_idx, np.int32)
            ue_tab, ub_tab = model._device_users
            if quantized:
                items_q, scales, bias, base_mask = model._device_items
            else:
                item_t, item_b, base_mask = model._device_items
            mask = base_mask
            if exclude is not None and len(exclude):
                m = np.zeros(base_mask.shape[0], np.float32)
                m[np.asarray(exclude, np.int64)] = -np.inf
                mask = mask + jnp.asarray(m)
            rmask = None
            if row_mask is not None:
                # pad rows to the batch bucket and columns to the (quantized)
                # catalog padding; padded columns are already -inf in base_mask
                n_cols = int(mask.shape[0])
                rm = _row_mask_pad_buffer(bucket, n_cols)
                rm[:b, : row_mask.shape[1]] = row_mask
                rmask = jnp.asarray(rm)
            with jitstats.dispatch_timer((
                path, bucket, k, model.n_items, ue_tab.shape[0], rmask is not None,
            )):
                if quantized:
                    idx, scores = _topk_quantized(
                        jnp.asarray(uidx), ue_tab, ub_tab,
                        items_q, scales, bias, mask, rmask, model.mean, k,
                    )
                else:
                    idx, scores = _topk_scores(
                        jnp.asarray(uidx), ue_tab, ub_tab,
                        item_t, item_b, model.mean, mask, rmask, k,
                    )
                # ONE batched device→host pull for both results: each separate
                # np.asarray costs a full round trip on remote-attached devices
                idx_h, scores_h = jax.device_get((idx, scores))
        return idx_h[:b, :num], scores_h[:b, :num]


#: Per-thread [bucket, n_cols] row-mask pad buffers: the device dispatch
#: consumes the padded mask synchronously (recommend_batch device_gets its
#: results before returning), so each serving thread can recycle one scratch
#: buffer per shape instead of allocating bucket × N × 4 bytes per dispatch.
#: Thread-local because serving overlaps batches across threads
#: (serving_thread_safe / max_in_flight).
_ROW_MASK_SCRATCH = threading.local()


def _row_mask_pad_buffer(bucket: int, n_cols: int) -> np.ndarray:
    """A zeroed, reusable ``[bucket, n_cols]`` f32 pad buffer."""
    cache = getattr(_ROW_MASK_SCRATCH, "cache", None)
    if cache is None:
        cache = _ROW_MASK_SCRATCH.cache = {}
    buf = cache.get((bucket, n_cols))
    if buf is None:
        if len(cache) >= 16:  # many models/shapes in one process: tests
            cache.clear()
        buf = cache[(bucket, n_cols)] = np.zeros((bucket, n_cols), np.float32)
    else:
        buf.fill(0.0)
    return buf


def _recommend_batch_two_stage(
    model: TwoTowerModel,
    plan: serve_plan.ServePlan,
    user_idx: np.ndarray,
    num: int,
    exclude: Optional[np.ndarray],
    row_mask: Optional[np.ndarray],
) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """Coarse IVF pruning + exact rerank (serving/ann.py): centroid scores
    pick top-nprobe partitions per user, only their members are scored with
    the exact math, and ``exclude``/``row_mask`` land on the rerank scores
    in candidate-index space after the gather. Returns None when the probe
    can't cover ``num`` candidates — the caller's exact path answers."""
    ivf = model._ivf
    filtered = row_mask is not None or (
        exclude is not None and len(exclude) > 0)
    if plan.pruned == serve_plan.DEVICE_LEG and not filtered:
        # the queries are the bfloat16 rows the exact path scores with: the
        # fused float32 [U, D+1] tower lies column-major on the device, and
        # a row gather from it copies all of it. A rule-filtered batch stays
        # with the host routine below: search_device answers it alike, but
        # a dense catalog-length mask a batch costs more to make and send
        # (10-15 MB at a bucket of 8, 17 ms on the v5e host) than the host
        # rerank it would save
        return ivf.search_device(
            user_idx, model._device_users, model.mean, num,
            k=plan.serve_k, nprobe=plan.nprobe,
            interpret=plan.backend == "interpret")
    if not ivf.hydrated:
        # persisted slim and this model never ran _prepare_index (e.g. a
        # build_index=False prepare): rebuild the rerank tables lazily
        ivf.rehydrate(*model._host_item_table())
    model.ensure_host()  # no-op unless the towers are device-resident
    uidx = np.asarray(user_idx, np.int64)
    q = np.asarray(model.user_emb, np.float32)[uidx]
    ub = np.asarray(model.user_bias, np.float32)[uidx]
    return ivf.search(
        q, ub, model.mean, num, nprobe=plan.nprobe, exclude=exclude,
        row_mask=row_mask, backend=plan.backend)


def _recommend_batch_host(
    model: TwoTowerModel,
    user_idx: np.ndarray,
    num: int,
    exclude: Optional[np.ndarray] = None,
    row_mask: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Small-catalog top-k in host numpy: one [b, k] @ [k, n] GEMM + argpartition.

    Microseconds for catalogs under :data:`HOST_SERVE_MAX_ELEMENTS`; never
    pays a device dispatch round trip (which dominates small-model serving
    latency on remote-attached accelerators)."""
    item_t, item_b = model._host_items
    ue = np.asarray(model.user_emb, np.float32)[user_idx]
    ub = np.asarray(model.user_bias, np.float32)[user_idx]
    scores = ue @ item_t + item_b[None, :] + ub[:, None] + model.mean
    if exclude is not None and len(exclude):
        scores[:, np.asarray(exclude, np.int64)] = -np.inf
    if row_mask is not None:
        scores += row_mask
    k = min(num, scores.shape[1])
    part = np.argpartition(-scores, k - 1, axis=1)[:, :k]
    row = np.arange(scores.shape[0])[:, None]
    ordr = np.argsort(-scores[row, part], axis=1)
    idx = part[row, ordr]
    return idx, scores[row, idx]


def _sort_batches_by_entity(
    order: np.ndarray, w: np.ndarray, entities: np.ndarray,
    n_batches: int, batch: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Sort each batch's rows by entity (user) index, on the host.

    Batch composition — and therefore the math — is unchanged (the loss sums
    over the batch); only the within-batch ORDER changes, which lets the
    device gather/scatter walk the big user table quasi-sequentially
    (measured ~15% off the step time at 1M users). Returns the re-ordered
    (order, w) pair; ``w`` rides along so padding rows keep zero weight.

    Used by :meth:`TwoTowerMF._stage_local`, where each process sorts its
    own ``b_local`` slice BEFORE a global array exists (a separate need, not
    a second copy of :func:`_order_batches`, which every other fit takes),
    and by the tests as the device sort's oracle (tests/test_batch_order.py)."""
    o2 = order.reshape(n_batches, batch)
    keys = entities[o2] if len(entities) else o2
    srt = np.argsort(keys, axis=1, kind="stable")
    return (
        np.take_along_axis(o2, srt, 1).reshape(-1),
        np.take_along_axis(w.reshape(n_batches, batch), srt, 1).reshape(-1),
    )


# rows of one _order_batches dispatch. Its compile is dear at a wide batch
# (14.5 s on a v5e at 65536 columns, whatever the rows) and its run cheap
# (30 ms for 306 rows), so its shape is fixed, [_ORDER_ROWS, the batch width
# padded to its power of two], and a fit loops over such blocks: the sort
# compiles once for a batch size and not again as the event table grows.
# Few, tall blocks keep the compile of _join_batches, which follows their
# number, well under a second (PERF.md section 6, PR 25)
_ORDER_ROWS = 64


def _stage_blocks(ctx: MeshContext, order: np.ndarray, batch: int,
                  users: np.ndarray, items: np.ndarray, ratings: np.ndarray):
    """``users[order]``, ``items[order]``, ``ratings[order]`` as batches of
    ``batch``, on the mesh unsorted, in the blocks :func:`_order_batches`
    takes: a list of ``(ub, ib, rb)``, each ``[_ORDER_ROWS, width]``. The
    random gathers stay on the host: a scalar gather is what the chip is
    worst at (see train.fit.init). What pads a block to its shape holds
    the largest int32 as its user, so it sorts behind every real column."""
    per = _ORDER_ROWS * batch
    width = ctx.pad_to_batch_multiple(1 << (batch - 1).bit_length())

    def stage(a, fill=0):
        for lo in range(0, len(order), per):
            blk = a[order[lo:lo + per]].reshape(-1, batch)
            if blk.shape != (_ORDER_ROWS, width):
                blk = np.pad(blk, ((0, _ORDER_ROWS - len(blk)),
                                   (0, width - batch)), constant_values=fill)
            yield ctx.put(blk, None, ctx.data_axis)

    return list(zip(stage(users, np.iinfo(np.int32).max), stage(items),
                    stage(ratings)))


def _order_blocks(ctx: MeshContext, blocks, n: int, batch: int):
    """The staged blocks of :func:`_stage_blocks`, each sorted on the device
    and joined: ``(ub, ib, rb, wb)`` as ``_train_epochs`` takes them, the
    first ``n`` staged triples real. The blocks are donated."""
    out = ctx.sharding(None, ctx.data_axis)
    per = _ORDER_ROWS * batch
    return _join_batches(
        [_order_batches(*blk, min(n - k * per, per), batch, out)
         for k, blk in enumerate(blocks)],
        max(1, -(-n // batch)), batch, out)


@partial(jax.jit, static_argnames=("out",), donate_argnums=(0, 1, 2))
def _order_batches(ub, ib, rb, n_real, batch, out):
    """The within-batch stable sort by user index, on the device: what
    :func:`_sort_batches_by_entity` does on the host, over one block of
    batches already staged unsorted ``[_ORDER_ROWS, width]``. Returns
    ``(ub, ib, rb, wb)`` with every row of ``ub`` non-decreasing
    (``_train_epochs`` gathers with ``indices_are_sorted=True``: a wrong sort
    there is undefined behaviour, not an error) and ``wb`` the 0/1 weights
    made here: of the block's triples, ``batch`` a row, the first ``n_real``
    as staged are real, the rest the padding of the last batch.

    ``batch`` and ``n_real`` are traced, so the executable depends on the
    block's shape alone: a row's columns from ``batch`` on, and the rows
    past the last batch, are there to make that shape. The user key of such
    a column is the largest int32, so a row's first ``batch`` columns come
    out as the batch; :func:`_join_batches` drops the rest.

    The order is the stable one spelled as its definition: by (user, staged
    column), a key that is unique in a row, so there is one answer wherever
    the sort runs and the batches are the host sort's bit for bit. XLA's
    ``is_stable`` does the same with a column operand of its own; sorting
    ours lets ``wb`` be read off the sorted columns instead of riding as a
    fifth operand (on a v5e at 306 x 65536: 14.5 s to compile and 30 ms to
    run, against 20.0 s and 37 ms; PERF.md section 6, PR 25).

    ``out`` (static) is the sharding ``_train_epochs`` takes the batches
    in, ``(None, data_axis)``: a mesh with ``data`` > 1 gets the globally
    sorted batch split as the host staging split it. The executable's name
    ``jit__order_batches`` is pinned (tests/test_program_spans.py)."""
    col = jax.lax.broadcasted_iota(jnp.int32, ub.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, ub.shape, 0)
    ub, col, ib, rb = jax.lax.sort((ub, col, ib, rb), dimension=1,
                                   is_stable=False, num_keys=2)
    wb = ((col < batch) & (row * batch + col < n_real)).astype(jnp.float32)
    return tuple(jax.lax.with_sharding_constraint(b, out)
                 for b in (ub, ib, rb, wb))


@partial(jax.jit, static_argnames=("n_batches", "batch", "out"))
def _join_batches(blocks, n_batches, batch, out):
    """The sorted blocks of :func:`_order_batches`, ``(ub, ib, rb, wb)``
    each, as the four ``[n_batches, batch]`` arrays ``_train_epochs`` scans.
    This is the executable that follows the event count: copies alone."""
    return tuple(
        jax.lax.with_sharding_constraint(
            jnp.concatenate(bs)[:n_batches, :batch], out)
        for bs in zip(*blocks))


def _carry_cols(rank: int) -> int:
    """Columns of the table the step loop carries. A fused ``[rows, rank +
    1]`` row (bias last) is one gather a table and a step, so the loop
    carries it wherever the bias column costs no lane tile (rank 10, 64,
    200). Where it does (rank 128: ``[rows, 129]`` holds two tiles a row,
    the second with one live column, and dense adam's passes over p, m, v
    move both) the loop carries the embedding ``[rows, rank]`` and the bias
    ``[rows]`` apart. On a v5e (PERF.md section 6, PR 43): 1M x 100k rows at
    rank 128, 17.55 ms a step fused and 10.90 split, the bias look-ups
    (65,536 scalar gathers and their scatter-adds a table) included; at 100k
    x 10k rows and rank 64 the same look-ups make the split step 3.85 ms
    against 1.94 fused."""
    # rank + 1 crosses a multiple of 128 lanes that rank does not
    return rank if rank % 128 == 0 else rank + 1


def _carry_pad_pct(cols: int) -> float:
    """Padded lanes per 100 live ones of a float32 ``[rows, cols]`` array
    under the TPU's ``(8, 128)`` tile: 0.0 at 128, 98.4 at 129."""
    return 100.0 * (-cols % 128) / cols


@partial(jax.jit, static_argnames=("lr", "reg", "n_epochs"), donate_argnums=(0, 1))
def _train_epochs(p, o, ub, ib, rb, wb, lr, reg, n_epochs):
    """``n_epochs`` epochs in one dispatch: lax.scan over epochs of lax.scan
    over staged batches — the whole schedule runs on device with no host
    round-trips. Module-level with
    static (lr, reg, n_epochs) so repeated fits of the same shapes reuse one
    executable. Returns the last epoch's mean loss. Adam runs through
    utils/optim.adam_apply (optax-equivalent math; moment storage dtype —
    fp32 or bf16 — is carried by the state ``o`` itself).

    ``p`` and the moments of ``o`` come in and go out as the fused
    ``[rows, rank + 1]`` tables every other module stores and serves (bias
    in the last column). What the loop carries follows :func:`_carry_cols`:
    the fused table, or ``(embedding [rows, rank], bias [rows])`` split at
    entry and joined at exit, two passes a dispatch and none a step."""
    from incubator_predictionio_tpu.utils.optim import adam_apply

    rank = p["ue"].shape[1] - 1
    apart = _carry_cols(rank) == rank

    def split(tree):
        if not apart:
            return tree
        return {k: (t[:, :-1], t[:, -1]) for k, t in tree.items()}

    def join(tree):
        if not apart:
            return tree
        return {k: jnp.concatenate([e, b[:, None]], axis=1)
                for k, (e, b) in tree.items()}

    def emb(g):     # of a batch's gathered rows, as the carry holds them
        return g[0] if apart else g[:, :-1]

    def bias(g):
        return g[1] if apart else g[:, -1]

    def loss_fn(p, bu, bi, br, bw):
        # batches are user-sorted at staging, so the user-table gather (and
        # its transpose scatter-add) walks the big table quasi-sequentially.
        # A fused table gives vector + bias in one ROW gather; a split one
        # looks its bias up as scalars (see _carry_cols for what each costs)
        with jax.named_scope("gather"):
            gu = jax.tree.map(lambda t: jnp.take(
                t, bu, axis=0, indices_are_sorted=True), p["ue"])
            gi = jax.tree.map(lambda t: t[bi], p["ie"])
        ue = emb(gu).astype(jnp.bfloat16)
        ie = emb(gi).astype(jnp.bfloat16)
        pred = (
            jnp.sum(ue * ie, axis=-1).astype(jnp.float32)
            + bias(gu) + bias(gi)
        )
        err = (pred - br) ** 2
        denom = jnp.maximum(jnp.sum(bw), 1.0)
        mse = jnp.sum(err * bw) / denom
        l2 = reg * (
            jnp.sum(ue.astype(jnp.float32) ** 2) + jnp.sum(ie.astype(jnp.float32) ** 2)
        ) / denom
        return mse + l2

    def step(carry, batch):
        p, o = carry
        bu, bi, br, bw = batch
        # named scopes are metadata on the same program: they name the ops
        # in a device trace (tests/test_program_spans.py pins them). The
        # backward pass is split from the forward only to be named: the
        # gathers' transposes are the scatter-adds into the tables
        with jax.named_scope("loss_grad"):
            loss, vjp = jax.vjp(lambda q: loss_fn(q, bu, bi, br, bw), p)
        with jax.named_scope("scatter"):
            (grads,) = vjp(jnp.ones_like(loss))
        p, o = adam_apply(p, grads, o, lr,
                          scopes={"ue": "adam_user", "ie": "adam_item"})
        return (p, o), loss

    def epoch(carry, _):
        carry, losses = jax.lax.scan(step, carry, (ub, ib, rb, wb))
        return carry, losses.mean()

    count, m, v = o
    (p, (count, m, v)), epoch_losses = jax.lax.scan(
        epoch, (split(p), (count, split(m), split(v))), None, length=n_epochs)
    return join(p), (count, join(m), join(v)), epoch_losses[-1]


@partial(jax.jit, static_argnames=("num",))
def _topk_quantized(uidx, ue_tab, ub_tab, items_q, scales, bias, mask,
                    row_mask, mean, num):
    """Quantized catalog scoring: Pallas kernel on TPU, jnp oracle elsewhere.
    User rows are gathered on device from the resident bf16 table.
    ``row_mask`` (None or [b, n]) carries per-query rule filters into the
    kernel itself — masked batches stay one dispatch."""
    from incubator_predictionio_tpu.ops.retrieval import (
        score_catalog_quantized,
        score_catalog_reference,
    )

    backend = kernel_backend()
    with jax.named_scope("score"):
        if backend:
            scores = score_catalog_quantized(
                ue_tab[uidx], items_q, scales, bias, mask, row_mask,
                interpret=backend == "interpret")
        else:
            scores = score_catalog_reference(
                ue_tab[uidx], items_q, scales, bias, mask, row_mask)
        scores = scores + ub_tab[uidx][:, None] + mean
    with jax.named_scope("topk"):
        values, indices = jax.lax.top_k(scores, num)
    return indices, values


@partial(jax.jit, static_argnames=("num",))
def _topk_scores(uidx, ue_tab, ub_tab, item_t, item_b, mean, mask, row_mask,
                 num):
    # device gather of the query rows, then [b,k] @ [k,n] on the MXU in
    # bfloat16 with fp32 score accumulation; row_mask (None or [b, n]) adds
    # per-query rule filters without leaving the single dispatch
    with jax.named_scope("score"):
        scores = (
            jax.lax.dot_general(
                ue_tab[uidx], item_t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + item_b[None, :]
            + ub_tab[uidx][:, None]
            + mean
            + mask[None, :]
        )
        if row_mask is not None:
            scores = scores + row_mask
    with jax.named_scope("topk"):
        values, indices = jax.lax.top_k(scores, num)
    return indices, values
