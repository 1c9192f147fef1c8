"""Two-stage retrieval: trained IVF coarse pruning + exact candidate rerank.

Exact serving scores every query against the whole catalog — an O(catalog)
``[B, N]`` matmul per batch that stops being "as fast as the hardware
allows" at the 10M-item shapes ALX (arxiv 2112.02194) targets. This module
is the coarse-to-fine answer:

- **Build** (train or deploy time, :func:`build_ivf_fused`; jitted programs
  on the device that holds the item table): k-means over the item
  embeddings *augmented with the item bias as an extra coordinate* (the
  query side implicitly carries a 1.0 there, so a centroid's coarse score
  ``q·c_emb + c_bias`` is an unbiased estimate of its members' exact
  scores — popular-but-orthogonal items don't fall out of the probe set).
  Members are laid out contiguously per partition (CSR: ``member_ids`` +
  ``offsets``), so gathering a partition's candidates is a slice, never a
  fancy-index gather.
- **Coarse stage**: score the ``[C]`` centroids per query and keep the
  top-``nprobe`` partitions — pruning the catalog to a few percent.
- **Rerank stage**: int8 storage is the DEFAULT — member rows are held
  quantized (the same symmetric row quantization the Pallas kernel uses,
  :func:`~incubator_predictionio_tpu.ops.retrieval.quantize_rows`) and
  scored int8×int8→int32 with ONE fp32 rescale per candidate, grouped by
  partition across the batch so each probed int8 block is read once.
  The coarse stage follows the index's storage: an int8 index probes int8.
  ``PIO_RETRIEVAL_QUANTIZE=0`` opts a deployment back onto fp32 rows +
  exact serving math for the rerank (the recall-oracle path, always kept).
  Either way the shared serial-parity top-k chain picks the result.

Rule filters (``exclude`` / ``row_mask``) are applied **in candidate-index
space after the gather**, as -inf on the exact rerank scores — a filtered
candidate can therefore never displace an unfiltered one, exactly like the
full-catalog path. The exact path itself stays untouched as the recall
oracle; tests assert a recall@k floor against it
(tests/test_two_stage_retrieval.py).

Mode selection is env-driven (``PIO_RETRIEVAL_MODE`` = ``exact`` |
``two_stage`` | ``auto``; auto keeps catalogs under
``PIO_RETRIEVAL_MIN_ITEMS`` on the exact path so small templates keep
bitwise parity), read once a deployment by serving/plan.resolve. See
docs/serving.md ("How the serve path is chosen", "Two-stage retrieval").
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Iterator, Optional

import numpy as np

from incubator_predictionio_tpu.obs.metrics import REGISTRY
from incubator_predictionio_tpu.obs.trace import span
from incubator_predictionio_tpu.serving.topk import topk_row

COARSE_SEC = REGISTRY.histogram(
    "pio_retrieval_coarse_seconds",
    "Two-stage retrieval: centroid scoring + partition selection per batch")
RERANK_SEC = REGISTRY.histogram(
    "pio_retrieval_rerank_seconds",
    "Two-stage retrieval: exact candidate rerank per batch")
CANDIDATES = REGISTRY.histogram(
    "pio_retrieval_candidates",
    "Candidates gathered per query by the coarse stage",
    buckets=(64, 256, 1024, 4096, 16384, 65536, 262144, 1048576))
TWO_STAGE_BATCHES = REGISTRY.counter(
    "pio_retrieval_two_stage_total",
    "Batches served through the two-stage (pruned) path")
FALLBACKS = REGISTRY.counter(
    "pio_retrieval_fallback_total",
    "Two-stage-eligible batches that fell back to the exact path "
    "(probed partitions held fewer raw — or post-rule-filter finite — "
    "candidates than the requested top-k)")
INT8_COARSE = REGISTRY.counter(
    "pio_retrieval_int8_coarse_total",
    "Batches whose coarse (centroid) stage scored int8×int8→int32 "
    "against the quantized centroid table (every batch of an int8 index)")
INT8_RERANK = REGISTRY.counter(
    "pio_retrieval_int8_rerank_total",
    "Batches whose candidate rerank scored int8×int8→int32 over the "
    "quantized member slices (one fp32 rescale per candidate; the fp32 "
    "dequantize-first path is retired)")
DEVICE_RERANK = REGISTRY.counter(
    "pio_retrieval_device_rerank_total",
    "Two-stage batches answered by the device leg (probe selection, member "
    "gather, int8 rerank and top-k on the chip, one device_get); its share "
    "of pio_retrieval_two_stage_total is how often the leg is engaged")

#: The device leg holds every partition as one block of a fixed length: a
#: power of two that holds this many times the MEAN partition, and the
#: largest one if that is longer still (k-means leaves the largest at 3-5
#: times the mean). The length is a shape of the leg's executables: taken
#: from the catalog's size it stays put from one retrain to the next, and a
#: deploy finds them compiled; taken from the largest partition it would not.
DEVICE_BLOCK_SKEW = 4


# -- env knobs ---------------------------------------------------------------

def two_stage_enabled(n_items: int) -> bool:
    """Whether the environment says to prune a catalog of ``n_items``
    (``two_stage``, or ``auto`` from ``PIO_RETRIEVAL_MIN_ITEMS`` rows on).
    For serving/plan.resolve, once a prepare, and the train-time build."""
    mode = os.environ.get("PIO_RETRIEVAL_MODE", "auto").strip().lower()
    if mode not in ("exact", "two_stage", "auto"):
        raise ValueError(
            f"PIO_RETRIEVAL_MODE={mode!r} (want exact|two_stage|auto)")
    return mode == "two_stage" or (mode == "auto" and n_items >= int(
        os.environ.get("PIO_RETRIEVAL_MIN_ITEMS", "100000")))


def default_partitions(n_items: int) -> int:
    """√N partitions, clamped — the classic IVF sizing."""
    if n_items <= 0:
        return 1
    c = int(round(np.sqrt(n_items)))
    return max(1, min(c, max(1, n_items // 4), 65_536))


def resolved_partitions(n_items: int) -> int:
    c = int(os.environ.get("PIO_RETRIEVAL_PARTITIONS", "0"))
    return c if c > 0 else default_partitions(n_items)


def nprobe_override() -> Optional[int]:
    """``PIO_RETRIEVAL_NPROBE`` when set to a positive count, else None."""
    p = int(os.environ.get("PIO_RETRIEVAL_NPROBE", "0"))
    return p if p > 0 else None


def resolved_nprobe(n_partitions: int, nprobe: Optional[int] = None) -> int:
    """``nprobe`` clamped to the partition count; √C probes without one."""
    if nprobe is None:
        nprobe = int(round(np.sqrt(n_partitions)))
    return min(max(1, nprobe), n_partitions)


def quantize_enabled() -> bool:
    """int8 rerank storage is the default; ``PIO_RETRIEVAL_QUANTIZE=0``
    opts a deployment back onto the fp32 exact-math rerank."""
    return os.environ.get("PIO_RETRIEVAL_QUANTIZE", "1") != "0"


def _device_free_bytes() -> Optional[int]:
    """Free memory of the default device, where the backend reports it."""
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def coarse_bucket(b: int) -> int:
    """Rows the int8 coarse kernel (and the device leg behind it) pads a
    batch of ``b`` queries to: a power of two, at least 8."""
    return 1 << max(3, (b - 1).bit_length())


def build_key(n_items: int) -> dict:
    """Everything that invalidates a built index when it changes — a
    persisted index whose key still matches is reused instead of rebuilt."""
    return {
        "n_items": n_items,
        "n_partitions": resolved_partitions(n_items),
        "quantize": quantize_enabled(),
        "kmeans_iters": int(os.environ.get("PIO_RETRIEVAL_KMEANS_ITERS", "6")),
        "train_sample": int(
            os.environ.get("PIO_RETRIEVAL_TRAIN_SAMPLE", "65536")),
        "seed": int(os.environ.get("PIO_RETRIEVAL_SEED", "0")),
    }


# -- the index ---------------------------------------------------------------

@dataclasses.dataclass
class IVFIndex:
    """Trained partition of the catalog + member-order rerank tables.

    ``centroids`` is ``[C, D+1]`` — the last column is the partition's mean
    item bias (see the module docstring). Members are stored sorted by
    partition: ``member_ids[offsets[p]:offsets[p+1]]`` are partition ``p``'s
    catalog indices, and ``emb_m``/``bias_m`` (or ``emb_q``/``scales_m``
    when quantized) hold the matching rows contiguously, so the rerank
    reads each probed partition as one slice. Read-only after build —
    serving threads share it without locks. Pickles with the model (host
    numpy only), so a persisted model redeploys without re-clustering.
    """

    centroids: np.ndarray        # [C, D+1] f32 (last col = mean member bias)
    member_ids: np.ndarray       # [N] int32, partition-sorted catalog indices
    offsets: np.ndarray          # [C+1] int64 partition boundaries
    bias_m: np.ndarray           # [N] f32 item bias in member order
    key: dict                    # build_key() this index was built under
    emb_m: Optional[np.ndarray] = None     # [N, D] f32 (fp32 rerank mode)
    emb_q: Optional[np.ndarray] = None     # [N, D] int8 (quantized mode)
    scales_m: Optional[np.ndarray] = None  # [N] f32 dequant scales
    build_seconds: float = 0.0
    # -- streaming staleness overlay (docs/streaming.md) -------------------
    # Rows a delta deploy updated AFTER this index was built: the k-means
    # assignment (and the member-order rerank tables, which older deployed
    # models may still share) hold their PRE-update embeddings. The overlay
    # keeps the current rows; search (a) rescores any gathered stale
    # candidate from the overlay and (b) appends stale ids a probe missed
    # to every candidate set — so a pruned probe never serves a pre-update
    # embedding as if it were current, and a row that moved INTO a user's
    # taste stays reachable until the rebuild threshold re-clusters.
    stale_ids: Optional[np.ndarray] = None      # sorted int64 catalog ids
    stale_emb: Optional[np.ndarray] = None      # [S, D] f32 current rows
    stale_bias: Optional[np.ndarray] = None     # [S] f32 current biases

    @property
    def n_partitions(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_items(self) -> int:
        return self.member_ids.shape[0]

    @property
    def quantized(self) -> bool:
        return self.emb_q is not None

    def matches(self, key: dict) -> bool:
        return self.key == key

    # -- persistence -------------------------------------------------------
    #
    # The member-order rerank tables duplicate the catalog (emb_m is a full
    # fp32 copy of item_emb) — at the 10M-item scales two-stage targets that
    # would DOUBLE the persisted model artifact and every deploy transfer.
    # Only the clustering (centroids/member_ids/offsets/key — the part that
    # is expensive to recompute) pickles; load rehydrates the tables with
    # one O(N) gather from arrays the model blob already carries.

    def __post_init__(self):
        self._rehydrate_lock = threading.Lock()
        self._cent_quant = None
        self._cent_device = None
        self._member_device = None
        self._mean_device = None

    def __getstate__(self):
        state = dict(self.__dict__)
        for k in ("_rehydrate_lock", "_cent_quant", "_cent_device",
                  "_member_device", "_mean_device"):
            state.pop(k, None)
        for k in ("emb_m", "emb_q", "scales_m", "bias_m"):
            state[k] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._rehydrate_lock = threading.Lock()
        self._cent_quant = None
        self._cent_device = None
        self._member_device = None
        self._mean_device = None

    def _coarse_quant(self) -> tuple[np.ndarray, np.ndarray]:
        """Lazy ``(cent_q [C, D] int8, cent_scales [C] f32)`` — the
        quantized twin of the centroid embedding columns (the mean-bias
        column stays fp32 and is added after the rescale). Derived data:
        cheap to recompute, so it never pickles (the slim-persistence
        contract) and rebuilds on first int8 probe after a load."""
        cq = self._cent_quant
        if cq is None:
            with self._rehydrate_lock:
                cq = self._cent_quant
                if cq is None:
                    from incubator_predictionio_tpu.ops.retrieval import (
                        quantize_rows,
                    )

                    q8, scales = quantize_rows(
                        np.asarray(self.centroids[:, :-1], np.float32))
                    cq = self._cent_quant = (q8, scales)
        return cq

    @property
    def hydrated(self) -> bool:
        """Whether the rerank tables are resident (False right after
        unpickling — :meth:`rehydrate` before :meth:`search`)."""
        return self.bias_m is not None and (
            self.emb_m is not None or self.emb_q is not None)

    def rehydrate(self, item_emb: np.ndarray,
                  item_bias: np.ndarray) -> "IVFIndex":
        """Rebuild the member-order rerank tables after unpickling.

        Lock-guarded: a runtime mode flip (exact → two_stage) can land the
        first rehydration on overlapped serving threads. ``bias_m`` is
        assigned LAST — :attr:`hydrated` requires it, so a concurrent
        reader can never observe a half-built table set."""
        if self.hydrated:
            return self
        with self._rehydrate_lock:
            if self.hydrated:
                return self
            order = self.member_ids.astype(np.int64)
            emb_m = np.ascontiguousarray(
                np.asarray(item_emb, np.float32)[order])
            bias_m = np.ascontiguousarray(
                np.asarray(item_bias, np.float32)[order])
            if self.key.get("quantize"):
                from incubator_predictionio_tpu.ops.retrieval import (
                    quantize_rows,
                )

                self.emb_q, self.scales_m = quantize_rows(emb_m)
            else:
                self.emb_m = emb_m
            self.bias_m = bias_m
        return self

    # -- streaming staleness ----------------------------------------------
    @property
    def stale_count(self) -> int:
        return 0 if self.stale_ids is None else int(len(self.stale_ids))

    @property
    def stale_fraction(self) -> float:
        n = self.n_items
        return (self.stale_count / n) if n else 0.0

    def with_updated_rows(self, ids: np.ndarray, emb_rows: np.ndarray,
                          bias_rows: np.ndarray) -> "IVFIndex":
        """A NEW index view with ``ids``' current rows overlaid. The big
        arrays (centroids, member layout, rerank tables) are shared with
        this index — the old deployed model keeps serving its own view
        untouched while the delta-applied model serves the overlay."""
        ids = np.asarray(ids, np.int64)
        emb_rows = np.asarray(emb_rows, np.float32).reshape(len(ids), -1)
        bias_rows = np.asarray(bias_rows, np.float32).reshape(len(ids))
        merged: dict[int, tuple[np.ndarray, float]] = {}
        if self.stale_ids is not None:
            for i, sid in enumerate(self.stale_ids):
                merged[int(sid)] = (self.stale_emb[i], float(self.stale_bias[i]))
        for i, sid in enumerate(ids):
            merged[int(sid)] = (emb_rows[i], float(bias_rows[i]))
        order = np.asarray(sorted(merged), np.int64)
        new = dataclasses.replace(
            self,
            stale_ids=order,
            stale_emb=np.stack([merged[int(s)][0] for s in order]).astype(
                np.float32),
            stale_bias=np.asarray(
                [merged[int(s)][1] for s in order], np.float32),
        )
        return new

    def _apply_stale_overlay(
        self, ids: np.ndarray, scores: np.ndarray, qrow: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Rescore gathered stale candidates from the overlay and append
        the stale ids this probe missed (pre-bias score space)."""
        s_ids = self.stale_ids
        pos = np.minimum(np.searchsorted(s_ids, ids), len(s_ids) - 1)
        hit = s_ids[pos] == ids
        if hit.any():
            sel = pos[hit]
            scores[hit] = self.stale_emb[sel] @ qrow + self.stale_bias[sel]
        present = np.zeros(len(s_ids), bool)
        present[pos[hit]] = True
        missing = ~present
        if missing.any():
            add_scores = (self.stale_emb[missing] @ qrow
                          + self.stale_bias[missing])
            ids = np.concatenate([ids, s_ids[missing]])
            scores = np.concatenate([scores, add_scores])
        return ids, scores

    def stats(self) -> dict:
        """Partition-shape summary for ``pio-tpu index`` / status pages."""
        sizes = np.diff(self.offsets)
        mean = float(sizes.mean()) if len(sizes) else 0.0
        nbytes = sum(
            a.nbytes for a in (
                self.centroids, self.member_ids, self.offsets, self.bias_m,
                self.emb_m, self.emb_q, self.scales_m)
            if a is not None)
        # analytic rerank-storage accounting (stable whether or not the
        # tables are hydrated): int8 layout = 1 byte/coord + one f32 scale
        # per row; the fp32 equivalent is what the same rows cost unquantized
        n = self.n_items
        d = self.centroids.shape[1] - 1
        fp32_bytes = n * d * 4
        rerank_bytes = (n * d + n * 4) if self.quantized else fp32_bytes
        return {
            "n_partitions": int(self.n_partitions),
            "n_items": int(self.n_items),
            # which table shard this index covers, when it is one of a
            # sharded model's per-shard partitions (docs/sharding.md);
            # None for a whole-catalog index
            "shard": self.key.get("shard"),
            "partition_size_min": int(sizes.min()) if len(sizes) else 0,
            "partition_size_mean": round(mean, 1),
            "partition_size_max": int(sizes.max()) if len(sizes) else 0,
            "size_skew": round(float(sizes.max()) / mean, 2) if mean else 0.0,
            "empty_partitions": int((sizes == 0).sum()),
            "quantized": self.quantized,
            # the coarse stage follows the index's storage
            "quant_coarse": self.quantized,
            "rerank_bytes": int(rerank_bytes),
            "rerank_bytes_fp32": int(fp32_bytes),
            "bytes_saved": int(fp32_bytes - rerank_bytes),
            "default_nprobe": resolved_nprobe(
                self.n_partitions, nprobe_override()),
            "index_bytes": int(nbytes),
            "build_seconds": round(self.build_seconds, 2),
            "stale_rows": self.stale_count,
        }

    # -- search -----------------------------------------------------------

    def probe(self, q: np.ndarray, nprobe: int,
              q_quant: Optional[tuple] = None,
              backend: Optional[str] = None) -> np.ndarray:
        """Top-``nprobe`` partition ids per query row (``[B, nprobe]``).

        With ``q_quant`` (the ``(q_q int8, q_scales f32)`` pair from
        ``quantize_rows``) the centroid scores run int8×int8→int32 with one
        fp32 rescale — the host-exact twin of the Pallas coarse kernel
        (ops/retrieval.py ``score_centroids_quantized``), which runs them
        instead where the caller's serve plan holds a kernel ``backend``;
        the fp32 mean-member-bias column is added after the rescale."""
        if q_quant is not None:
            from incubator_predictionio_tpu.ops.retrieval import (
                int8_matmul_exact,
            )

            q_q, q_scales = q_quant
            if backend:
                # the Pallas int8 coarse kernel (ops/retrieval.py). Same
                # int8×int8→int32 + one-rescale contract as the host twin
                # below — the accumulation is exact integers either way;
                # only the final rescale may FMA-contract (≤1 ulp), so
                # probe sets agree except exact near-ties at the boundary
                coarse = self._probe_tpu(
                    q_q, q_scales, interpret=backend == "interpret")
            else:
                cent_q, cent_scales = self._coarse_quant()
                coarse = (int8_matmul_exact(q_q, cent_q)
                          * (q_scales[:, None] * cent_scales[None, :])
                          + self.centroids[:, -1][None, :])
        else:
            coarse = (q @ self.centroids[:, :-1].T
                      + self.centroids[:, -1][None, :])
        if nprobe >= self.n_partitions:
            return np.tile(np.arange(self.n_partitions), (len(q), 1))
        return np.argpartition(-coarse, nprobe - 1, axis=1)[:, :nprobe]

    def _centroid_device(self) -> tuple:
        """Resident device copy of the quantized centroid table, padded to
        the coarse kernel's block (``(cent_q, cent_scales, cent_bias)``;
        padding carries -inf bias and can never win a probe slot)."""
        dev = self._cent_device
        if dev is None:
            import jax

            from incubator_predictionio_tpu.ops.retrieval import pad_centroids

            # _coarse_quant takes the (non-reentrant) lock itself: resolve
            # it BEFORE entering the locked section below
            cent_q, cent_scales = self._coarse_quant()
            with self._rehydrate_lock:
                dev = self._cent_device
                if dev is None:
                    cq, cs, cb = pad_centroids(
                        cent_q, cent_scales,
                        np.asarray(self.centroids[:, -1], np.float32))
                    dev = self._cent_device = tuple(
                        jax.device_put(v) for v in (cq, cs, cb))
        return dev

    def _probe_tpu(self, q_q: np.ndarray, q_scales: np.ndarray,
                   interpret: bool = False) -> np.ndarray:
        """Coarse scores through the Pallas int8 kernel on the resident
        centroid table. The batch pads to :func:`coarse_bucket` so the
        query mix shares a handful of executables."""
        import jax
        import jax.numpy as jnp

        from incubator_predictionio_tpu.ops.retrieval import (
            score_centroids_quantized,
        )
        from incubator_predictionio_tpu.utils import jitstats

        cq, cs, cb = self._centroid_device()
        b = q_q.shape[0]
        bp = coarse_bucket(b)
        qq = np.zeros((bp, q_q.shape[1]), np.int8)
        qq[:b] = q_q
        qs = np.zeros(bp, np.float32)
        qs[:b] = q_scales
        with jitstats.dispatch_timer(
                ("ivf_coarse_int8", bp, int(cq.shape[0]))):
            out = jax.device_get(score_centroids_quantized(
                jnp.asarray(qq), jnp.asarray(qs), cq, cs, cb,
                interpret=interpret))
        return np.asarray(out)[:b, : self.n_partitions]

    def candidate_ids(self, qrow: np.ndarray, nprobe: int) -> np.ndarray:
        """One query's gathered candidate set (tests / inspection)."""
        parts = np.sort(self.probe(qrow[None, :], nprobe)[0])
        return np.concatenate([
            self.member_ids[self.offsets[p]:self.offsets[p + 1]]
            for p in parts]) if len(parts) else np.empty(0, np.int32)

    def _int8_partition_scores(
        self, probe: np.ndarray, q_quant: tuple,
    ) -> dict[int, "Iterator[np.ndarray]"]:
        """int8×int8→int32 rerank scores for every probed partition,
        grouped by partition across the batch: each probed partition's int8
        member block is upcast (and its scores rescaled) ONCE for all the
        queries that probe it — one ``[probers, members]`` GEMM per
        partition instead of a GEMV per (query, partition) pair. Because
        the int8 accumulation is exact integers in f32
        (ops/retrieval.int8_matmul_exact), the batched GEMM scores are
        bit-identical to what per-query GEMVs would produce — batching is
        free of reduction-order drift, something the fp32 path can't claim.
        This cross-query amortization is where the int8 lane's serve-side
        speedup comes from, so it grows with the coalesced batch size.

        The (query, partition) grouping comes from ONE stable argsort of
        the probe matrix — no per-partition membership scans. Returns
        ``{partition: row-iterator}`` where the iterator yields that
        partition's ``[members]`` f32 score rows in ascending query order:
        the rescale (``scale_query · scale_row``) and member bias are
        already applied, and because :meth:`search` walks queries in
        ascending order and each query probes a partition at most once,
        ``next()`` hands every consumer exactly its row with no lookup."""
        from incubator_predictionio_tpu.ops.retrieval import (
            INT8_EXACT_MAX_RANK,
            int8_matmul_exact,
        )

        q_q, q_scales = q_quant
        flat = probe.ravel()
        order = np.argsort(flat, kind="stable")  # stable ⇒ ascending query
        qidx = order // probe.shape[1]
        sflat = flat[order]
        bounds = np.flatnonzero(np.diff(sflat)) + 1
        starts = np.concatenate(([0], bounds))
        ends = np.concatenate((bounds, [len(sflat)]))
        # the exact-accumulation dtype decision is per BATCH, not per GEMM:
        # upcast the query block once and inline the per-partition matmul
        # (int8_matmul_exact's math, minus its per-call dispatch overhead)
        exact_f32 = q_q.shape[1] <= INT8_EXACT_MAX_RANK
        qf = q_q.astype(np.float32 if exact_f32 else np.float64)
        emb_q, offsets = self.emb_q, self.offsets
        scales_m, bias_m = self.scales_m, self.bias_m
        out: dict[int, Iterator[np.ndarray]] = {}
        for a, e in zip(starts.tolist(), ends.tolist()):
            p = int(sflat[a])
            lo, hi = int(offsets[p]), int(offsets[p + 1])
            if hi == lo:
                continue
            who = qidx[a:e]
            if exact_f32:
                acc = qf[who] @ emb_q[lo:hi].astype(np.float32).T
            else:
                acc = int8_matmul_exact(q_q[who], emb_q[lo:hi])
            acc *= q_scales[who][:, None] * scales_m[lo:hi][None, :]
            acc += bias_m[lo:hi][None, :]
            out[p] = iter(acc)
        return out

    # -- the device leg ---------------------------------------------------
    #
    # search() below is the semantic reference and the only routine on a
    # host without a chip. Where the queries' tower and this index's int8
    # tables are resident on a device, search_device() runs the same two
    # stages there without coming back to the host in between.

    def prepare_device(self) -> bool:
        """Put the int8 member tables on the device beside the centroids
        (deploy time), one fixed-length block a partition so that a probed
        partition is one block read: ``emb_q`` as ``[P, L, D]`` int8 and
        ``scales_m``, ``bias_m``, ``member_ids`` and a zero ``exclude`` mask
        as ``[P, 1, L]``, ``L`` a power of two set by the catalog's size
        (:data:`DEVICE_BLOCK_SKEW`) — ``P × L × (D + 16)`` bytes. Returns
        whether the device leg can serve this index: quantized, hydrated,
        no stale overlay (its rows are float32 and live on the host), and
        a layout that fits in half of what the device has free (one giant
        partition pads every other to its length)."""
        if not (self.quantized and self.hydrated) or self.stale_count:
            return False
        if self._member_device is not None:
            return True
        import jax

        self._centroid_device()
        with self._rehydrate_lock:
            if self._member_device is None:
                n, c = self.n_items, self.n_partitions
                sizes = np.diff(self.offsets).astype(np.int32)
                longest = max(int(sizes.max()),
                              -(-DEVICE_BLOCK_SKEW * n // c), 128)
                length = 1 << (longest - 1).bit_length()
                free = _device_free_bytes()
                need = c * length * (self.emb_q.shape[1] + 16)
                if free is not None and need > free // 2:
                    return False
                # member row -> its slot in the [P, L] layout
                part = np.repeat(np.arange(c), sizes)
                slot = part * length + (np.arange(n) - self.offsets[part])

                def blocks(a, shape):
                    out = np.zeros((c * length,) + a.shape[1:], a.dtype)
                    out[slot] = a
                    return out.reshape(shape + a.shape[1:])

                position = np.empty(n, np.int64)  # catalog id -> slot
                position[self.member_ids] = slot
                tables = jax.block_until_ready(tuple(
                    jax.device_put(v) for v in (
                        sizes, blocks(self.emb_q, (c, length)),
                        blocks(self.scales_m, (c, 1, length)),
                        blocks(self.bias_m, (c, 1, length)),
                        blocks(self.member_ids.astype(np.int32),
                               (c, 1, length)),
                        np.zeros((c, 1, length), np.float32))))
                self._member_device = (position, tables)
        return True

    @property
    def device_ready(self) -> bool:
        """Whether :meth:`search_device` can answer: :meth:`prepare_device`
        made the tables resident and no overlay has been laid since."""
        return self._member_device is not None and not self.stale_count

    def search_device(
        self,
        user_idx: np.ndarray,        # [B] rows of the resident user tower
        user_tables: tuple,          # device (user_emb [U, D], user_bias [U])
        mean: float,
        num: int,
        k: Optional[int] = None,
        nprobe: Optional[int] = None,
        exclude: Optional[np.ndarray] = None,
        row_mask: Optional[np.ndarray] = None,
        interpret: bool = False,
    ) -> Optional[tuple[np.ndarray, np.ndarray]]:
        """:meth:`search` as one device leg (ops/retrieval.py): the host
        pads ``user_idx`` to :func:`coarse_bucket`, three executables run
        back to back on the device — gather + quantize the user rows, the
        int8 coarse kernel, probe selection + member gather + int8 rerank +
        masks + top-k — and ONE ``device_get`` brings ids, scores and
        candidate counts back. Same probe rule, rerank arithmetic, mask
        order and ``None`` (→ the caller's exact path) as :meth:`search`.

        ``k`` ≥ ``num`` is the static top-k the executable is compiled for
        (a deployment's ``serve_k``, so that ``num`` never recompiles)."""
        import jax
        import jax.numpy as jnp

        from incubator_predictionio_tpu.ops.retrieval import (
            quantize_user_rows,
            score_centroids_quantized,
            two_stage_rerank,
        )
        from incubator_predictionio_tpu.utils import jitstats

        b = len(user_idx)
        if num <= 0:
            return (np.zeros((b, 0), np.int64), np.zeros((b, 0), np.float32))
        if b == 0:
            return (np.zeros((0, num), np.int64), np.zeros((0, num), np.float32))
        nprobe = resolved_nprobe(self.n_partitions, nprobe)
        position, tables = self._member_device
        length = int(tables[1].shape[1])
        # a scalar costs a host-to-device transfer of its own on every
        # launch it is passed to (0.3-0.5 ms on the v5e host): keep it there
        held = self._mean_device
        if held is None or held[0] != mean:
            held = self._mean_device = (mean, jax.device_put(np.float32(mean)))
        k = min(max(k or num, num), nprobe * length)
        if num > k:
            # more than the probe can hold at all: search()'s counts < num
            FALLBACKS.inc()
            return None
        bucket = coarse_bucket(b)
        with span("retrieval.batch.coarse", batch=b, nprobe=nprobe,
                  where="device") as sp:
            uidx = np.zeros(bucket, np.int32)
            uidx[:b] = np.asarray(user_idx, np.int32)
            cq, cs, cb = self._centroid_device()
            with jitstats.dispatch_timer(
                    ("ivf_quantize_users", bucket,
                     tuple(user_tables[0].shape), str(user_tables[0].dtype))):
                q_q, q_scales, ubias = quantize_user_rows(uidx, *user_tables)
            with jitstats.dispatch_timer(
                    ("ivf_coarse_int8", bucket, int(cq.shape[0]))):
                coarse = score_centroids_quantized(
                    q_q, q_scales, cq, cs, cb, interpret=interpret)
        COARSE_SEC.observe(sp.duration)
        INT8_COARSE.inc()
        with span("retrieval.batch.rerank", batch=b, nprobe=nprobe,
                  where="device") as sp:
            mask_m = tables[-1]
            if exclude is not None and len(exclude):
                # search() drops ids outside the catalog without a word
                ex = np.asarray(exclude, np.int64)
                ex = ex[(ex >= 0) & (ex < self.n_items)]
                m = np.zeros(mask_m.shape, np.float32)
                m.reshape(-1)[position[ex]] = -np.inf
                mask_m = jnp.asarray(m)
            rmask = None
            if row_mask is not None:
                rm = row_mask
                if b < bucket:
                    rm = np.zeros((bucket, self.n_items), np.float32)
                    rm[:b] = row_mask
                rmask = jnp.asarray(rm, jnp.float32)
            INT8_RERANK.inc()
            with jitstats.dispatch_timer(
                    ("ivf_rerank_int8", bucket, k, nprobe,
                     tuple(tables[1].shape), rmask is not None)):
                ids, scores, counts = jax.device_get(two_stage_rerank(
                    coarse, q_q, q_scales, ubias, held[1],
                    *tables[:-1], mask_m, rmask,
                    nprobe=nprobe, k=k, interpret=interpret))
            if (int(counts[:b].min()) < num
                    or not np.isfinite(scores[:b, num - 1]).all()):
                # too few candidates, or too few that survive the rule
                # filters: the exact path sees the whole catalog
                FALLBACKS.inc()
                return None
        RERANK_SEC.observe(sp.duration)
        for cnt in counts[:b].tolist():
            CANDIDATES.observe(cnt)
        TWO_STAGE_BATCHES.inc()
        DEVICE_RERANK.inc()
        return ids[:b, :num].astype(np.int64), scores[:b, :num]

    def search(
        self,
        q: np.ndarray,               # [B, D] f32 user vectors
        user_bias: np.ndarray,       # [B] f32
        mean: float,
        num: int,
        nprobe: Optional[int] = None,
        exclude: Optional[np.ndarray] = None,
        row_mask: Optional[np.ndarray] = None,
        observe: bool = True,
        backend: Optional[str] = None,
    ) -> Optional[tuple[np.ndarray, np.ndarray]]:
        """Two-stage top-``num``: returns ``(idx [B, num] int64, scores
        [B, num] f32)`` with the exact path's score semantics, or ``None``
        when some row's probed partitions hold fewer than ``num`` raw
        candidates — or fewer than ``num`` candidates that survive the
        rule filters with a finite score (the caller falls back to the
        exact path, which sees the whole catalog — the pruned path never
        returns a short result, and never serves a masked item in place
        of an unmasked one the probe missed).

        ``exclude``/``row_mask`` are in catalog-index space and are applied
        to the exact rerank scores AFTER the gather (candidate-index
        space): masked candidates score -inf and can only fill trailing
        slots once every unmasked candidate is placed, mirroring the
        full-catalog mask semantics.
        """
        b = q.shape[0]
        if num <= 0:
            return (np.zeros((b, 0), np.int64), np.zeros((b, 0), np.float32))
        if b == 0:
            return (np.zeros((0, num), np.int64), np.zeros((0, num), np.float32))
        nprobe = resolved_nprobe(self.n_partitions, nprobe)
        # the two stages are spans (retrieval.batch.coarse|rerank); the
        # histograms read the same clock. Per-shard searches (observe=False)
        # are accounted once, by their caller
        def stage(name):
            return span(name, batch=b, nprobe=nprobe, where="host") \
                if observe else contextlib.nullcontext()

        with stage("retrieval.batch.coarse") as sp:
            q_quant = None
            if self.quantized:
                from incubator_predictionio_tpu.ops.retrieval import quantize_rows

                # one per-row query quantization serves BOTH stages (the int8
                # coarse probe and the int8 rerank share q_q/q_scales)
                q_quant = quantize_rows(np.asarray(q, np.float32))
            probe = self.probe(q, nprobe, q_quant=q_quant, backend=backend)
            counts = np.diff(self.offsets)[probe].sum(axis=1)
        if observe:
            COARSE_SEC.observe(sp.duration)
            if q_quant is not None:
                INT8_COARSE.inc()
        if int(counts.min()) < num:
            if observe:
                FALLBACKS.inc()
            return None
        # exclude lands per row via searchsorted over the SORTED exclude set
        # — O(cnt log E) in candidate space; an n_items-sized lookup table
        # would put O(catalog) allocation back on the path built to avoid it
        excl_sorted = None
        if exclude is not None and len(exclude):
            excl_sorted = np.sort(np.asarray(exclude, np.int64))
        with stage("retrieval.batch.rerank") as sp:
            part_scores = None
            if q_quant is not None:
                part_scores = self._int8_partition_scores(probe, q_quant)
                if observe:
                    INT8_RERANK.inc()
            out_idx = np.empty((b, num), np.int64)
            out_scores = np.empty((b, num), np.float32)
            for r in range(b):
                parts = np.sort(probe[r])  # ordered slices walk memory forward
                cnt = int(counts[r])
                ids = np.empty(cnt, np.int32)
                scores = np.empty(cnt, np.float32)
                qrow = q[r]
                pos = 0
                bnds = self.offsets[parts].tolist()
                ubnds = self.offsets[parts + 1].tolist()
                for p, lo, hi in zip(parts.tolist(), bnds, ubnds):
                    m = hi - lo
                    if not m:
                        continue
                    ids[pos:pos + m] = self.member_ids[lo:hi]
                    if part_scores is not None:
                        # rows come off each partition's iterator in ascending
                        # query order — exactly this loop's visit order
                        scores[pos:pos + m] = next(part_scores[p])
                    else:
                        scores[pos:pos + m] = \
                            self.emb_m[lo:hi] @ qrow + self.bias_m[lo:hi]
                    pos += m
                if self.stale_ids is not None and len(self.stale_ids):
                    ids, scores = self._apply_stale_overlay(ids, scores, qrow)
                scores += user_bias[r] + mean
                if excl_sorted is not None:
                    pos = np.minimum(np.searchsorted(excl_sorted, ids),
                                     len(excl_sorted) - 1)
                    scores[excl_sorted[pos] == ids] = -np.inf
                if row_mask is not None:
                    scores += row_mask[r, ids]
                top = topk_row(scores, num)
                if not np.isfinite(scores[top[-1]]):
                    # fewer than num candidates survived the rule filters in
                    # THIS probe set — a masked (-inf) item would fill the
                    # trailing slots where the exact path, seeing the whole
                    # catalog, still has unmasked items to place. Fall back.
                    if observe:
                        FALLBACKS.inc()
                    return None
                out_idx[r] = ids[top]
                out_scores[r] = scores[top]
                if observe:
                    CANDIDATES.observe(cnt)
        if observe:
            RERANK_SEC.observe(sp.duration)
            TWO_STAGE_BATCHES.inc()
        return out_idx, out_scores


# -- build -------------------------------------------------------------------

def build_ivf(item_emb: np.ndarray, item_bias: np.ndarray,
              key: Optional[dict] = None) -> IVFIndex:
    """:func:`build_ivf_fused` over host rows: the catalog goes to the
    default backend's device as one ``[n, D+1]`` array, bias column last."""
    return build_ivf_fused(np.concatenate(
        [np.asarray(item_emb, np.float32),
         np.asarray(item_bias, np.float32)[:, None]], axis=1),
        len(item_emb), key)


def build_ivf_fused(rows, n: int, key: Optional[dict] = None) -> IVFIndex:
    """Cluster the catalog and lay out the member-order rerank tables.

    ``rows`` is the catalog fused ``[>= n, D+1]`` (embedding + bias column;
    rows past ``n`` are padding): host numpy, or a jax Array where a device
    already holds it, as a device-resident model's item table does, and
    then nothing of it is copied. The build is jitted programs on that
    device (``ops/retrieval.py`` ``ivf_*``): k-means on a bounded sample,
    ONE assignment pass over the catalog, then the member-order gather and
    its int8 quantization, of which only the finished tables come to the
    host. Milliseconds of a chip at a million rows; on a process without
    one the same programs are XLA's CPU code. The host keeps the generator:
    which rows are sampled, which seed a centroid and which replace a dead
    one are ``key["seed"]``'s draws, in that order.
    """
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import retrieval as dev

    key = dict(key if key is not None else build_key(n))
    if key.get("n_items") != n:
        key["n_items"] = n
    rng = np.random.default_rng(key["seed"])
    t0 = time.perf_counter()
    sample = min(int(key["train_sample"]), n)
    sel = np.arange(n) if sample >= n else \
        rng.choice(n, size=sample, replace=False)
    # can't seed more centroids than training rows
    c = min(key["n_partitions"], max(1, sample))
    iters = int(key["kmeans_iters"])
    with span("train.index.cluster", backend=jax.default_backend(), rows=n,
              partitions=c, iters=iters) as sp:
        rows = jnp.asarray(rows)
        train, cent = dev.ivf_sample(
            rows, sel.astype(np.int32),
            rng.choice(sample, size=c, replace=False).astype(np.int32))
        train_host, reseeded = None, 0
        for _ in range(iters):
            cent, counts = dev.ivf_update(
                train, dev.ivf_assign(train, cent, n=sample), c=c)
            dead = np.flatnonzero(np.asarray(counts) == 0)
            if len(dead):
                # every centroid stays live: an empty one restarts from a
                # random row of the sample. Patched on the host into the
                # whole [C, D+1] array, so the next program sees the shape
                # it was compiled for however many died
                if train_host is None:
                    train_host = np.asarray(train)
                patched = np.array(cent)
                patched[dead] = train_host[
                    rng.choice(sample, size=len(dead), replace=False)]
                cent = jnp.asarray(patched)
                reseeded += len(dead)
        assign = np.asarray(dev.ivf_assign(rows, cent, n=n))
        sp.set_attr("reseeded", reseeded)
    with span("train.index.layout"):
        order = np.argsort(assign, kind="stable").astype(np.int32)
        sizes = np.bincount(assign, minlength=c)
        quantize = bool(key["quantize"])
        emb, scales, bias_m = jax.device_get(
            dev.ivf_layout(rows, order, quantize=quantize))
        index = IVFIndex(
            centroids=np.asarray(cent),
            member_ids=order,
            offsets=np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64),
            bias_m=bias_m,
            key=key,
            emb_m=None if quantize else emb,
            emb_q=emb if quantize else None,
            scales_m=scales,
        )
    index.build_seconds = time.perf_counter() - t0
    return index
