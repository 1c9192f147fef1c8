"""The serve plan: which routine answers a two-tower query, chosen once.

``TwoTowerModel.prepare_for_serving`` calls :func:`resolve` with the model's
facts, builds the buffers and the index the plan names, and
:meth:`ServePlan.settle` fixes the pruned routine once the index has
answered. From then on warm-up, the status page and every dispatch READ the
plan: no batch looks at the environment (``PIO_RETRIEVAL_MODE``,
``_MIN_ITEMS``, ``_NPROBE``, ``PIO_SHARD_SERVE*``: read here, once), the
kernel backend or which buffer happens to be resident (docs/serving.md "How
the serve path is chosen"). The index BUILD knobs stay behind
``ann.build_key``: they are the index's format, not the serve choice.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

from incubator_predictionio_tpu.serving import ann

#: Micro-batch bucket ladder for serving: every request batch is padded up to
#: the next bucket so the jitted scorers see a handful of static shapes
#: instead of one per batch size (the round-2 compile-churn bug). Beyond the
#: largest bucket, batches round up to a multiple of it.
SERVE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

#: Catalogs with ≤ this many table elements (rows × columns) serve from HOST
#: numpy instead of the device: scoring a 3.7k-item catalog is ~100 µs of
#: numpy, while EVERY device call pays a dispatch/result round trip. Big
#: catalogs amortize the round trip over real MXU work and stay on device.
HOST_SERVE_MAX_ELEMENTS = 2_000_000

#: Per-row rule masks are DENSE [batch, n_items] f32 — the host build +
#: device transfer scales with batch × catalog, so the row-mask path (and
#: its deploy-time warmup) is limited to batches where that mask stays
#: modest (≤ this many elements, 32 MB f32). Above it, callers fall back to
#: shared-exclude / over-fetch semantics and warmup skips the row-mask
#: executables (which are then never dispatched — the compile-count gauge
#: stays flat either way).
ROW_MASK_MAX_ELEMENTS = 8_000_000

# full-catalog scorers
HOST_NUMPY = "host-numpy"
DEVICE_BF16 = "device-bf16"
DEVICE_INT8 = "device-int8"
SHARDED = "sharded"

# routines that answer an unfiltered batch of a pruned catalog
DEVICE_LEG = "device-leg"
HOST_ROUTINE = "host-routine"


def serve_bucket(b: int) -> int:
    """Smallest bucket ≥ ``b`` (multiples of the top bucket past the ladder)."""
    for s in SERVE_BUCKETS:
        if b <= s:
            return s
    top = SERVE_BUCKETS[-1]
    return ((b + top - 1) // top) * top


class WarmShape(NamedTuple):
    """One ``deploy.warmup.bucket`` span: a dispatch at ``bucket`` users on
    the pruned (``two_stage``) or the full-catalog (``exact``) path, the
    latter with its row-mask form beside it when ``row_mask``."""

    bucket: int
    path: str
    row_mask: bool = False


@dataclasses.dataclass(frozen=True)
class ServePlan:
    catalog_rows: int
    #: the full-catalog scorer, also the fallback of a pruned catalog
    scorer: str
    #: > 1 exactly when ``scorer`` is ``sharded``, else 0
    n_shards: int
    #: the towers live on the device alone (a restored deployment): the
    #: sharded scorer then runs there too, else over host blocks
    tables_on_device: bool
    #: mode and threshold say this catalog is to be pruned: prepare builds
    #: (or reuses) the index
    two_stage: bool
    #: ``PIO_RETRIEVAL_NPROBE``; None = every index takes √C of its own
    #: partitions, per shard too
    nprobe: Optional[int]
    #: parallel/mesh.kernel_backend() at prepare
    backend: Optional[str]
    serve_k: int
    #: storage of the index at hand (``fp32`` | ``int8``), None without one
    index: Optional[str] = None
    #: the routine that answers an unfiltered batch of a pruned catalog,
    #: None where the full-catalog scorer answers everything. A batch with
    #: ``exclude`` / ``row_mask`` keeps the host routine either way
    pruned: Optional[str] = None

    @property
    def wants_device_leg(self) -> bool:
        """Towers and kernels are on a device: an int8 index may join them
        (``IVFIndex.prepare_device`` has the last word)."""
        return (self.two_stage and self.backend is not None
                and self.scorer in (DEVICE_BF16, DEVICE_INT8))

    def settle(self, index: Optional[str],
               on_device: bool = False) -> "ServePlan":
        """The plan once the index exists: ``index`` its storage,
        ``on_device`` what ``IVFIndex.prepare_device()`` answered
        (quantized, hydrated, no stale overlay, the layout fits)."""
        pruned = None
        if self.two_stage and index is not None:
            pruned = DEVICE_LEG if (
                on_device and self.wants_device_leg) else HOST_ROUTINE
        return dataclasses.replace(self, index=index, pruned=pruned)

    @property
    def path(self) -> str:
        """The scorer as the status page names it (``servingPaths[].path``:
        the executable ``_topk_quantized`` dispatches is part of the name)."""
        if self.scorer == SHARDED:
            return ("sharded-device-bf16" if self.tables_on_device
                    else "sharded-host-numpy")
        if self.scorer == DEVICE_INT8:
            return {"mosaic": "device-int8-pallas",
                    "interpret": "device-int8-pallas-interpret",
                    None: "device-int8-jnp"}[self.backend]
        return self.scorer

    def warm_shapes(self, max_batch: int) -> list[WarmShape]:
        """Every dispatch shape a deploy warms so that no live batch up to
        ``max_batch`` builds an executable, in warm-up order. The first
        pruned dispatch is a prime (it also faults the member tables in and
        spins up BLAS on the host routine) and builds the smallest coarse
        bucket's executables; the int8 coarse kernel pads queries to
        ``ann.coarse_bucket`` and the device leg's other two executables
        follow it, so each further coarse bucket gets one dispatch. The
        full-catalog executables are warmed whatever answers by default:
        they are the pruned path's fallback. Numpy scorers compile none."""
        buckets = [b for b in SERVE_BUCKETS if b <= max(1, max_batch)]
        shapes = []
        if self.pruned is not None:
            shapes.append(WarmShape(1, "two_stage"))
            if self.index == "int8" and self.backend is not None:
                # (the ladder's buckets past the prime's pad to themselves)
                shapes += [WarmShape(b, "two_stage") for b in buckets
                           if ann.coarse_bucket(b) > ann.coarse_bucket(1)]
        if self.scorer in (DEVICE_BF16, DEVICE_INT8) or (
                self.scorer == SHARDED and self.tables_on_device):
            shapes += [
                # beyond ROW_MASK_MAX_ELEMENTS serving never dispatches the
                # row-mask form, and warming it would cost a batch×catalog
                # host allocation + transfer per bucket
                WarmShape(b, "exact",
                          b * self.catalog_rows <= ROW_MASK_MAX_ELEMENTS)
                for b in buckets]
        return shapes


def resolve(*, n_items: int, rank: int, tables_on_device: bool,
            layout_shards: int, backend: Optional[str],
            quantize: bool = False, serve_k: int = 128,
            host_max_elements: Optional[int] = None) -> ServePlan:
    """The plan for a model of these facts under the current environment
    (read here, once). ``layout_shards`` is how many ways the restored item
    table is split on the model axis (1 for host towers); ``backend`` is
    ``parallel/mesh.kernel_backend()``. Raises ``ValueError`` on an invalid
    ``PIO_RETRIEVAL_MODE`` / ``PIO_SHARD_SERVE``."""
    from incubator_predictionio_tpu.sharding import serve as shard_serve

    host_max = (HOST_SERVE_MAX_ELEMENTS if host_max_elements is None
                else host_max_elements)
    n_shards = shard_serve.serving_shards_for(
        n_items, rank, layout_shards, host_max)
    if n_shards > 1:
        scorer = SHARDED
    elif n_items * (rank + 1) <= host_max:
        # host check first: ``quantize`` applies to device-resident
        # catalogs; one small enough for the host never benefits from it
        scorer = HOST_NUMPY
    else:
        scorer = DEVICE_INT8 if quantize else DEVICE_BF16
    return ServePlan(
        catalog_rows=n_items, scorer=scorer, n_shards=n_shards,
        tables_on_device=tables_on_device,
        two_stage=ann.two_stage_enabled(n_items),
        nprobe=ann.nprobe_override(), backend=backend,
        serve_k=min(serve_k, n_items))
