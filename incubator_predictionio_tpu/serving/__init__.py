"""Vectorized batched serving for the rule-filtered templates.

The reference evaluates every business rule (category filter, white/black
lists, live unavailable-items constraint, unseen-only) per query with
per-item Scala closures (ECommAlgorithm.scala isCandidateItem); the seed
port kept that shape as per-item Python loops, so a coalesced micro-batch
of B queries still ran O(B × catalog) interpreter work plus O(B) live
event-store reads. This package is the batched replacement:

- :mod:`masks <incubator_predictionio_tpu.serving.masks>` — compile the
  catalog's category metadata once at ``prepare_for_serving`` into a
  :class:`~incubator_predictionio_tpu.serving.masks.CategoryIndex`
  (category → member-row arrays), then assemble every query's filter as
  vectorized index scatters into a ``[B, N]`` additive -inf mask.
- :mod:`cache <incubator_predictionio_tpu.serving.cache>` — a TTL +
  single-flight cache for serving-time live store reads (the per-query
  ``unavailableItems`` constraint read), clock-injectable so tests script
  expiry deterministically. ``PIO_SERVING_CONSTRAINT_TTL_MS=0`` restores
  the reference's read-per-query semantics.

- :mod:`ann <incubator_predictionio_tpu.serving.ann>` — two-stage
  retrieval for big catalogs: a trained IVF partition over the item
  embeddings prunes each query to the top-``nprobe`` partitions' members,
  then the exact scoring math reranks only the gathered candidates
  (``PIO_RETRIEVAL_*`` knobs; the full-catalog path stays the recall
  oracle).

See docs/serving.md ("Batched serving & mask compilation",
"Two-stage retrieval").
"""

from incubator_predictionio_tpu.serving.ann import (
    IVFIndex,
    build_ivf,
    build_ivf_fused,
)
from incubator_predictionio_tpu.serving.cache import TTLCache, constraint_ttl_sec
from incubator_predictionio_tpu.serving.masks import (
    CategoryIndex,
    HasCategoryIndex,
    ban_rows,
    whitelist_vec,
)
from incubator_predictionio_tpu.serving.topk import grouped_topk, topk_row

__all__ = [
    "CategoryIndex",
    "HasCategoryIndex",
    "IVFIndex",
    "TTLCache",
    "ban_rows",
    "build_ivf",
    "build_ivf_fused",
    "constraint_ttl_sec",
    "grouped_topk",
    "topk_row",
    "whitelist_vec",
]
