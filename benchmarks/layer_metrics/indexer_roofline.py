"""Kernels: the sparse index's share of its roofline, in %.

Needed time = over the traced part's requests and every layer, the larger of
bytes / HBM peak and operations / bfloat16 peak
(``benchmarks/costs/indexer.py``: the index scores of every visible key for
every query, the session's index keys read once). Device time = the trace's
time in operations under the ``idx_score`` and ``idx_select`` scopes (index
rows written and read, scores, top-k or threshold): the selection needs no
operation the cost function counts, so all of its time lowers the share.
"""

from benchmarks.costs import indexer
from benchmarks.layer_metrics import _sparse_index


def read(ev: dict):
    least = _sparse_index.least_seconds(ev, indexer.cost)
    device_s = _sparse_index.scope_seconds(ev, ("idx_score", "idx_select"))
    if not least or not device_s:
        return None
    return 100.0 * least / device_s
