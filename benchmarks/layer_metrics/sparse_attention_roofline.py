"""Kernels: attention over the selected rows, its share of its roofline, in
%.

Needed time = over the traced part's requests and every layer, the larger of
bytes / HBM peak and operations / bfloat16 peak
(``benchmarks/costs/sparse_attention.py``: ``q . k`` and ``p v`` over the
rows each query selected, and those rows' bytes). Device time = the trace's
time in operations under the ``sparse_attn`` scope of the layer executables
(key/value rows written, gathered or read by tiles, scores, softmax,
weighted sum).
"""

from benchmarks.costs import sparse_attention
from benchmarks.layer_metrics import _sparse_index


def read(ev: dict):
    least = _sparse_index.least_seconds(ev, sparse_attention.cost)
    device_s = _sparse_index.scope_seconds(ev, ("sparse_attn",))
    if not least or not device_s:
        return None
    return 100.0 * least / device_s
