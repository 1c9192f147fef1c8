"""Sequence serving, the sparse-index block: the share of the device's busy
time in the traced part of the window that lies under the attention half's
scopes (``gqa_proj``, ``idx_score``, ``idx_select``, ``sparse_attn``), in %:
the evidence that the mechanism, and not the experts or the head, does most
of the work in the cell."""

from benchmarks.layer_metrics import _sparse_index

SCOPES = ("gqa_proj", "idx_score", "idx_select", "sparse_attn")


def read(ev: dict):
    busy = (ev.get("trace") or {}).get("busy_s")
    under = _sparse_index.scope_seconds(ev, SCOPES)
    if not busy or not under:
        return None
    return 100.0 * under / busy
