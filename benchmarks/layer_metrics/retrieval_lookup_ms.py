"""Retrieval: mean milliseconds of host work per batch before the scorer
(BiMap look-ups, banned sets, the user-index vector), span
``retrieval.batch.lookup`` over the window."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "retrieval.batch.lookup")
    return None if s is None else s * 1e3
