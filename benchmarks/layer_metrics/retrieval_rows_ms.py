"""Retrieval: mean milliseconds of host work per batch after the scorer
(building the ``PredictedResult`` rows), span ``retrieval.batch.rows`` over
the window."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "retrieval.batch.rows")
    return None if s is None else s * 1e3
