"""Retrieval, exact cells only: mean milliseconds per batch of pad -> jitted
scorer + top-k -> ``device_get`` in ``recommend_batch``, span
``retrieval.batch.device`` over the window (the two-stage path never opens
it: its stages are ``retrieval.batch.coarse`` / ``.rerank``)."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "retrieval.batch.device")
    return None if s is None else s * 1e3
