"""Trainer: seconds of a fit ordering its triples on the host (permutation +
per-batch sort by user), the part of ``stage_sec`` before the first ``ctx.put``, span
``train.fit.order`` in the ring of the program's process, mean over the window's
verbs."""

from benchmarks import program_spans


def read(ev: dict):
    return program_spans.verb_span_s(ev, "train.fit.order")
