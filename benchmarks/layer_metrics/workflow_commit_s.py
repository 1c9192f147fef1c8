"""Workflow: seconds of a verb in ``serialize_model``, the blob insert and the
instance's COMPLETED update, span
``train.verb.commit`` in the ring of the program's process, mean over the window's
verbs."""

from benchmarks import program_spans


def read(ev: dict):
    return program_spans.verb_span_s(ev, "train.verb.commit")
