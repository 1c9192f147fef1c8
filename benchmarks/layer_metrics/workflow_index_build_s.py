"""Workflow: seconds of a verb in ``_prepare_index`` (the IVF build where the
catalog qualifies), span
``train.verb.index`` in the ring of the program's process, mean over the window's
verbs."""

from benchmarks import program_spans


def read(ev: dict):
    return program_spans.verb_span_s(ev, "train.verb.index")
