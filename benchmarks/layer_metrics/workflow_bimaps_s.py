"""Workflow: seconds of a verb building the user and item BiMaps, span
``train.verb.bimaps`` in the ring of the program's process, mean over the window's
verbs."""

from benchmarks import program_spans


def read(ev: dict):
    return program_spans.verb_span_s(ev, "train.verb.bimaps")
