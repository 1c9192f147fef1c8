"""Workflow: seconds of a verb in ``models_for_persistence`` (orbax save of the
towers, child span ``train.persist.orbax``, + the sidecar pickle), span
``train.verb.persist`` in the ring of the program's process, mean over the window's
verbs."""

from benchmarks import program_spans


def read(ev: dict):
    return program_spans.verb_span_s(ev, "train.verb.persist")
