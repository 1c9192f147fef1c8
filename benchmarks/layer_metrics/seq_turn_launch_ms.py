"""Sequence serving: mean milliseconds a dispatch of the short block spent
issuing its programs: embed, one a layer, the head (the call, not the
answer); for the two blocks that hand numpy operands to every launch, their
transfers too; span ``seq.turn.launch`` over the window. What the host
spends issuing: a launch blocks only when the runtime's queue is full."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "seq.turn.launch")
    return None if s is None else s * 1e3
