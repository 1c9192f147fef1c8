"""Sequence serving: mean milliseconds a dispatch of the short block spent
in ``device_get`` of the head's answer: the device finishing what the launch
span issued, the runtime's completion and the transfer back; span
``seq.turn.wait`` over the window. (A head-less piece has no wait span; a
short block always answers.)"""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "seq.turn.wait")
    return None if s is None else s * 1e3
