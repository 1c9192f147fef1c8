"""Sequence serving: mean milliseconds of one extend dispatch (pad → embed,
the layer executable once a layer, head + top-k → ``device_get``), span
``seq.batch.extend`` over the window. A batch makes one dispatch for its
short blocks and one more for each long one."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "seq.batch.extend")
    return None if s is None else s * 1e3
