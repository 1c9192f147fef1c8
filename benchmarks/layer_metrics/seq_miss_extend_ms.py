"""Sequence serving: mean milliseconds of one dispatch of a long block (a
miss, or a piece of a cut one): seconds of spans ``seq.miss.stage``,
``seq.miss.launch`` and ``seq.miss.wait`` together over the count of
``seq.miss.launch`` (every dispatch launches; a piece that is not its
block's last waits for nothing). ``seq_extend_ms`` is the mean over these
and the turns together, which describes neither."""

from benchmarks import program_spans

PARTS = ("seq.miss.stage", "seq.miss.launch", "seq.miss.wait")


def read(ev: dict):
    launch = program_spans.window(ev, "seq.miss.launch")
    if launch is None:
        return None
    parts = (program_spans.window(ev, name) for name in PARTS)
    return sum(w[0] for w in parts if w) / launch[1] * 1e3
