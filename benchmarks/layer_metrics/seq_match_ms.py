"""Sequence serving: mean milliseconds a batch spent matching its sessions
against the latent cache's table (item ids → tokens, prefix comparison, page
assignment, evictions), span ``seq.batch.match`` over the window."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "seq.batch.match")
    return None if s is None else s * 1e3
