"""Query server: mean milliseconds of a request from the handler's entry to its
enqueue in the micro-batcher (body read, JSON, admission, breaker), span
``serve.request.parse`` over the window."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "serve.request.parse")
    return None if s is None else s * 1e3
