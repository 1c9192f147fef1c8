"""Query server: mean milliseconds of a request from its future's result to
the built answer (``to_jsonable``, output plugins, last-good cache, timing
header), span ``serve.request.respond`` over the window."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "serve.request.respond")
    return None if s is None else s * 1e3
