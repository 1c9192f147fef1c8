"""Micro-batcher: mean milliseconds a request waited in the queue, from its
enqueue to the instant a batch took it, span ``serve.request.queue`` over the
window (``batcher_queue_p99_ms`` is the same wait's 99th percentile)."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "serve.request.queue")
    return None if s is None else s * 1e3
