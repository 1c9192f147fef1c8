"""Micro-batcher: mean milliseconds per batch between the loop and the worker
thread: span ``serve.batch.dispatch`` (the loop's ``await`` of the executor)
minus ``serve.batch.predict`` (the body of ``predict_batch`` in the worker),
over the window: the hand-over to the thread and the loop's wake-up after it."""

from benchmarks import program_spans


def read(ev: dict):
    outer = program_spans.window(ev, "serve.batch.dispatch")
    inner = program_spans.window(ev, "serve.batch.predict")
    if outer is None or inner is None:
        return None
    return (outer[0] - inner[0]) / outer[1] * 1e3
