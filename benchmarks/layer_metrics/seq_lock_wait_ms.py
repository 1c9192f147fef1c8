"""Sequence serving: mean milliseconds a batch stood at the serving lock:
seconds of span ``seq.batch.lock`` (a caller's wait for its turn, behind
another batch's dispatch, and a miss's hand-overs to waiting turns between
its pieces, ``why="offer"``) over the window's batches (the count of
``seq.batch.match``: one a batch)."""

from benchmarks import program_spans


def read(ev: dict):
    lock = program_spans.window(ev, "seq.batch.lock")
    batches = program_spans.window(ev, "seq.batch.match")
    if lock is None or batches is None:
        return None
    return lock[0] / batches[1] * 1e3
