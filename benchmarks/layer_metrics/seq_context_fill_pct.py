"""Sequence serving: what the window's short-block dispatches (turns) held
of what they read, in %: ``pio_seq_context_rows_held_total`` (the tokens of
a dispatch's sessions) ÷ ``pio_seq_context_rows_read_total`` (batch x
context of its bucket). 100 would be a turn that gathers and scores only
its own session's rows; a lone 1,024-token turn in a bucket of 4 sessions x
4096 rows reads 6. A program without the counters reads nothing."""

from benchmarks import seq_trace


def read(ev: dict):
    held = seq_trace.total(ev, "pio_seq_context_rows_held_total")
    rows = seq_trace.total(ev, "pio_seq_context_rows_read_total")
    if held is None or not rows:
        return None
    return 100.0 * held / rows
