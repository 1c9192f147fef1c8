"""Sequence serving, the window / full attention pattern: the key/value rows
a window layer read for the sessions of the window's short dispatches over
the rows it would have read without the window
(``pio_seq_window_rows_held_total`` ÷
``pio_seq_window_rows_unwindowed_total``), in %: at 100 the window keeps
nothing out (every session is shorter than it); well under it a session
costs a window layer a ring, not its length. A program without the counters
reads nothing."""

from benchmarks import seq_trace


def read(ev: dict):
    held = seq_trace.total(ev, "pio_seq_window_rows_held_total")
    whole = seq_trace.total(ev, "pio_seq_window_rows_unwindowed_total")
    if held is None or not whole:
        return None
    return 100.0 * held / whole
