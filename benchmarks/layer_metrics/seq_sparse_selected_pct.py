"""Sequence serving, the sparse-index block: rows attended ÷ rows the index
scored over the window, in % (``pio_seq_sparse_rows_selected_total`` ÷
``pio_seq_index_rows_scored_total``): 100 means the selection left nothing
out (sessions no longer than ``topk``), 2048 / 12288 = 17 is a turn of a
median session of the lifelong cell."""

from benchmarks import seq_trace


def read(ev: dict):
    scored = seq_trace.total(ev, "pio_seq_index_rows_scored_total")
    selected = seq_trace.total(ev, "pio_seq_sparse_rows_selected_total")
    if not scored or selected is None:
        return None
    return 100.0 * selected / scored
