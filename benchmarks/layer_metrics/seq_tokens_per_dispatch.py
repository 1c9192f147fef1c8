"""Sequence serving: session tokens computed per extend dispatch over the
window, ``pio_seq_tokens_computed_total`` ÷ ``pio_seq_dispatches_total``: a
few for a batch of turns, a whole session for a miss."""

from benchmarks import seq_trace


def read(ev: dict):
    tokens = seq_trace.total(ev, "pio_seq_tokens_computed_total")
    dispatches = seq_trace.total(ev, "pio_seq_dispatches_total")
    return tokens / dispatches if tokens is not None and dispatches else None
