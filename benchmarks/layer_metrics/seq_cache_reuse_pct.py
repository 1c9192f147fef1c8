"""Sequence serving: share of the window's session tokens that the latent
cache already held, in %: ``pio_seq_tokens_reused_total`` ÷ (reused +
computed). The schedule implies about 90% in this cell's mix; a server that
quietly recomputes reads lower."""

from benchmarks import seq_trace


def read(ev: dict):
    reused = seq_trace.total(ev, "pio_seq_tokens_reused_total")
    computed = seq_trace.total(ev, "pio_seq_tokens_computed_total")
    if reused is None or computed is None or reused + computed <= 0:
        return None
    return 100.0 * reused / (reused + computed)
