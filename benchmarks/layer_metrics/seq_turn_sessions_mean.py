"""Sequence serving, the state-space pattern: sessions a short-block
dispatch over the window, ``pio_seq_state_step_sessions_total`` ÷ the
``pio_seq_dispatches_total`` of the buckets whose block is the short one:
whether state steps batch (1 = every turn went alone). A program without the
counter reads nothing."""

from benchmarks import seq_trace


def read(ev: dict):
    sessions = seq_trace.total(ev, "pio_seq_state_step_sessions_total")
    short = (ev.get("shape") or {}).get("short_block")
    if sessions is None or not short:
        return None
    dispatches = sum(
        n for labels, n in seq_trace.delta(
            ev, "pio_seq_dispatches_total").items()
        if dict(labels).get("bucket", "").partition("@")[0].endswith(
            f"x{short}"))
    return sessions / dispatches if dispatches else None
