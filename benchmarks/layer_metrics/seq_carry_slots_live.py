"""Sequence serving, a pattern with per-session state: the sessions that
hold a slot when the window ends over the slots there are
(``pio_seq_state_slots{state="used"}`` ÷ ``{state="capacity"}``), in %: near
100 the slots are the scarce thing and a new visitor evicts a live session;
well under it the paged rows are what a live session costs. A program
without the gauge reads nothing."""


def read(ev: dict):
    after = ev.get("metrics_after") or {}
    used = after.get('pio_seq_state_slots{state="used"}')
    capacity = after.get('pio_seq_state_slots{state="capacity"}')
    if used is None or not capacity:
        return None
    return 100.0 * used / capacity
