"""Query server + micro-batcher: share of the window in which the batcher held
no request (nothing queued, nothing in a dispatch), in %: seconds of span
``serve.server.empty`` over those of ``serve.server.empty`` and
``serve.server.occupied`` together, both rows of
``pio_profile_phase_seconds_total``. That share of the chip's idle time is
the traffic's, not the program's; 100 minus it is the server's utilisation.
An interval is booked when it ends, so one that straddles an edge of the
window counts whole on the side where it closed."""

from benchmarks import program_spans


def read(ev: dict):
    empty = program_spans.window(ev, "serve.server.empty")
    occupied = program_spans.window(ev, "serve.server.occupied")
    if empty is None and occupied is None:
        return None
    e, o = (w[0] if w else 0.0 for w in (empty, occupied))
    return 100.0 * e / (e + o) if e + o > 0 else None
