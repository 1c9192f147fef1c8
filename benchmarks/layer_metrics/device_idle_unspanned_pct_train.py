"""Device, training cells: share of the device's idle time in the traced part
of the window that no program span (``pio.*`` on the profiler's timeline)
covers, in %. Prints the idle seconds per span."""

from benchmarks import program_spans


def read(ev: dict):
    if ev.get("kind") != "train":
        return None
    return program_spans.unspanned_pct(ev)
