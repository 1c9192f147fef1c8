"""Device, serving cells: the share of the traced part of the window in which
the chip was idle WHILE the server held a request, in %: (idle seconds − idle
seconds under ``pio.serve.server.empty``) over the traced extent, from the
run's ``.xplane.pb``. ``device_idle_pct.serve`` less what the traffic leaves
empty: the idle share a change to the program can shorten. Prints every row
of the idle-by-span table (``device_idle_unspanned_pct.serve`` prints its top
twelve) and that the rows sum to the idle time.

Reads nothing from a program that books no occupancy (no such event on the
timeline and no such row on ``/metrics``)."""

from benchmarks import program_spans, trace_reduce

EMPTY = "serve.server.empty"


def read(ev: dict, path: str | None = None):
    if not ev.get("trace") or ev.get("kind") == "train":
        return None
    path = path or program_spans.newest_trace()
    if path is None:
        return None
    ops, spans = program_spans.load(path)
    idle = program_spans.idle_by_span(ops, spans)
    if not idle:
        return None
    if EMPTY not in idle and program_spans.total_s(
            ev.get("metrics_after"), "serve.server.occupied") is None:
        return None
    # the rows partition the idle part of the extent (operations and spans
    # together, as ``idle_by_span`` takes it); the busy part is the rest
    total = sum(idle.values())
    busy = sum(e - s for s, e in trace_reduce.union(ops)) / 1e9
    extent = total + busy
    print(f"device idle by program span, every row ({total:.4f} s idle + "
          f"{busy:.4f} s busy = an extent of {extent:.4f} s):", flush=True)
    for name, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {name:<28} {s:9.4f} s  {100.0 * s / extent:5.1f}% of "
              "the extent", flush=True)
    return 100.0 * (total - idle.get(EMPTY, 0.0)) / extent
