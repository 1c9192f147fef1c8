"""Workflow, serving cells: seconds of the deploy spent bringing the towers
back: spans ``deploy.restore`` (orbax restore onto the device) +
``deploy.quantize`` (serving state: slice, cast, int8 catalog) +
``deploy.ensure_host`` (the towers pulled to the host, two-stage path).
Absolute, from the counters after the window: the deploy is set-up."""

from benchmarks import program_spans

SPANS = ("deploy.restore", "deploy.quantize", "deploy.ensure_host")


def read(ev: dict):
    got = [program_spans.total_s(ev.get("metrics_after"), s) for s in SPANS]
    if got[0] is None:
        return None
    return sum(s for s in got if s is not None)
