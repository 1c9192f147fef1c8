"""Sequence serving, the short-convolution pattern: the share of the
device's busy time in the traced part of the window that lies under the
convolution layers' scopes (``conv_proj``, ``conv_mix``), in %: how the
device's time is shared between the convolutions and the experts, the dense
layers, attention and head."""

from benchmarks import seq_trace

SCOPES = ("conv_proj", "conv_mix")


def read(ev: dict):
    busy = (ev.get("trace") or {}).get("busy_s")
    tr = seq_trace.traced_scopes(ev)
    under = sum(tr["scope_s"].get(n, 0.0) for n in SCOPES) if tr else 0.0
    if not busy or not under:
        return None
    return 100.0 * under / busy
