"""Sequence serving, the state-space pattern: the share of the device's busy
time in the traced part of the window that lies under the state-space
layers' scopes (``ssm_proj``, ``ssm_conv``, ``ssm_scan``), in %: how the
device's time is shared between the mixers and the experts, attention and
head."""

from benchmarks import seq_trace

SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan")


def read(ev: dict):
    busy = (ev.get("trace") or {}).get("busy_s")
    tr = seq_trace.traced_scopes(ev)
    under = sum(tr["scope_s"].get(n, 0.0) for n in SCOPES) if tr else 0.0
    if not busy or not under:
        return None
    return 100.0 * under / busy
