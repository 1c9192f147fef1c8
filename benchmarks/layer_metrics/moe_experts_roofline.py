"""Kernels: the routed experts' share of their roofline, in %.

Needed time = the larger of bytes / HBM peak and operations / bfloat16 peak
(``benchmarks/costs/moe_experts.py``) for the token-picks the held experts
took and the experts touched (``pio_moe_expert_tokens_total``,
``pio_moe_experts_touched_total``, counted on the device), brought from the
whole window to its traced part by the computed tokens of the requests due
there. Device time = the trace's time in operations under the ``moe_experts``
scope of the layer executables.
"""

from benchmarks import seq_trace
from benchmarks.costs import moe_experts


def read(ev: dict):
    tr, peaks, shape = seq_trace.traced_scopes(ev), ev.get("peaks"), \
        ev.get("shape") or {}
    part = seq_trace.traced_requests(ev)
    picks = seq_trace.total(ev, "pio_moe_expert_tokens_total")
    touched = seq_trace.total(ev, "pio_moe_experts_touched_total")
    if not tr or not part or not picks or touched is None or not peaks:
        return None
    device_s = tr["scope_s"].get("moe_experts")
    if not device_s:
        return None
    c = moe_experts.cost(picks * part[2], touched * part[2],
                         shape["hidden_size"], shape["moe_intermediate_size"])
    least = max(c["bytes"] / peaks["hbm_bytes_per_s"],
                c["ops"] / peaks[c["ops_peak"]])
    return 100.0 * least / device_s
