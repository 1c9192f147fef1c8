"""Kernels: the two-matrix (squared-ReLU) routed experts' share of their
roofline, in %: ``moe_experts_roofline``'s reading with
``benchmarks/costs/moe_experts_relu2.py`` (the accepted cost counts three
matrices an expert and would read this stack 1.5 x too high).

Needed time = the larger of bytes / HBM peak and operations / bfloat16 peak
for the token-picks the held experts took and the experts touched
(``pio_moe_expert_tokens_total``, ``pio_moe_experts_touched_total``, counted
on the device), brought from the whole window to its traced part by the
computed tokens of the requests due there. Device time = the trace's time in
operations under the ``moe_experts`` scope of the layer executables. Reads
nothing where the configuration's experts are not of this kind.
"""

from benchmarks import seq_trace
from benchmarks.costs import moe_experts_relu2


def read(ev: dict):
    tr, peaks, shape = seq_trace.traced_scopes(ev), ev.get("peaks"), \
        ev.get("shape") or {}
    part = seq_trace.traced_requests(ev)
    picks = seq_trace.total(ev, "pio_moe_expert_tokens_total")
    touched = seq_trace.total(ev, "pio_moe_experts_touched_total")
    if not tr or not part or not picks or touched is None or not peaks \
            or "moe_shared_expert_intermediate_size" not in shape:
        return None
    device_s = tr["scope_s"].get("moe_experts")
    if not device_s:
        return None
    c = moe_experts_relu2.cost(picks * part[2], touched * part[2],
                               shape["hidden_size"],
                               shape["moe_intermediate_size"])
    least = max(c["bytes"] / peaks["hbm_bytes_per_s"],
                c["ops"] / peaks[c["ops_peak"]])
    return 100.0 * least / device_s
