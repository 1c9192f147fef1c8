"""Kernels: latent attention's share of its roofline, in %.

Needed time = over the answered requests due in the traced part of the
window, each as the schedule has it (tokens reused, tokens computed; the form
by block length, as the program chooses it) and every layer, the larger of
bytes / HBM peak and operations / bfloat16 peak
(``benchmarks/costs/mla_attention.py``). Device time = the trace's time in
operations under the ``mla_attn`` scope of the layer executables.
"""

from benchmarks import seq_trace
from benchmarks.costs import mla_attention


def read(ev: dict):
    tr, peaks, shape = seq_trace.traced_scopes(ev), ev.get("peaks"), \
        ev.get("shape") or {}
    part = seq_trace.traced_requests(ev)
    if not tr or not part or not peaks:
        return None
    device_s = tr["scope_s"].get("mla_attn")
    if not device_s:
        return None
    least = 0.0
    for reused, new in zip(part[0], part[1]):
        form = "absorbed" if new <= shape["short_block"] else "up"
        c = mla_attention.cost(float(reused), float(new), form, shape)
        least += max(c["bytes"] / peaks["hbm_bytes_per_s"],
                     c["ops"] / peaks[c["ops_peak"]])
    return 100.0 * shape["num_hidden_layers"] * least / device_s
