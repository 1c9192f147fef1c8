"""Kernels: window attention's share of its roofline in the turn programs,
in %.

Needed time = the larger of bytes / HBM peak and operations / bfloat16 peak
(``benchmarks/costs/window_attention.py``) for the turns of the requests that
were due in the traced part of the window (``seq_window_trace.dispatches``:
each extends its session from the tokens the schedule says were cached by
those it says were computed), times the stack's window layers. Device time =
the trace's time in operations under the ``win_attn`` scope (ring read and
write, scores, softmax, sum) inside the short block's turn programs
(``jit_seq_turn_*``). The long blocks' pieces are read the same way from
their own programs and printed, not returned: a traced part holds one miss
or none (``seq_window_trace``). A program without that scope reads nothing.
"""

from benchmarks import seq_window_trace
from benchmarks.costs import window_attention


def read(ev: dict):
    tr, peaks, shape = seq_window_trace.scopes_by_block(ev), \
        ev.get("peaks"), ev.get("shape") or {}
    asked = seq_window_trace.dispatches(ev)
    if not tr or not asked or not peaks or "sliding_window" not in shape:
        return None
    layers = shape["layer_types"].count("sliding_attention")
    share = {}
    for kind in ("turn", "piece"):
        device_s = tr[kind]["scope_s"].get("win_attn")
        if not device_s or not layers or not asked[kind][0]:
            continue
        c = window_attention.cost(*asked[kind], shape)
        least = max(c["bytes"] / peaks["hbm_bytes_per_s"],
                    c["ops"] / peaks[c["ops_peak"]])
        share[kind] = 100.0 * layers * least / device_s
    print(f"window attention's share of its roofline, in %: {share}",
          flush=True)
    return share.get("turn")
