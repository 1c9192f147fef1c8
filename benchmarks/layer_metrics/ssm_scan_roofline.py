"""Kernels: the state-space recurrence's share of its roofline, in %.

Needed time = over the state-space layers, the larger of bytes / HBM peak
and operations / bfloat16 peak (``benchmarks/costs/ssm_scan.py``) for the
sessions the window's dispatches carried (``pio_seq_state_step_sessions_total``
in short dispatches, one a long dispatch: ``pio_seq_prefill_chunks_total``)
over the tokens they computed (``pio_seq_state_tokens_total``), brought from
the whole window to its traced part by the computed tokens of the requests
due there. Device time = the trace's time in operations under the
``ssm_scan`` scope (state read, scan, state write) of the layer executables.
"""

from benchmarks import seq_trace
from benchmarks.costs import ssm_scan


def read(ev: dict):
    tr, peaks, shape = seq_trace.traced_scopes(ev), ev.get("peaks"), \
        ev.get("shape") or {}
    part = seq_trace.traced_requests(ev)
    tokens = seq_trace.total(ev, "pio_seq_state_tokens_total")
    turns = seq_trace.total(ev, "pio_seq_state_step_sessions_total")
    if not tr or not part or not tokens or turns is None or not peaks \
            or "hybrid_override_pattern" not in shape:
        return None
    device_s = tr["scope_s"].get("ssm_scan")
    if not device_s:
        return None
    sessions = turns + (seq_trace.total(ev, "pio_seq_prefill_chunks_total")
                        or 0.0)
    c = ssm_scan.cost(sessions * part[2], tokens * part[2], shape)
    least = max(c["bytes"] / peaks["hbm_bytes_per_s"],
                c["ops"] / peaks[c["ops_peak"]])
    return 100.0 * shape["hybrid_override_pattern"].count("M") * least \
        / device_s
