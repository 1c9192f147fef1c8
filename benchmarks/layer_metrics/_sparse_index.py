"""What the sparse-index cell's roofline readers share: the least time the
equations need over the answered requests due in the traced part of the
window (each as the schedule has it: tokens reused, tokens computed), every
layer, by a cost function of (reused, new, shape)."""

from benchmarks import seq_trace


def least_seconds(ev: dict, cost):
    peaks, shape = ev.get("peaks"), ev.get("shape") or {}
    part = seq_trace.traced_requests(ev)
    if not part or not peaks or "sa_config" not in shape:
        return None
    least = 0.0
    for reused, new in zip(part[0], part[1]):
        c = cost(float(reused), float(new), shape)
        least += max(c["bytes"] / peaks["hbm_bytes_per_s"],
                     c["ops"] / peaks[c["ops_peak"]])
    return shape["num_hidden_layers"] * least


def scope_seconds(ev: dict, names) -> float:
    """Device seconds under the named scopes of the layer executables, or
    None where the trace or the program's scope map has none of them."""
    tr = seq_trace.traced_scopes(ev)
    if not tr:
        return None
    return sum(tr["scope_s"].get(n, 0.0) for n in names) or None
