"""Kernels: the gated short convolution's share of its roofline, in %, read
where a convolution sub-block runs as a program of its own: the long blocks'
``jit_seq_conv_b1_t<T>`` (one run a convolution layer a long dispatch).

Needed time = over the convolution layers, the larger of bytes / HBM peak and
operations / bfloat16 peak (``benchmarks/costs/short_conv.py``) for the long
dispatches the traced part of the window ran (the trace's own runs of those
programs ÷ the layers), carrying one session each
(``pio_seq_prefill_chunks_total``) over the tokens the long blocks computed
(``pio_seq_state_tokens_total{form="scan"}``), both brought from the whole
window to its traced part by the computed tokens of the requests due there.
Device time = the trace's time in those programs' runs, whole (``XLA
Modules``: what a program waits for is inside its run).

Not the short block's turn program: there the compiler starts the copy of a
convolution's weights into fast memory while the sub-block before it runs,
so the time under ``conv_proj`` + ``conv_mix`` is less than the bytes' floor
(a lone turn's 18 sub-blocks read 0.31 ms there against 0.74 ms for their
604 MB; PERF.md, PR 38) and a share of it would pass 100. A program without
these executables reads nothing.
"""

from benchmarks import seq_trace
from benchmarks.costs import short_conv


def read(ev: dict):
    trace, peaks, shape = ev.get("trace"), ev.get("peaks"), \
        ev.get("shape") or {}
    part = seq_trace.traced_requests(ev)
    tokens = seq_trace.delta(ev, "pio_seq_state_tokens_total").get(
        (("form", "scan"),))
    chunks = seq_trace.total(ev, "pio_seq_prefill_chunks_total")
    if not trace or not part or not tokens or not chunks or not peaks \
            or "conv_L_cache" not in shape:
        return None
    layers = shape["layer_types"].count("conv")
    own = [n for n in trace.get("module_s", {}) if "_seq_conv_" in n]
    device_s = sum(trace["module_s"][n] for n in own)
    runs = sum(trace["module_runs"][n] for n in own)
    if not device_s or not layers:
        return None
    c = short_conv.cost(runs / layers, chunks * part[2], tokens * part[2],
                        shape)
    least = max(c["bytes"] / peaks["hbm_bytes_per_s"],
                c["ops"] / peaks[c["ops_peak"]])
    return 100.0 * layers * least / device_s
