"""Kernels: the serving scorer's share of its roofline, in %.

Exact cells: the full-catalog int8 scorer (``two_tower._topk_quantized``);
two-stage cells: the int8 centroid scorer (``score_centroids_quantized``).
Needed time = sum over the window's dispatches, by batch bucket (the
``pio_serving_template_batch_size`` histogram's edges are the bucket ladder),
of the larger of bytes / HBM peak and operations / int8 peak; HBM bytes bound
both. Device time = the trace's time in that executable's runs, scaled from
the traced part of the window to the whole of it by the dispatch counts.
"""

from benchmarks.costs import ivf_coarse_int8, scorer_full_int8
from benchmarks.runners.common import batch_histogram

EXACT_MODULE = "_topk_quantized"
COARSE_MODULE = "score_centroids_quantized"


def read(ev: dict):
    tr, shape, peaks = ev.get("trace"), ev.get("shape") or {}, ev.get("peaks")
    status = ev.get("status")
    if not tr or not status or not ev.get("metrics_after"):
        return None
    hist = batch_histogram(ev["metrics_before"], ev["metrics_after"])
    mode = status["servingPaths"][0]["retrieval_mode"]
    if mode == "two_stage":
        module = COARSE_MODULE

        def cost(b):
            bp = 1 << max(3, (b - 1).bit_length())
            return ivf_coarse_int8.cost(bp, shape["n_partitions"],
                                        shape["rank"])
    else:
        module = EXACT_MODULE

        def cost(b):
            return scorer_full_int8.cost(b, shape["n_items"], shape["rank"])

    names = [n for n in tr["module_s"] if module in n]
    runs = sum(tr["module_runs"][n] for n in names)
    dispatches = sum(hist.values())
    if not runs or not dispatches:
        return None
    least = 0.0
    for b, n in hist.items():
        c = cost(int(b))
        least += n * max(c["bytes"] / peaks["hbm_bytes_per_s"],
                         c["ops"] / peaks[c["ops_peak"]])
    # the histogram covers the whole window, the trace its first part
    device_s = sum(tr["module_s"][n] for n in names) * dispatches / runs
    return 100.0 * least / device_s
