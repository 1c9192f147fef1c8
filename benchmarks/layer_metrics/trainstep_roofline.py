"""Kernels: the train step's share of its roofline, in %. The least time one
dense-adam step could take on this chip (costs/trainstep.py: the larger of
bytes / HBM peak and operations / bf16 peak; HBM bytes bound it) over the
device time of one step, which is the trace's time in the train executable
(``jit__train_epochs``) / (its runs x steps per run)."""

from benchmarks.costs import trainstep

MODULE = "_train_epochs"


def read(ev: dict):
    tr, shape, peaks = ev.get("trace"), ev.get("shape") or {}, ev.get("peaks")
    if not tr or ev.get("kind") != "train":
        return None
    names = [n for n in tr["module_s"] if MODULE in n]
    runs = sum(tr["module_runs"][n] for n in names)
    if not runs:
        return None
    per_step = (sum(tr["module_s"][n] for n in names)
                / (runs * shape["steps_per_verb"]))
    c = trainstep.cost(shape["n_users"], shape["n_items"], shape["rank"],
                       shape["batch"])
    least = max(c["bytes"] / peaks["hbm_bytes_per_s"],
                c["ops"] / peaks[c["ops_peak"]])
    return 100.0 * least / per_step
