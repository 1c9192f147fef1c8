"""Device, serving cells: 100 * (1 - busy / traced window), busy being the
union of the intervals in which an operation ran on the TPU plane."""


def read(ev: dict):
    tr, w = ev.get("trace"), ev.get("trace_window_s")
    if not tr or not w or ev.get("kind") == "train":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / w)
