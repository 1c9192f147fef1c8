"""Trainer: one-time seconds before the first step, ``stage_sec`` (host sort,
h2d of the staged batches) + ``init_sec`` (tables and moments on the device),
mean over the verbs."""


def read(ev: dict):
    verbs = ev.get("verbs")
    if not verbs:
        return None
    return sum(v["timings"]["stage_sec"] + v["timings"]["init_sec"]
               for v in verbs) / len(verbs)
