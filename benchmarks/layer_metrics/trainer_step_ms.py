"""Trainer: host-clock milliseconds per train step, ``train_sec`` of the fit
(fenced by the blocking read of the loss) / steps, mean over the verbs."""


def read(ev: dict):
    verbs, shape = ev.get("verbs"), ev.get("shape") or {}
    if not verbs or not shape.get("steps_per_verb"):
        return None
    return (sum(v["timings"]["train_sec"] for v in verbs) / len(verbs)
            / shape["steps_per_verb"] * 1e3)
