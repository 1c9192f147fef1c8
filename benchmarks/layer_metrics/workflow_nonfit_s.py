"""Workflow: seconds of a verb outside the fit's own four phases (BiMaps, IVF
build, orbax persist, metadata, DataSource hand-over): verb wall -
(stage + init + train + gather of ``model.timings``), mean over the verbs."""


def read(ev: dict):
    verbs = ev.get("verbs")
    if not verbs:
        return None
    return sum(v["wall_s"] - sum(v["timings"].values())
               for v in verbs) / len(verbs)
