"""Retrieval: median time of one batch dispatch (``batch_predict`` through
``recommend_batch``), from ``GET /`` ``dispatchSecPercentiles``."""


def read(ev: dict):
    p = (ev.get("status") or {}).get("dispatchSecPercentiles") or {}
    return None if p.get("p50") is None else p["p50"] * 1e3
