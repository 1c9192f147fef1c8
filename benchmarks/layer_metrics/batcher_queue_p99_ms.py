"""Micro-batcher: 99th percentile of the time a query waited for a dispatch
slot, from ``GET /`` ``queueDelaySecPercentiles`` (the server's own ring)."""


def read(ev: dict):
    p = (ev.get("status") or {}).get("queueDelaySecPercentiles") or {}
    return None if p.get("p99") is None else p["p99"] * 1e3
