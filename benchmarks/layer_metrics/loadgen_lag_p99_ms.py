"""The benchmark's own load generator: 99th percentile of (actual send - due
instant). A starved generator must not be read as a fast server."""


def read(ev: dict):
    return (ev.get("loadgen") or {}).get("lag_p99_ms")
