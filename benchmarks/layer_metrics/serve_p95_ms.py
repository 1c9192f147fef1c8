"""The client's 95th percentile of latency from the due instant, over all
requests of the window. Recorded, not judged: steady to 3% on the exact path,
but between 17 and 37 ms from one window to the next on the two-stage path
(PERF.md section 2); the judged tail is ``serve_within_limit_pct``."""


def read(ev: dict):
    return (ev.get("loadgen") or {}).get("p95_ms")
