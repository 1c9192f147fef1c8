"""The client's 99th percentile of latency from the due instant, over all
requests of the window (a failed request counts as the window length).
Recorded, not judged: the server's event loop stops for about 110 ms a few
times a minute (PERF.md section 6, PR 23), so the 99th percentile reads 20 or
100 ms by whether two or five such stops fell into the window; the judged
tail is ``serve_within_limit_pct``."""


def read(ev: dict):
    return (ev.get("loadgen") or {}).get("p99_ms")
