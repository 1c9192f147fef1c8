"""Retrieval, two-stage cells only: mean host rerank time per batch over the
window, delta sum / delta count of ``pio_retrieval_rerank_seconds``."""


def read(ev: dict):
    a, b = ev.get("metrics_after"), ev.get("metrics_before")
    if not a or not b:
        return None
    n = (a.get("pio_retrieval_rerank_seconds_count", 0.0)
         - b.get("pio_retrieval_rerank_seconds_count", 0.0))
    s = (a.get("pio_retrieval_rerank_seconds_sum", 0.0)
         - b.get("pio_retrieval_rerank_seconds_sum", 0.0))
    return s / n * 1e3 if n > 0 else None
