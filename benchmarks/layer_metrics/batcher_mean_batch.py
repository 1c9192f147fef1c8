"""Micro-batcher: queries per dispatched batch over the window,
delta ``pio_serving_requests`` / delta ``pio_serving_batches``."""


def read(ev: dict):
    a, b = ev.get("metrics_after"), ev.get("metrics_before")
    if not a or not b or "pio_serving_batches" not in a:
        return None
    batches = a["pio_serving_batches"] - b.get("pio_serving_batches", 0.0)
    reqs = a["pio_serving_requests"] - b.get("pio_serving_requests", 0.0)
    return reqs / batches if batches > 0 else None
