"""Micro-batcher: mean number of dispatch slots the window's batches ran
under, delta ``pio_serving_batch_slots_total`` / delta ``pio_serving_batches``
(the bound = the adaptive limiter never shrank it; 1.0 = one slot throughout).
A program without the counter reads nothing."""


def read(ev: dict):
    a, b = ev.get("metrics_after"), ev.get("metrics_before")
    if not a or not b or "pio_serving_batch_slots_total" not in a:
        return None
    batches = a["pio_serving_batches"] - b.get("pio_serving_batches", 0.0)
    slots = (a["pio_serving_batch_slots_total"]
             - b.get("pio_serving_batch_slots_total", 0.0))
    return slots / batches if batches > 0 else None
