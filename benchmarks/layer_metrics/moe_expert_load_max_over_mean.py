"""Routed experts: the busiest held expert's token-picks over the mean of
all experts held here, in the worst layer, over the window
(``pio_moe_expert_tokens_total{layer,expert}``; an expert that got no pick
counts as zero). 1 is an even spread; the grouped matmul's longest group sets
its time."""

from benchmarks import seq_trace


def read(ev: dict):
    picks = seq_trace.delta(ev, "pio_moe_expert_tokens_total")
    info = ((ev.get("status") or {}).get("servingPaths") or [{}])[0] or {}
    held = info.get("experts_held")
    if not picks or not held:
        return None
    layers: dict = {}
    for labels, n in picks.items():
        layers.setdefault(dict(labels)["layer"], []).append(n)
    worst = [max(v) / (sum(v) / held) for v in layers.values() if sum(v) > 0]
    return max(worst) if worst else None
