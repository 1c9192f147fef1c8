"""Workflow, serving cells: seconds of the deploy spent warming every batch
bucket (one ``deploy.warmup.bucket`` child per dispatch shape), span
``deploy.warmup``. Absolute, from the counters after the window."""

from benchmarks import program_spans


def read(ev: dict):
    return program_spans.total_s(ev.get("metrics_after"), "deploy.warmup")
