"""Readers' side of the program's own spans (``obs/trace.span``, PR 24).

The program times its layers itself and publishes every span three ways; the
readers under ``layer_metrics/`` take each from where the run already left it:

- the aggregate on ``/metrics``: a span named ``<scope>.<phase>`` is a row of
  ``pio_profile_phase_seconds_total`` / ``pio_profile_phases_total``
  (:func:`mean_s`, :func:`total_s`; serving cells, from ``metrics_before`` /
  ``metrics_after``);
- the ring ``obs.trace.TRACES`` of this process (:func:`verb_span_s`; training
  cells: the spans of the traces whose ``train.verb`` root names one of the
  window's instances);
- the profiler's timeline: a thread-scoped span is a ``pio.<name>`` event on
  ``/host:CPU`` of the same ``.xplane.pb`` as the device's ``XLA Ops`` line
  (:func:`idle_by_span`; the runners do not hand the file over, so the newest
  one under ``benchmarks/_work/*/trace/`` is read: the run's own, written
  seconds before).

A program that has none of these (a commit before PR 24) gives ``None``
everywhere: nothing here raises for what is missing.
"""

from __future__ import annotations

import glob
import os

from benchmarks import trace_reduce

SPAN_PREFIX = "pio."
UNSPANNED = "host:unspanned"
_SECONDS = "pio_profile_phase_seconds_total"
_COUNT = "pio_profile_phases_total"


# -- the aggregate on /metrics ----------------------------------------------------

def _row(family: str, span: str) -> str:
    scope, _, phase = span.rpartition(".")
    return f'{family}{{scope="{scope}",phase="{phase}"}}'


def total_s(metrics: dict | None, span: str):
    """Seconds the span has taken since the process started, or None."""
    return (metrics or {}).get(_row(_SECONDS, span))


def window(ev: dict, span: str):
    """``(seconds, count)`` of the span inside the measured window, or None
    where the program publishes no such span or none finished."""
    a, b = ev.get("metrics_after"), ev.get("metrics_before")
    if not a or b is None or _row(_COUNT, span) not in a:
        return None
    n = a[_row(_COUNT, span)] - b.get(_row(_COUNT, span), 0.0)
    s = a[_row(_SECONDS, span)] - b.get(_row(_SECONDS, span), 0.0)
    return (s, n) if n > 0 else None


def mean_s(ev: dict, span: str):
    """Mean seconds of one such span over the window."""
    w = window(ev, span)
    return None if w is None else w[0] / w[1]


# -- the ring of the program's process ----------------------------------------------

def _ring_spans() -> list:
    try:
        from incubator_predictionio_tpu.obs.trace import TRACES
    except ImportError:
        return []
    return TRACES.spans()


def verb_span_s(ev: dict, span: str, spans: list | None = None):
    """Mean over the window's verbs of the seconds spent under ``span`` inside
    one ``run_train`` (all spans of that name in the verb's trace). The verbs
    are ``ev["verbs"]`` (the warm-up verb is not among them); a verb's trace
    is the one whose ``train.verb`` span carries its instance id."""
    verbs = ev.get("verbs")
    if not verbs:
        return None
    spans = _ring_spans() if spans is None else spans
    wanted = {v["instance_id"] for v in verbs}
    trace_of = {s["traceId"] for s in spans
                if s["name"] == "train.verb"
                and s["attrs"].get("instance") in wanted}
    if not trace_of:
        return None
    per_trace = dict.fromkeys(trace_of, 0.0)
    for s in spans:
        if s["name"] == span and s["traceId"] in per_trace:
            per_trace[s["traceId"]] += s["durationSec"]
    return sum(per_trace.values()) / len(per_trace)


# -- the profiler's timeline -----------------------------------------------------------

def newest_trace(root: str | None = None):
    """The newest ``*.xplane.pb`` under ``benchmarks/_work/*/trace/``."""
    root = root or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "_work")
    found = glob.glob(os.path.join(
        root, "*", "trace", "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> tuple[list, list]:
    """``(device op intervals [(start_ns, end_ns)], program spans [(name,
    start_ns, end_ns)])`` of one trace file; the span names lose their
    ``pio.`` prefix."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = (lines.get(trace_reduce.OPS_LINE)
                    or lines.get(trace_reduce.MODULES_LINE))
            if line is not None:
                ops.extend((float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns))
                           for e in line.events)
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        start = float(e.start_ns)
                        spans.append((e.name[len(SPAN_PREFIX):], start,
                                      start + float(e.duration_ns)))
    return ops, spans


def idle_by_span(ops: list, spans: list):
    """Seconds of device idle time by the innermost program span covering
    each part of each gap, :data:`UNSPANNED` for the rest; over the extent of
    the device's operations and the program's spans together. None where the
    trace holds no operation or no program span."""
    if not ops or not spans:
        return None
    busy = trace_reduce.union(ops)
    t_min = min(busy[0][0], min(s[1] for s in spans))
    t_max = max(busy[-1][1], max(s[2] for s in spans))
    return trace_reduce.attribute_gaps(
        trace_reduce.gaps(busy, t_min, t_max), spans)


def unspanned_pct(ev: dict, path: str | None = None, top: int = 12):
    """100 x idle seconds under no program span / all idle seconds of the
    traced part of the window; prints the idle seconds per span."""
    if not ev.get("trace"):
        return None
    path = path or newest_trace()
    if path is None:
        return None
    idle = idle_by_span(*load(path))
    if not idle:
        return None
    total = sum(idle.values())
    rows = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    print(f"device idle by program span ({total:.3f} s idle; top {top}):",
          flush=True)
    for name, s in rows:
        print(f"  {name:<28} {s:9.4f} s  {100.0 * s / total:5.1f}%",
              flush=True)
    return 100.0 * idle.get(UNSPANNED, 0.0) / total if total > 0 else None
