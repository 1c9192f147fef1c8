"""From a profiler trace (``*.xplane.pb``) to numbers: device busy time as the
union of the intervals in which an operation ran, time per operation and per
executable, and the idle gaps with what the host was doing in them.

Reads the file with jax alone (``jax.profiler.ProfileData``). The arithmetic
works on plain tuples so that it can be checked without a trace.

What a TPU trace looks like (jax 0.9, one v5e; see testdata/): one plane per
chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO operation and whose line ``XLA Modules`` holds one event per
executable run, named ``<jit name>(<fingerprint>)``; host threads are lines
of the plane ``/host:CPU`` and carry the ``TraceAnnotation`` spans.
"""

from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
_FINGERPRINT = re.compile(r"\(\d+\)$")


def load(path: str) -> list:
    """``[(plane name, [(line name, [(event name, start_ns, dur_ns)])])]``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    events.append((e.name, float(e.start_ns),
                                   float(e.duration_ns)))
            if events:
                lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list, start: float, end: float) -> list:
    """The complement of merged ``busy`` inside ``[start, end]``."""
    out, cur = [], start
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, end)))
        cur = max(cur, e)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    return [(s, e) for s, e in out if e > s]


def module_name(event_name: str) -> str:
    """``jit__train_epochs(1234)`` → ``jit__train_epochs``."""
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%fusion.3 = f32[8,1024]{...} fusion(...)``: keep ``fusion.3 f32[8,1024]``
    (the instruction and its result shape tell one bucket's run from
    another's)."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:120]


def attribute_gaps(idle: list, spans: list) -> dict:
    """Seconds of idle time by the innermost host span covering each part of
    each gap (``spans``: ``[(name, start_ns, end_ns)]``); ``host:unspanned``
    for what no span covers. Inner means shorter: a gap under both a verb's
    span and its persist span counts for persist."""
    import bisect

    out = defaultdict(float)
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0.0)
    for gs, ge in idle:
        lo = bisect.bisect_left(starts, gs - longest)
        hi = bisect.bisect_left(starts, ge)
        cover = sorted((s for s in spans[lo:hi] if s[2] > gs),
                       key=lambda s: s[2] - s[1])
        rest = [(gs, ge)]
        for name, ss, se in cover:
            nxt = []
            for a, b in rest:
                o0, o1 = max(a, ss), min(b, se)
                if o1 <= o0:
                    nxt.append((a, b))
                    continue
                out[name] += (o1 - o0) / 1e9
                if a < o0:
                    nxt.append((a, o0))
                if o1 < b:
                    nxt.append((o1, b))
            rest = nxt
            if not rest:
                break
        for a, b in rest:
            out["host:unspanned"] += (b - a) / 1e9
    return dict(out)


def reduce(planes: list) -> dict:
    """The numbers the readers take: all times in seconds."""
    per_chip_busy = []
    op_s, module_s, module_runs = (defaultdict(float), defaultdict(float),
                                   defaultdict(int))
    all_busy = []
    t_min, t_max = None, None
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        by_line = dict(lines)
        ops = by_line.get(OPS_LINE) or by_line.get(MODULES_LINE) or []
        busy = union((s, s + d) for _, s, d in ops)
        per_chip_busy.append(sum(e - s for s, e in busy) / 1e9)
        all_busy.extend(busy)
        for name, _, d in by_line.get(OPS_LINE, []):
            op_s[op_name(name)] += d / 1e9
        for name, _, d in by_line.get(MODULES_LINE, []):
            module_s[module_name(name)] += d / 1e9
            module_runs[module_name(name)] += 1
    spans = []
    for pname, lines in planes:
        if pname != HOST_PLANE:
            continue
        for _, events in lines:
            spans.extend((n, s, s + d) for n, s, d in events
                         if n.startswith(SPAN_PREFIX))
    for s, e in all_busy:
        t_min = s if t_min is None else min(t_min, s)
        t_max = e if t_max is None else max(t_max, e)
    for _, s, e in spans:
        t_min = s if t_min is None else min(t_min, s)
        t_max = e if t_max is None else max(t_max, e)
    n_chips = len(per_chip_busy)
    idle = gaps(union(all_busy), t_min, t_max) if all_busy else []
    return {
        "chips": n_chips,
        "busy_s": sum(per_chip_busy) / n_chips if n_chips else 0.0,
        "extent_s": (t_max - t_min) / 1e9 if t_min is not None else 0.0,
        "op_s": dict(op_s),
        "module_s": dict(module_s),
        "module_runs": dict(module_runs),
        "idle_by_span_s": attribute_gaps(idle, spans),
        "span_s": _span_totals(spans),
    }


def _span_totals(spans: list) -> dict:
    out = defaultdict(float)
    for name, s, e in spans:
        out[name] += (e - s) / 1e9
    return dict(out)


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The result line's optional ``breakdown``."""
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(reduced["idle_by_span_s"].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}


def reduce_file(path: str) -> dict:
    return reduce(load(path))
