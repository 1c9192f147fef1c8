"""Readers' side of the sequence-serving cell: device time by named scope
from a trace, and counter deltas over the window.

A TPU trace names an operation by its HLO instruction (``%fusion.7 = ...``),
not by the ``jax.named_scope`` it was traced under. The program publishes
``{executable: {instruction: scope}}`` from its compiled programs' own
metadata (``LatentServing.device_scopes``); an operation belongs to the
executable whose run (``XLA Modules`` line) contains it. A program without
that map, or a trace without those executables, gives ``None``: nothing here
raises for what is missing.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from benchmarks import program_spans, trace_reduce

_LABEL = re.compile(r'(\w+)="([^"]*)"')


def scope_seconds(path: str, scopes: dict) -> dict:
    """``{"scope_s": {scope: s}, "unscoped_s": s, "module_runs": {name: n}}``
    over the executables ``scopes`` names; device seconds summed over chips."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    scope_s, runs = defaultdict(float), defaultdict(int)
    unscoped = 0.0
    for plane in data.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace_reduce.MODULES_LINE not in lines \
                or trace_reduce.OPS_LINE not in lines:
            continue
        mods = sorted(
            (float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             trace_reduce.module_name(e.name))
            for e in lines[trace_reduce.MODULES_LINE].events)
        starts = [m[0] for m in mods]
        for _, _, name in mods:
            if name in scopes:
                runs[name] += 1
        for e in lines[trace_reduce.OPS_LINE].events:
            i = bisect.bisect_right(starts, float(e.start_ns)) - 1
            if i < 0 or float(e.start_ns) >= mods[i][1]:
                continue
            of_module = scopes.get(mods[i][2])
            if of_module is None:
                continue
            instruction = e.name.partition(" = ")[0].strip().lstrip("%")
            scope = of_module.get(instruction)
            if scope is None:
                unscoped += float(e.duration_ns) / 1e9
            else:
                scope_s[scope] += float(e.duration_ns) / 1e9
    return {"scope_s": dict(scope_s), "unscoped_s": unscoped,
            "module_runs": dict(runs)}


def traced_scopes(ev: dict):
    """The reduction above for this run's trace, or None."""
    scopes = ev.get("device_scopes")
    if not ev.get("trace") or not scopes:
        return None
    if "seq_scope_s" not in ev:
        path = program_spans.newest_trace()
        ev["seq_scope_s"] = scope_seconds(path, scopes) if path else None
        print(f"device seconds by named scope: {ev['seq_scope_s']}",
              flush=True)
    return ev["seq_scope_s"]


def delta(ev: dict, family: str) -> dict:
    """``{labels tuple: increase over the window}`` of one counter family, or
    an empty dict where the program publishes none."""
    after, before = ev.get("metrics_after") or {}, ev.get("metrics_before")
    out = {}
    if before is None:
        return out
    for key, value in after.items():
        name, _, labels = key.partition("{")
        if name != family:
            continue
        d = value - before.get(key, 0.0)
        out[tuple(sorted(_LABEL.findall(labels)))] = d
    return out


def total(ev: dict, family: str):
    d = delta(ev, family)
    return sum(d.values()) if d else None


def traced_requests(ev: dict):
    """The window's answered requests that were due inside the traced part
    (the trace opens with the window), as ``(reused, computed)`` arrays, and
    their share of all computed tokens."""
    req, span_s = ev.get("requests"), ev.get("trace_window_s")
    if not req or not span_s:
        return None
    inside = (req["due"] < span_s) & req["ok"]
    all_computed = float(req["computed"][req["ok"]].sum())
    if not inside.any() or not all_computed:
        return None
    return (req["reused"][inside], req["computed"][inside],
            float(req["computed"][inside].sum()) / all_computed)
