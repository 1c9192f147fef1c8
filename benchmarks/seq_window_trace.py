"""Readers' side of the window / full attention pattern's cell: the short
block's turn programs read apart from the long blocks' chains.

A window holds a miss one request in fifty, so the ten traced seconds hold
one or none, and a piece is forty turns' device time: read together, the
trace's time by scope is whatever that draw makes it (two traced runs of one
tree read ``win_attn`` at 8.4% and 20.0% of the busy time; PERF.md, PR 46).
The turn programs (``jit_seq_turn_*``) run a few hundred times in any traced
part; the metrics read those, and print the chains' (``jit_seq_win_*``,
``jit_seq_gqa_*``, ...) beside them where the trace holds any.
"""

from __future__ import annotations

from benchmarks import program_spans, seq_trace

TURN = "_seq_turn_"


def scopes_by_block(ev: dict):
    """``{"turn": ..., "piece": ...}``, each ``seq_trace.scope_seconds``'
    reduction over that kind of executable alone, or None."""
    scopes = ev.get("device_scopes")
    if not ev.get("trace") or not scopes:
        return None
    if "seq_window_scope_s" not in ev:
        path = program_spans.newest_trace()
        ev["seq_window_scope_s"] = path and {
            kind: seq_trace.scope_seconds(path, {
                name: found for name, found in scopes.items()
                if (TURN in name) == (kind == "turn")})
            for kind in ("turn", "piece")}
        print(f"device seconds by named scope, turn programs and chains "
              f"apart: {ev['seq_window_scope_s']}", flush=True)
    return ev["seq_window_scope_s"] or None


def dispatches(ev: dict):
    """The traced part's answered requests as the dispatches the ladder cuts
    them into: ``{"turn": (offsets, counts), "piece": (offsets, counts)}``.
    A request that computes at most ``short_block`` tokens is one turn; a
    longer one runs in pieces of ``piece`` tokens from its offset, and a tail
    of at most ``short_block`` as a turn."""
    part, shape = seq_trace.traced_requests(ev), ev.get("shape") or {}
    short, piece = shape.get("short_block"), shape.get("piece")
    if not part or not short or not piece:
        return None
    out = {"turn": ([], []), "piece": ([], [])}
    for offset, count in zip(part[0], part[1]):
        offset, count = int(offset), int(count)
        while count > 0:
            n = min(count, piece)
            kind = "turn" if n <= short else "piece"
            out[kind][0].append(offset)
            out[kind][1].append(n)
            offset, count = offset + n, count - n
    return out
