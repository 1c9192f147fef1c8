"""Sequence serving, the window / full attention pattern: the share of the
turn programs' device time (``jit_seq_turn_*`` in the traced part of the
window: every operation of theirs, scoped or not) that lies under the
attention layers' scopes (``win_attn``, ``gqa_attn``, ``gqa_proj``), in %:
how a turn's device time is shared between the two kinds of attention and
the experts and head. Printed with the window layers' and the full layers'
attention apart, and the same for the long blocks' chains where the trace
holds a piece (``seq_window_trace``). A program without a ``win_attn`` scope
reads nothing."""

from benchmarks import seq_window_trace

SCOPES = ("win_attn", "gqa_attn", "gqa_proj")


def read(ev: dict):
    tr = seq_window_trace.scopes_by_block(ev)
    if not tr or not tr["turn"]["scope_s"].get("win_attn"):
        return None
    shares = {}
    for kind, found in tr.items():
        whole = sum(found["scope_s"].values()) + found["unscoped_s"]
        if whole:
            shares[kind] = {n: 100.0 * found["scope_s"].get(n, 0.0) / whole
                            for n in SCOPES}
    print("attention's share of the programs' device time, in %: " + str(
        {kind: {n: round(s, 2) for n, s in part.items()}
         for kind, part in shares.items()}), flush=True)
    return sum(shares["turn"].values())
