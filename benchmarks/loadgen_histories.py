"""The histories cell's generator: ``loadgen_sessions.py``'s plan, schedule,
sockets and accounting (imported, not copied), with every request's BYTES made
before the first send as well.

    python3 benchmarks/loadgen_histories.py <spec.json>

``loadgen_sessions.drive`` formats a request's body at its due instant, inside
the latency it then reports. For lists of a few hundred items that is
nothing; here a list is 256-14k items and the formatting alone takes 0.28 ms
a thousand items (0.3 ms at 1k, 4.0 ms at 14k on the sandbox's CPU: PERF.md,
PR 46), on the generator's one thread, which every socket's read waits for.
A run's median then follows the lengths of the sessions its seed makes
popular, by the generator's own work and not the server's. Everything else of
a run is fixed before the first send already; so are the bytes here (the
plan is simulated whole, so each request's list is known): a few tens of MB
for a window.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import loadgen_sessions as base  # noqa: E402


def payloads(spec: dict) -> dict:
    """``{(session, items sent): request bytes}`` of every request of the
    run (a session never sends a list of one length twice: a turn grows it)."""
    host, port, num = spec["host"], int(spec["port"]), int(spec["num"])
    p = base.plan(spec)
    return {(int(sid), int(n)): base._payload(
        host, port, int(sid), p["sessions"][sid][:int(n)], num)
        for sid, n in zip(p["sid"], p["length"])}


async def drive(spec: dict) -> dict:
    made = payloads(spec)
    # (``drive`` looks its formatter up in its module at every request)
    base._payload = lambda host, port, sid, items, num: made[sid, len(items)]
    return await base.drive(spec)


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        _spec = json.load(f)
    print(json.dumps(asyncio.run(drive(_spec))), flush=True)
