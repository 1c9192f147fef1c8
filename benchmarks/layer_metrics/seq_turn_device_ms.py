"""Kernels: mean milliseconds the chip spent on one dispatch of the short
block in the traced part of the window: device seconds of that block's
executables (``jit_seq_<kind>_b<B>_t<short_block>[_c<C>]`` on the trace's
``XLA Modules`` line, every batch and context bucket) over the runs of its
embed program, one a dispatch. Beside ``seq_turn_stage_ms`` +
``seq_turn_launch_ms`` + ``seq_turn_wait_ms``, the same dispatch's wall: the
difference is what the host and the runtime add to a turn. Reads nothing
where the program does not publish those spans."""

import re

from benchmarks import program_spans


def read(ev: dict):
    tr, short = ev.get("trace"), (ev.get("shape") or {}).get("short_block")
    if not tr or not short \
            or program_spans.window(ev, "seq.turn.launch") is None:
        return None
    name = re.compile(rf"^jit_seq_([a-z]+)_b\d+_t{int(short)}(?:_c\d+)?$")
    device_s = dispatches = 0.0
    for module, s in tr["module_s"].items():
        m = name.match(module)
        if m:
            device_s += s
            if m.group(1) == "embed":
                dispatches += tr["module_runs"][module]
    return device_s / dispatches * 1e3 if dispatches else None
