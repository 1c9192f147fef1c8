"""Sequence serving: mean milliseconds a dispatch of the short block (turns,
a cut block's tail, a short miss) spent building its operands: the numpy
arrays and, for a layer pattern, their one ``device_put``; span
``seq.turn.stage`` over the window. With ``seq_turn_launch_ms`` and
``seq_turn_wait_ms`` it covers ``seq.batch.extend`` of such a dispatch;
``seq_turn_device_ms`` is the chip's part of the same."""

from benchmarks import program_spans


def read(ev: dict):
    s = program_spans.mean_s(ev, "seq.turn.stage")
    return None if s is None else s * 1e3
