"""Transparent event-store proxy counting storage READ calls.

The batched-serving regression tests assert the O(1)-reads-per-batch
property by counting this method set: one place says what counts as a
read.
"""

from __future__ import annotations


class CountingEvents:
    def __init__(self, inner):
        self._inner = inner
        self.counts = {"find": 0, "find_by_entities": 0}

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name in self.counts:
            def wrapper(*a, _attr=attr, _name=name, **kw):
                self.counts[_name] += 1
                return _attr(*a, **kw)
            return wrapper
        return attr

    @property
    def total_reads(self) -> int:
        return sum(self.counts.values())
