"""The plain cache the tiered, collected KVCache is held to: a dict of key
-> entry bytes with touch times, a get that answers the bytes or a miss, a
capacity pass that removes oldest-touched first down to a budget. Imports
nothing of the program.

On the CPU the tests drive the program and this model step by step (with a
copy of their own: tier-1 does not import perfbench). On the chip the
collector's ticks are not the driver's to order, so the model supplies what
can be held to there: the bytes every answer is compared with (`verdict`)
and the rule for an absence — an acknowledged entry is there and exact, or
the collector's audit trail names it."""

from __future__ import annotations

MISS = None


class Cache:
    def __init__(self):
        self.entries: dict = {}   # key -> [value, touched]

    def put(self, key: str, value: bytes, now: float) -> None:
        self.entries[key] = [bytes(value), now]

    def get(self, key: str, now: float):
        """-> the entry's bytes, touched, or MISS."""
        entry = self.entries.get(key)
        if entry is None:
            return MISS
        entry[1] = now
        return entry[0]

    def touch(self, key: str, now: float) -> None:
        if key in self.entries:
            self.entries[key][1] = now

    def resident(self) -> int:
        return sum(len(v) for v, _ in self.entries.values())

    def capacity_pass(self, budget: int) -> list:
        """Oldest-touched first until what is left fits the budget.
        -> the keys removed, in the order they went."""
        total = self.resident()
        gone = []
        for key in sorted(self.entries, key=lambda k: (self.entries[k][1],
                                                       k)):
            if total <= budget:
                break
            total -= len(self.entries[key][0])
            del self.entries[key]
            gone.append(key)
        return gone


def verdict(got, want: bytes, named_removed: bool) -> bool:
    """One answer of the system for one acknowledged entry: exact, or a
    miss that the collector's audit trail accounts for. Anything else
    (zeros, a torn or stale payload, an absence nobody named) is wrong."""
    if got is MISS:
        return named_removed
    return bytes(got) == want
