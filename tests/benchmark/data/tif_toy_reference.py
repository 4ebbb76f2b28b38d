"""The plain reference of the `tif_toy` venue: benchmark/reference.py's rules
plus an immediate-or-cancel add (kind 2), which crosses like a limit order up
to its limit price; what is left of it is dropped: it never rests, makes no
event and is no cancel target. It imports nothing of the program.
"""

import bisect

from benchmark import reference

PRIORITY = "fifo"
CONTROL_PRIORITY = "lifo"
IOC = 2


class Book(reference.Book):
    def add(self, i, sym, uid, oid, side, kind, price, volume, emit,
            gone=None) -> bool:
        if kind != IOC:
            return super().add(i, sym, uid, oid, side, kind, price, volume,
                               emit, gone)
        if super().add(i, sym, uid, oid, side, reference.LIMIT, price, volume,
                       emit, gone):
            level = self.levels[side][price]  # the remainder came last: drop it
            level.pop()
            if not level:
                del self.levels[side][price]
                occupied = self.prices[side]
                del occupied[bisect.bisect_left(occupied, price)]
            self.count[side] -= 1
        return False


def run(cols, priority=PRIORITY):
    """Events of the stream columns; see benchmark.reference.run."""
    return reference.run(cols, priority, Book)
