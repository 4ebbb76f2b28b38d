"""The plain reference of the `spot10k_tif` venue: benchmark/reference.py's rules
(per-symbol price-time priority, one order at a time, in stream order) plus three
kinds of add that carry a time in force. For an add of `volume` at limit `price`,
let C be the crossing prefix of the opposite side: asks at or under the limit for
a buy, bids at or over it for a sell, best price first, first in first out
inside a level.

  * immediate or cancel (kind 3): fills down C as a limit add does; what is left
    is dropped: it never rests, makes no event and is no cancel target.
  * fill or kill (kind 4): if all of C holds at least `volume` lots it fills down
    C as a limit add does and nothing is left; otherwise nothing happens: no
    fill, no event, the book untouched. The lots are summed before anything is
    touched.
  * post only (kind 6): if C is not empty (an equal price crosses) nothing
    happens; otherwise it rests at `price` at the tail of its level, as a limit
    add does.

A cancel takes no notice of the kind. It imports nothing of the program.
"""

from benchmark import reference

#: The guarantee the configuration states, and the control that breaks it.
PRIORITY = "fifo"
CONTROL_PRIORITY = "lifo"
IOC, FOK, POST_ONLY = 3, 4, 6


class Book(reference.Book):
    def crossed(self, side, price) -> list:
        """Occupied prices of the opposite side that an add at `price`
        crosses (C's levels), best first."""
        opposite = self.prices[1 - side]
        if side == reference.BUY:
            return [p for p in opposite if p <= price]
        return [p for p in reversed(opposite) if p >= price]

    def add(self, i, sym, uid, oid, side, kind, price, volume, emit,
            gone=None) -> bool:
        if kind == POST_ONLY:
            if self.crossed(side, price):
                return False  # it would take: nothing happens
            kind = reference.LIMIT  # nothing to cross: it rests
        elif kind == FOK:
            levels = self.levels[1 - side]
            available = sum(node[2] for p in self.crossed(side, price)
                            for node in levels[p])
            if available < volume:
                return False  # killed: nothing happens
            kind = reference.LIMIT  # fills whole: nothing is left to rest
        if kind != IOC:
            return super().add(i, sym, uid, oid, side, kind, price, volume,
                               emit, gone)
        if super().add(i, sym, uid, oid, side, reference.LIMIT, price, volume,
                       emit, gone):
            self.drop_newest(side, price)  # the remainder came last
        return False

    def drop_newest(self, side, price) -> None:
        """Take the newest order of `side` at `price` out of the book."""
        level = self.levels[side][price]
        level.pop()
        if not level:
            del self.levels[side][price]
            self.prices[side].remove(price)
        self.count[side] -= 1


def run(cols, priority=PRIORITY):
    """Events of the stream columns; see benchmark.reference.run."""
    return reference.run(cols, priority, Book)
