"""The plain reference of the `spot10k_stp` venue: benchmark/reference.py's rules
(per-symbol price-time priority, one order at a time, in stream order) under
self-trade prevention, rule "expire the taker" (Binance spot
selfTradePreventionMode EXPIRE_TAKER; CME tag 8000 N, cancel newest; Coinbase
Exchange stp cn). Own means equal uid.

For an add of `volume` at limit `price`, let C be the crossing prefix of the
opposite side: asks at or under the limit for a buy, bids at or over it for a
sell, every level for a market order; best price first, first in first out
inside a level. Let j be the first order of C whose uid is the add's own.

  * The add fills down C ahead of j exactly as without the rule (same events).
    If volume is left when it arrives at j, the remainder expires: it does not
    trade with j, does not pass j, does not rest, makes no event and is no
    cancel target. If the volume runs out ahead of j, or C holds no own order,
    nothing differs. The resting order j is never touched.
  * limit (kind 0): as above; with no own order in its way its remainder rests
    at its own price.
  * market (kind 1) and immediate or cancel (kind 3): as above; their
    remainder is dropped anyway, only the stop at j differs.
  * fill or kill (kind 4): what C can give is the lots ahead of j; less than
    `volume` and nothing happens: no fill, no event, the book untouched. The
    lots are summed before anything is touched.
  * post only (kind 6): if C is not empty nothing happens, whoever owns C's
    first order (an add whose only crossing order is its owner's must not
    rest into a crossed book); otherwise it rests as a limit add does.
  * a cancel takes no notice of the owner.

The venue's flow sends limit and market orders; the other kinds are stated and
kept so that the rule is whole. It imports nothing of the program.
"""

import bisect
from collections import deque

from benchmark import reference

#: The guarantee the configuration states, and the control that breaks it.
PRIORITY = "fifo"
CONTROL_PRIORITY = "lifo"
LIMIT, MARKET, IOC, FOK, POST_ONLY = 0, 1, 3, 4, 6
BUY = reference.BUY


class Book(reference.Book):
    def crossed(self, side, kind, price) -> list:
        """Occupied prices of the opposite side that the add crosses (C's
        levels), best first."""
        opposite = self.prices[1 - side]
        if kind == MARKET:
            return opposite[:] if side == BUY else opposite[::-1]
        if side == BUY:
            return [p for p in opposite if p <= price]
        return [p for p in reversed(opposite) if p >= price]

    def ahead(self, side, uid, crossed) -> int:
        """The lots of C that lie ahead of the first order of `uid`."""
        levels, lots = self.levels[1 - side], 0
        for p in crossed:
            for node in (reversed(levels[p]) if self.lifo else levels[p]):
                if node[1] == uid:
                    return lots
                lots += node[2]
        return lots

    def add(self, i, sym, uid, oid, side, kind, price, volume, emit,
            gone=None) -> bool:
        opp = 1 - side
        crossed = self.crossed(side, kind, price)
        if kind == POST_ONLY and crossed:
            return False  # it would take, from whomever: nothing happens
        if kind == FOK and self.ahead(side, uid, crossed) < volume:
            return False  # killed: nothing happens
        levels, prices = self.levels[opp], self.prices[opp]
        remaining, stopped = volume, False
        for best in crossed:
            level = levels[best]
            while remaining > 0 and level:
                node = level[-1] if self.lifo else level[0]
                if node[1] == uid:
                    stopped = True  # its owner's order: the remainder expires
                    break
                if remaining >= node[2]:
                    traded = node[2]
                    remaining -= traded
                    if self.lifo:
                        level.pop()
                    else:
                        level.popleft()
                    self.count[opp] -= 1
                    if gone is not None:
                        gone.append(node[0])
                    emit((i, sym, uid, oid, side, price, remaining,
                          node[1], node[0], opp, best, traded, traded))
                else:
                    traded = remaining
                    node[2] -= traded
                    remaining = 0
                    emit((i, sym, uid, oid, side, price, 0,
                          node[1], node[0], opp, best, node[2], traded))
            if not level:  # the best level of its side, as C is walked
                del levels[best]
                if side == BUY:
                    del prices[0]
                else:
                    prices.pop()
            if stopped or remaining == 0:
                break
        if remaining == 0 or stopped or kind not in (LIMIT, POST_ONLY):
            return False
        own = self.levels[side]
        level = own.get(price)
        if level is None:
            level = own[price] = deque()
            bisect.insort(self.prices[side], price)
        level.append([oid, uid, remaining])
        self.count[side] += 1
        return True


def run(cols, priority=PRIORITY):
    """Events of the stream columns; see benchmark.reference.run."""
    return reference.run(cols, priority, Book)
