"""The plain reference: price-time priority matching, one order at a time.

Independent of the program: it imports nothing from gome_tpu and takes nothing
the program made. It follows the published semantics of lxalano/gome
(engine.go SetOrder / DeleteOrder / MatchOrder) plus the MARKET extension of
BASELINE.json configs[4]:

  * a limit ADD crosses opposing levels best price first, FIFO inside a level;
    its remainder rests at its own price. A MARKET add crosses every level and
    its remainder is dropped.
  * each fill is one event: taker (volume = remaining after the fill), maker
    (volume = its pre-fill volume when fully filled, else what remains), and
    the traded volume. The fill price is the maker's.
  * a cancel needs the resting order's side, exact price and oid; it takes no
    notice of the owner. A hit is one event carrying the request's fields and
    the resting remainder, traded volume 0; a miss is silent.

Events are tuples in EVENT_FIELDS order, all scaled integers. `priority`
selects the control that breaks the configuration's stated guarantee: "lifo"
serves the newest order of a level first (time priority broken).

The generator (stream.py) keeps one Book per symbol in its loop, so a stream's
expected events come with the stream; `run` replays a finished stream, which is
how the control is computed. A venue with other rules brings a Book of its own
in configs/<name>_reference.py (a subclass, as a rule); the generator's loop and
`run(..., Book=...)` then keep that one.
"""

from __future__ import annotations

import bisect
from collections import deque

EVENT_FIELDS = (
    "order", "sym", "taker_uid", "taker_oid", "taker_side", "taker_price",
    "taker_volume", "maker_uid", "maker_oid", "maker_side", "maker_price",
    "maker_volume", "match_volume",
)
BUY, SALE = 0, 1
LIMIT, MARKET = 0, 1


class Book:
    """One symbol: per side a dict price -> FIFO of [oid, uid, volume], the
    occupied prices in ascending order, and the resting count."""

    __slots__ = ("levels", "prices", "count", "lifo")

    def __init__(self, priority: str = "fifo"):
        if priority not in ("fifo", "lifo"):
            raise ValueError(priority)
        self.levels = ({}, {})
        self.prices = ([], [])
        self.count = [0, 0]
        self.lifo = priority == "lifo"

    def cancel(self, i, sym, uid, oid, side, price, emit) -> bool:
        """True on a hit (one event); a miss is silent."""
        level = self.levels[side].get(price)
        if level is None:
            return False
        for node in level:
            if node[0] == oid:
                break
        else:
            return False
        level.remove(node)
        if not level:
            del self.levels[side][price]
            ps = self.prices[side]
            del ps[bisect.bisect_left(ps, price)]
        self.count[side] -= 1
        emit((i, sym, uid, oid, side, price, node[2],
              uid, oid, side, price, node[2], 0))
        return True

    def add(self, i, sym, uid, oid, side, kind, price, volume, emit,
            gone=None) -> bool:
        """Cross, then rest the remainder of a limit order. True when it
        rested. Fully filled makers' oids are appended to `gone`."""
        opp = 1 - side
        levels, prices = self.levels[opp], self.prices[opp]
        remaining = volume
        market = kind == MARKET
        lifo = self.lifo
        while remaining > 0 and prices:
            # best opposing price: lowest ask for a buy, highest bid for a sell
            best = prices[0] if side == BUY else prices[-1]
            if not market and (best > price if side == BUY else best < price):
                break
            level = levels[best]
            while remaining > 0 and level:
                node = level[-1] if lifo else level[0]
                if remaining >= node[2]:
                    traded = node[2]
                    remaining -= traded
                    if lifo:
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
            if not level:
                del levels[best]
                if side == BUY:
                    del prices[0]
                else:
                    prices.pop()
        if remaining > 0 and not market:
            own = self.levels[side]
            level = own.get(price)
            if level is None:
                level = own[price] = deque()
                bisect.insort(self.prices[side], price)
            level.append([oid, uid, remaining])
            self.count[side] += 1
            return True
        return False


def replay(cols: dict, priority: str = "fifo", Book=Book) -> tuple:
    """(events, books by symbol) of the stream columns (sym, uid, oid, side,
    kind, cancel, price, volume: sequences of equal length), processed in
    order by one `Book` per symbol."""
    books: dict = {}
    events: list = []
    emit = events.append
    for i, (sym, uid, oid, side, kind, cancel, price, volume) in enumerate(zip(
        cols["sym"], cols["uid"], cols["oid"], cols["side"], cols["kind"],
        cols["cancel"], cols["price"], cols["volume"],
    )):
        book = books.get(sym)
        if book is None:
            book = books[sym] = Book(priority)
        if cancel:
            book.cancel(i, sym, uid, oid, side, price, emit)
        else:
            book.add(i, sym, uid, oid, side, kind, price, volume, emit)
    return events, books


def run(cols: dict, priority: str = "fifo", Book=Book) -> list:
    """Events of the stream columns; see replay."""
    return replay(cols, priority, Book)[0]
