"""The comparison that decides `correct`: the events a subscriber received
against the plain reference's, event for event, over warm-up and window.

Every number compared is exact, so every limit is 0. The reference's events
come with the stream (the generator keeps the venue's Book in its loop); the
control replays the stream with the stated guarantee broken. A configuration
that is restarted on its directory (`restart`) is held to the same comparison
across both processes, and to the reference's books (restart_numbers).
"""

from __future__ import annotations

import numpy as np

from . import reference

#: Columns of wire.decode_events in terms of reference.EVENT_FIELDS: the
#: symbol is checked on both snapshots.
_EXPECTED_COLUMNS = [1, 1] + list(range(2, len(reference.EVENT_FIELDS)))


def expected_rows(events: np.ndarray, n_orders: int) -> np.ndarray:
    """The reference's events of the first n_orders orders, as the wire
    carries them."""
    upto = np.searchsorted(events[:, 0], n_orders, side="left")
    return events[:upto][:, _EXPECTED_COLUMNS]


def compare_events(expected: np.ndarray, got: np.ndarray) -> dict:
    n = min(len(expected), len(got))
    differ = np.flatnonzero((expected[:n] != got[:n]).any(axis=1))
    return {
        "events.mismatched": int(len(differ)),
        "events.missing": int(max(len(expected) - len(got), 0)),
        "events.extra": int(max(len(got) - len(expected), 0)),
        "_first_difference": int(differ[0]) if len(differ) else None,
    }


def control(cols: dict, n_orders: int, got: np.ndarray, priority: str,
            run=reference.run) -> dict:
    """The reference put in the program's place with the guarantee broken
    (`priority`), compared the same way: it has to come out not correct."""
    events = np.array(run(_part(cols, n_orders), priority), np.int64).reshape(
        -1, len(reference.EVENT_FIELDS))
    return compare_events(events[:, _EXPECTED_COLUMNS], got)


def _part(cols: dict, n_orders: int) -> dict:
    return {k: np.asarray(v[:n_orders]).tolist() for k, v in cols.items()}


def resting_counts(cols: dict, n_orders: int, Book=reference.Book) -> dict:
    """{symbol: [resting buys, resting sells]} after the stream's first
    n_orders orders, by the reference's books."""
    _events, books = reference.replay(_part(cols, n_orders), Book=Book)
    return {sym: list(book.count) for sym, book in books.items()}


def restart_numbers(expected: np.ndarray, first: np.ndarray,
                    second: np.ndarray, second_from: int | None,
                    counts: dict | None, served_counts: dict | None,
                    invariant_failures: int, recovered: bool) -> dict:
    """A serving process killed and booted again on its directory, against
    the reference. `expected`: the events of every acknowledged order, as the
    wire carries them; `first`: what the subscriber held when the first
    process was killed; `second`: what the second process delivered, from seq
    `second_from` on (the match feed's own count: the wire carries none). A
    subscriber that asks for what follows the last seq it holds reads each
    seq once: the first process's events, then the second's from there. What
    the second process delivered again is compared too. `counts` and
    `served_counts`: resting orders per symbol and side, the reference's and
    the second process's, by symbol number."""
    numbers = {
        "restart.events_mismatched": 0, "restart.events_missing": 0,
        "restart.events_extra": 0, "restart.books_mismatched": 0,
        "restart.not_recovered": int(not recovered),
    }
    if not recovered:
        numbers["restart.events_missing"] = max(len(expected) - len(first), 0)
        return numbers
    if second_from is None:  # it delivered nothing: none was owed, or all lost
        second_from = len(first)

    def differ(got, at):
        owed = expected[at:at + len(got)]
        return int((owed != got[:len(owed)]).any(axis=1).sum())

    end = max(len(first), second_from + len(second))
    numbers["restart.events_mismatched"] = (differ(first, 0)
                                            + differ(second, second_from))
    # seqs that neither process delivered: between the two, and at the end
    numbers["restart.events_missing"] = (max(second_from - len(first), 0)
                                         + max(len(expected) - end, 0))
    numbers["restart.events_extra"] = max(end - len(expected), 0)
    served = {int(name[1:]): pair for name, pair in served_counts.items()}
    numbers["restart.books_mismatched"] = invariant_failures + sum(
        1 for sym in set(counts) | set(served) for side in (0, 1)
        if counts.get(sym, [0, 0])[side] != served.get(sym, [0, 0])[side])
    return numbers
