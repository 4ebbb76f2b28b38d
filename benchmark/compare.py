"""The comparison that decides `correct`: the events a subscriber received
against the plain reference's, event for event, over warm-up and window.

Every number compared is exact, so every limit is 0. The reference's events
come with the stream (the generator runs benchmark/reference.py in its loop);
the control replays the stream with the stated guarantee broken.
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
    part = {k: np.asarray(v[:n_orders]).tolist() for k, v in cols.items()}
    events = np.array(run(part, priority), np.int64).reshape(
        -1, len(reference.EVENT_FIELDS))
    return compare_events(events[:, _EXPECTED_COLUMNS], got)
