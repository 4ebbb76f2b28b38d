"""Where a symbol lives when the lane axis is split over shards.

One rule for every tier that has to agree on it (the mesh engine's book
stack, parallel.router's in-process shards): symbols are dealt round-robin
over the shards in the order they first arrive. The k-th symbol to arrive
(k = its interner id - 1, what a one-chip engine calls its lane) goes to
shard k mod D, and is the (k // D)-th lane of that shard's contiguous block
of the stack.

Why arrival order and not a hash of the name: a venue's first listings are
its majors, so the head of a Zipf flow arrives first and a deal spreads it
evenly, the same way in every run (24 hot symbols land 6 to a chip on 4
chips); a stateless hash spreads the head by chance (3 to 10 of 24 on a
chip) and the padded per-shard row block flips between buckets from run to
run. Block placement in arrival order (lane // (S / D), what the mesh engine
did before) puts the whole head on shard 0. The deal needs no table: it
replays from a snapshot's interner, which keeps the arrival order.

`arrival` and `lane` are ints or integer numpy arrays; with one shard both
maps are the identity.
"""

from __future__ import annotations


def shard_of(arrival, n_shards: int):
    """Shard of the k-th symbol to arrive."""
    return arrival % n_shards


def lane_of(arrival, n_slots: int, n_shards: int):
    """Row of the [n_slots] book stack holding the k-th symbol to arrive:
    shard k mod D owns rows [d * S/D, (d + 1) * S/D)."""
    return shard_of(arrival, n_shards) * (n_slots // n_shards) + (
        arrival // n_shards
    )


def arrival_of(lane, n_slots: int, n_shards: int):
    """Inverse of lane_of: which symbol (by arrival) a row holds."""
    local = n_slots // n_shards
    return (lane % local) * n_shards + lane // local
