"""Shard-routing tests: stable hashing, and the one placement rule the
router shares with the mesh engine (whose parity with the oracle and with a
one-chip engine is tests/test_sharding.py's)."""

import numpy as np
import pytest

from gome_tpu.engine import BookConfig
from gome_tpu.parallel import ShardRouter, fnv1a


def test_routing_is_stable_and_total():
    r = ShardRouter(8)
    for sym in ("eth2usdt", "btc2usdt", "sym123", ""):
        assert 0 <= r.route(sym) < 8
        assert r.route(sym) == r.route(sym)
    # fnv1a is the cross-process-stable hash (Python's is salted)
    assert fnv1a("eth2usdt") == fnv1a("eth2usdt")
    assert fnv1a("a") != fnv1a("b")
    with pytest.raises(ValueError):
        ShardRouter(0)


def test_router_deals_symbols_as_the_mesh_engine_places_them():
    """One placement rule (engine.placement): the k-th symbol a router meets
    goes to shard k mod D, which is the shard whose block of the book stack a
    D-device mesh engine gives the k-th symbol it meets."""
    from gome_tpu.engine import BatchEngine, placement
    from gome_tpu.parallel import make_mesh

    names = [f"sym{i}" for i in (7, 3, 11, 0, 5, 9, 2, 8, 1)]
    router = ShardRouter(4)
    assert [router.route(s) for s in names] == [k % 4 for k in range(9)]
    assert [router.route(s) for s in reversed(names)] == [
        k % 4 for k in reversed(range(9))]  # a symbol stays where it was put
    eng = BatchEngine(BookConfig(cap=16, max_fills=4), n_slots=16,
                      mesh=make_mesh(4))
    local = eng.n_slots // 4
    assert [eng._lane(s) // local for s in names] == [
        router.route(s) for s in names]
    k = np.arange(16)
    lanes = placement.lane_of(k, 16, 4)
    assert sorted(lanes.tolist()) == list(range(16))
    assert (placement.arrival_of(lanes, 16, 4) == k).all()
    assert (placement.lane_of(k, 16, 1) == k).all()
