"""int32 operating-envelope guarantees: saturating depth prefix sums keep
fills exact when crossed depth exceeds 2^31, and the per-order lot ceiling
is enforced at ingestion (engine/step.py SAT32_MAX / LOT_MAX32)."""

import numpy as np
import pytest

import jax.numpy as jnp

from gome_tpu.engine import BatchEngine, BookConfig, batch_step, init_books
from gome_tpu.engine.book import DeviceOp
from gome_tpu.engine.step import LOT_MAX32
from gome_tpu.types import Order, Side


def _grid(config, rows):
    """rows: list of (action, side, price, volume, oid) on one lane."""
    t = len(rows)
    d = np.dtype(config.dtype)
    g = dict(
        action=np.zeros((1, t), np.int32),
        side=np.zeros((1, t), np.int32),
        kind=np.zeros((1, t), np.int32),
        price=np.zeros((1, t), d), volume=np.zeros((1, t), d),
        oid=np.zeros((1, t), d), uid=np.ones((1, t), d),
    )
    for i, (a, s, p, v, o) in enumerate(rows):
        g["action"][0, i] = a
        g["side"][0, i] = s
        g["price"][0, i] = p
        g["volume"][0, i] = v
        g["oid"][0, i] = o
    return DeviceOp(**g)


def test_deep_book_prefix_sum_saturates_exactly():
    """Rest 8 asks of LOT_MAX32 lots each (total ~8.6e9, far past 2^31),
    then a taker sweeps part of it: fills must match the int64 book."""
    rows = [(1, 1, 100 + i, LOT_MAX32, i + 1) for i in range(8)]
    rows.append((1, 0, 200, LOT_MAX32, 99))  # BUY taker: crosses everything
    results = {}
    for dt in (jnp.int32, jnp.int64):
        config = BookConfig(cap=16, max_fills=16, dtype=dt)
        books = init_books(config, 1)
        books, outs = batch_step(config, books, _grid(config, rows))
        results[dt] = (
            np.asarray(outs.n_fills)[0, -1],
            np.asarray(outs.fill_qty)[0, -1],
            np.asarray(outs.taker_remaining)[0, -1],
            np.asarray(books.count)[0],
        )
    n32, q32, r32, c32 = results[jnp.int32]
    n64, q64, r64, c64 = results[jnp.int64]
    assert n32 == n64 == 1  # taker volume == one maker's lots
    np.testing.assert_array_equal(q32, q64)
    assert r32 == r64 == 0
    np.testing.assert_array_equal(c32, c64)


def test_deep_book_partial_sweep_matches_int64():
    """Taker volume lands mid-way through a >2^31 crossed prefix."""
    maker = LOT_MAX32 // 4  # 9 makers total ~2.4e9 lots > 2^31
    rows = [(1, 1, 100 + i, maker, i + 1) for i in range(9)]
    taker_vol = maker * 3 + 12345  # crosses 3 makers + part of the 4th
    rows.append((1, 0, 200, taker_vol, 99))
    results = {}
    for dt in (jnp.int32, jnp.int64):
        config = BookConfig(cap=16, max_fills=16, dtype=dt)
        books = init_books(config, 1)
        books, outs = batch_step(config, books, _grid(config, rows))
        results[dt] = (
            np.asarray(outs.n_fills)[0, -1],
            np.asarray(outs.fill_qty)[0, -1].astype(np.int64),
            np.asarray(outs.maker_remaining)[0, -1].astype(np.int64),
        )
    assert results[jnp.int32][0] == results[jnp.int64][0] == 4
    np.testing.assert_array_equal(results[jnp.int32][1], results[jnp.int64][1])
    np.testing.assert_array_equal(results[jnp.int32][2], results[jnp.int64][2])


def test_lot_ceiling_enforced_at_ingestion():
    eng = BatchEngine(BookConfig(cap=16, max_fills=4, dtype=jnp.int32), n_slots=2)
    big = Order(uuid="u", oid="o", symbol="s", side=Side.BUY,
                price=100, volume=LOT_MAX32 + 1)
    with pytest.raises(ValueError, match="lot ceiling"):
        eng.process([big])
    with pytest.raises(ValueError, match="lot ceiling"):
        eng.process_columnar([big])
    ok = Order(uuid="u", oid="o2", symbol="s", side=Side.BUY,
               price=100, volume=LOT_MAX32)
    assert eng.process([ok]) == []  # rests quietly at the ceiling
