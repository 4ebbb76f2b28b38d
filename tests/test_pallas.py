"""Pallas match-kernel parity tests (interpret mode on CPU; the compiled
kernel runs the identical traced code on TPU — gome_tpu.ops.pallas_match).
"""

import numpy as np
import pytest

from bench import build_grids
from gome_tpu.engine import BatchEngine, BookConfig, batch_step, init_books
from gome_tpu.engine.book import DeviceOp
from gome_tpu.oracle import OracleEngine
from gome_tpu.ops import pallas_batch_step
from gome_tpu.utils.streams import mixed_stream


def assert_trees_equal(t1, t2):
    for name in t1._fields:
        np.testing.assert_array_equal(
            getattr(t1, name), getattr(t2, name), err_msg=name
        )


def test_grid_parity_vs_scan():
    """Random crossing flow: pallas kernel == scan baseline on every output
    leaf and every book leaf, across chained grids."""
    config = BookConfig(cap=32, max_fills=8)
    S, T = 16, 8
    b1 = b2 = init_books(config, S)
    for g in [DeviceOp(**d) for d in build_grids(S, T, 3, seed=5)]:
        b1, o1 = batch_step(config, b1, g)
        b2, o2 = pallas_batch_step(config, b2, g, block_s=8, interpret=True)
        assert_trees_equal(o1, o2)
    assert_trees_equal(b1, b2)


def test_grid_parity_with_cancels_markets_nops():
    """Grid containing NOPs, DELs and MARKET orders (all action paths)."""
    config = BookConfig(cap=16, max_fills=4)
    S, T = 8, 6
    rng = np.random.default_rng(0)
    d = np.int64
    grid = DeviceOp(
        action=rng.integers(0, 3, size=(S, T), dtype=np.int32),
        side=rng.integers(0, 2, size=(S, T), dtype=np.int32),
        kind=(rng.random((S, T)) < 0.2).astype(np.int32),
        price=rng.integers(90, 111, size=(S, T)).astype(d),
        volume=rng.integers(1, 10, size=(S, T)).astype(d),
        oid=np.arange(S * T, dtype=d).reshape(S, T) % 7 + 1,
        uid=np.ones((S, T), d),
    )
    books = init_books(config, S)
    b1, o1 = batch_step(config, books, grid)
    b2, o2 = pallas_batch_step(config, books, grid, block_s=8, interpret=True)
    assert_trees_equal(o1, o2)
    assert_trees_equal(b1, b2)


def test_block_size_validation():
    config = BookConfig(cap=16, max_fills=4)
    books = init_books(config, 6)
    grid = DeviceOp(**build_grids(6, 2, 1)[0])
    with pytest.raises(ValueError, match="multiple"):
        pallas_batch_step(config, books, grid, block_s=4, interpret=True)


def test_batch_engine_pallas_kernel_oracle_parity():
    """Full BatchEngine on the pallas kernel matches the oracle on a mixed
    stream (admission, escalations, decode — everything downstream of the
    kernel is shared)."""
    orders = mixed_stream(n=150, seed=9, cancel_prob=0.2, market_prob=0.1)
    oracle = OracleEngine()
    expected = []
    for o in orders:
        expected.extend(oracle.process(o))

    engine = BatchEngine(
        BookConfig(cap=32, max_fills=8), n_slots=8, max_t=16,
        kernel="pallas", pallas_interpret=True,
    )
    got = []
    for i in range(0, len(orders), 40):
        got.extend(engine.process(orders[i : i + 40]))
    assert got == expected


def test_int32_dtype_parity():
    import jax.numpy as jnp

    config = BookConfig(cap=16, max_fills=8, dtype=jnp.int32)
    S, T = 8, 4
    grids = build_grids(S, T, 2, seed=3, dtype=np.int32)
    # keep magnitudes in int32 range: small lots
    for d in grids:
        d["volume"] = (d["volume"] // 1_000_000).astype(np.int32)
        d["price"] = (d["price"] // 1000).astype(np.int32)
    b1 = b2 = init_books(config, S)
    for g in [DeviceOp(**d) for d in grids]:
        b1, o1 = batch_step(config, b1, g)
        b2, o2 = pallas_batch_step(config, b2, g, block_s=8, interpret=True)
        assert_trees_equal(o1, o2)
    assert_trees_equal(b1, b2)
    assert b1.price.dtype == jnp.int32
