"""Buffer donation on the jitted engine entries (gomelint GL6xx applied):
the `_donating` twins are configured with the audited donate_argnums, they
produce results identical to the public (reuse-safe) entries, donated
inputs actually die on donation-supporting backends, and the engine's
host-sourced dispatch path survives escalation replays with donation on.
"""

import ast
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gome_tpu.engine import BatchEngine, BookConfig, batch_step, init_books
from gome_tpu.engine.batch import (
    batch_step_donating,
    dense_batch_step,
    dense_batch_step_donating,
    lane_scan,
    lane_scan_donating,
)
from gome_tpu.engine.book import DeviceOp
from gome_tpu.types import Order, Side

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = BookConfig(cap=8, max_fills=4)


def _grid(config, s=2, t=4, seed=7):
    rng = np.random.default_rng(seed)
    d = np.dtype(config.dtype)
    g = dict(
        action=np.ones((s, t), np.int32),  # all ADDs
        side=rng.integers(0, 2, (s, t)).astype(np.int32),
        kind=np.zeros((s, t), np.int32),
        price=(100 + rng.integers(0, 5, (s, t))).astype(d),
        volume=(1 + rng.integers(0, 3, (s, t))).astype(d),
        oid=np.arange(1, s * t + 1, dtype=d).reshape(s, t),
        uid=np.ones((s, t), d),
    )
    return DeviceOp(**g)


def _donation_effective() -> bool:
    """Does this backend actually consume donated buffers? (The test
    contract: assert semantics everywhere, assert deletion only where
    the platform implements donation — elsewhere it is a silent no-op.)"""
    import functools

    f = functools.partial(jax.jit, donate_argnums=(0,))(lambda x: x + 1)
    probe = jnp.ones((4,), jnp.int32)
    f(probe)
    return probe.is_deleted()


def _spec(wrapper: str):
    from gome_tpu.analysis.donation import wrapper_jit_spec

    path = os.path.join(ROOT, "gome_tpu", "engine", "batch.py")
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    return wrapper_jit_spec(tree, wrapper)


# --- configuration: the audited donate_argnums are actually declared ----


def test_donating_twins_are_configured():
    """Tier-1, platform-independent: the donation the GL6xx audit signed
    off on is present in the source (a regressed donate_argnums would
    resurrect the double-buffer silently)."""
    assert _spec("batch_step")[1] == ()
    assert _spec("batch_step_donating")[1] == (2,)
    assert _spec("dense_batch_step_donating")[1] == (3,)
    assert _spec("dense_kernel_step_donating")[1] == (3,)
    assert _spec("full_kernel_step_donating")[1] == (2,)
    assert _spec("lane_scan_donating")[1] == (1, 2)

    from gome_tpu.analysis.donation import wrapper_jit_spec

    with open(os.path.join(ROOT, "gome_tpu", "engine", "step.py"),
              encoding="utf-8") as fh:
        step_tree = ast.parse(fh.read())
    assert wrapper_jit_spec(step_tree, "step")[1] == (1,)


# --- semantics: donating twins == public entries ------------------------


def test_batch_step_donating_matches_public():
    books = init_books(CFG, 2)
    ops = _grid(CFG)
    ref_books, ref_outs = batch_step(CFG, books, ops)
    don_books, don_outs = batch_step_donating(CFG, init_books(CFG, 2), ops)
    jax.tree.map(np.testing.assert_array_equal, ref_books, don_books)
    jax.tree.map(np.testing.assert_array_equal, ref_outs, don_outs)


def test_dense_step_donating_matches_public():
    books = init_books(CFG, 4)
    ops = _grid(CFG, s=2, t=4)
    ids = np.array([1, 3], np.int32)
    ref = dense_batch_step(CFG, books, jnp.asarray(ids), ops)
    don = dense_batch_step_donating(
        CFG, init_books(CFG, 4), jnp.asarray(ids), ops
    )
    jax.tree.map(np.testing.assert_array_equal, ref, don)


def test_lane_scan_donating_matches_public():
    books = init_books(CFG, 1)
    one = jax.tree.map(lambda a: a[0], books)
    ops = jax.tree.map(lambda a: a[0], _grid(CFG, s=1))
    ref = lane_scan(CFG, one, ops)
    don = lane_scan_donating(
        CFG, jax.tree.map(lambda a: a[0], init_books(CFG, 1)), ops
    )
    jax.tree.map(np.testing.assert_array_equal, ref, don)


# --- donation is live: inputs die (skip where the backend no-ops) -------


def test_donated_ops_buffers_die():
    if not _donation_effective():
        pytest.skip("backend does not implement buffer donation (no-op)")
    books = init_books(CFG, 2)
    ops_dev = jax.device_put(_grid(CFG))  # device copy: donation visible
    batch_step_donating(CFG, books, ops_dev)
    assert ops_dev.action.is_deleted()
    # the UNdonated books survive (escalation/rollback liveness contract)
    assert not books.price.is_deleted()


def test_public_entry_never_donates():
    books = init_books(CFG, 2)
    ops_dev = jax.device_put(_grid(CFG))
    batch_step(CFG, books, ops_dev)
    assert not ops_dev.action.is_deleted()
    assert not books.price.is_deleted()


def test_single_op_step_donates_book():
    if not _donation_effective():
        pytest.skip("backend does not implement buffer donation (no-op)")
    from gome_tpu.engine.book import init_book
    from gome_tpu.engine.step import step

    book = init_book(CFG)
    op = jax.tree.map(lambda a: a[0, 0], jax.device_put(_grid(CFG)))
    new_book, _out = step(CFG, book, op)
    assert book.price.is_deleted()  # donated: book was threaded through
    assert not new_book.price.is_deleted()


# --- the engine's dispatch path with donation + escalation --------------


def _orders(n, symbol="BTC", side=Side.SALE):
    return [
        Order(action=1, symbol=symbol, oid=f"o{i}", uuid="u",
              price=1.0 + i / 100, volume=1.0, side=side)
        for i in range(n)
    ]


def test_engine_escalation_replays_with_donation():
    """cap-2 engine + 6 resting orders: phase-1 escalation replays the
    SAME numpy grid through the donating twin — host-sourced grids
    re-transfer per dispatch, so donation must never break the replay."""
    eng = BatchEngine(BookConfig(cap=2, max_fills=2), n_slots=1, max_t=8,
                      dense=False)
    events = eng.process(_orders(6))
    assert eng.stats.cap_escalations >= 1
    assert events == []  # same-side adds: everything rests, no fills
    counts = np.asarray(jax.device_get(eng.books.count))
    assert counts[0, int(Side.SALE)] == 6
    eng.verify_books()


def test_engine_process_columnar_roundtrip_with_donation():
    eng = BatchEngine(BookConfig(cap=8, max_fills=4), n_slots=2, max_t=8)
    eng.process_columnar(_orders(4))
    batch = eng.process_columnar(
        [Order(action=1, symbol="BTC", oid="t", uuid="u", price=2.0,
               volume=2.0, side=Side.BUY)]
    )
    assert len(batch) == 2  # crosses the two cheapest asks
    eng.verify_books()
