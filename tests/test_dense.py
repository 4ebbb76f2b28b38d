"""Dense gather/scatter grids (batch.dense_batch_step): compact packing of
live lanes with row->lane indirection, deep time axes for hot symbols, and
escalation/rebasing interplay — all pinned against the oracle and the
full-grid path."""

import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.engine import BatchEngine, BookConfig
from gome_tpu.oracle import OracleEngine
from gome_tpu.types import Action, Order, Side
from gome_tpu.utils.streams import multi_symbol_stream


def _run_columnar(eng, orders, chunk=64):
    got = []
    for i in range(0, len(orders), chunk):
        got.extend(eng.process_columnar(orders[i : i + chunk]).to_results())
    return got


def _oracle_events(orders):
    oracle = OracleEngine()
    out = []
    for o in orders:
        out.extend(oracle.process(o))
    return out


def test_dense_grid_selected_and_matches_oracle():
    """Few live symbols in a wide engine: the columnar path must pick the
    dense grid (device work tracks live lanes) and reproduce the oracle's
    event stream exactly."""
    orders = multi_symbol_stream(n=300, n_symbols=5, seed=9, cancel_prob=0.2)
    eng = BatchEngine(
        BookConfig(cap=64, max_fills=8), n_slots=512, max_t=16
    )
    got = _run_columnar(eng, orders)
    assert got == _oracle_events(orders)
    eng.verify_books()


def test_dense_vs_full_grid_identical():
    """dense=True and dense=False produce byte-identical event streams and
    book state on the same stream."""
    orders = multi_symbol_stream(n=400, n_symbols=7, seed=3, cancel_prob=0.15)
    results = []
    books = []
    for dense in (True, False):
        eng = BatchEngine(
            BookConfig(cap=64, max_fills=8), n_slots=256, max_t=8,
            dense=dense,
        )
        results.append(_run_columnar(eng, orders, chunk=96))
        books.append(eng.lane_books())
    assert results[0] == results[1]
    for a, b in zip(books[0], books[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_deep_time_axis_single_symbol():
    """One hot symbol with hundreds of ops per batch: the dense grid packs
    far deeper than max_t (one device call instead of dozens) with exact
    semantics — the config 1-2 latency path."""
    rng = np.random.default_rng(12)
    orders = []
    for i in range(600):
        orders.append(
            Order(
                uuid="u", oid=str(i), symbol="hot",
                side=Side(int(rng.integers(0, 2))),
                price=100 + int(rng.integers(-5, 6)),
                volume=int(rng.integers(1, 10)),
            )
        )
    eng = BatchEngine(BookConfig(cap=128, max_fills=16), n_slots=64, max_t=4)
    calls_before = eng.stats.device_calls
    got = _run_columnar(eng, orders, chunk=600)
    # 600 ops, one lane: full grids would need ceil(600/4)=150 device calls;
    # dense packs t_grid=min(1024, next_pow2(600))=1024 -> ONE call.
    assert eng.stats.device_calls - calls_before == 1
    assert got == _oracle_events(orders)
    eng.verify_books()


def test_dense_with_cap_escalation():
    """Book overflow inside a dense grid: cap escalation replays the dense
    grid from the snapshot; results stay exact."""
    orders = [
        Order(uuid="u", oid=str(i), symbol="s", side=Side.SALE,
              price=100 + i, volume=1)
        for i in range(40)  # 40 resting asks > cap 8
    ]
    orders.append(
        Order(uuid="u", oid="t", symbol="s", side=Side.BUY, price=200,
              volume=100)  # sweeps all 40 levels (> max_fills too)
    )
    eng = BatchEngine(BookConfig(cap=8, max_fills=4), n_slots=64, max_t=4)
    got = _run_columnar(eng, orders, chunk=len(orders))
    assert got == _oracle_events(orders)
    assert eng.stats.cap_escalations >= 1
    assert eng.stats.fill_record_escalations >= 1
    eng.verify_books()


def test_dense_int32_rebasing_btc_scale():
    """Dense grids + int32 rebasing at BTC-scale prices (1e13 ticks)."""
    BTC = 10_000_000_000_000
    rng = np.random.default_rng(7)
    orders = []
    for i in range(200):
        sym = f"sym{int(rng.integers(0, 3))}"
        is_del = i > 30 and rng.random() < 0.2
        orders.append(
            Order(
                uuid="u", oid=str(rng.integers(1, i) if is_del else i),
                symbol=sym, side=Side(int(rng.integers(0, 2))),
                price=BTC + int(rng.integers(-1000, 1000)),
                volume=int(rng.integers(1, 20)),
                action=Action.DEL if is_del else Action.ADD,
            )
        )
    eng = BatchEngine(
        BookConfig(cap=64, max_fills=8, dtype=jnp.int32),
        n_slots=128, max_t=8,
    )
    got = _run_columnar(eng, orders, chunk=70)
    assert got == _oracle_events(orders)
    eng.verify_books()


def test_small_mesh_falls_back_to_full_grid():
    """Dense grids DO run under a mesh (per-shard row blocks inside
    shard_map, parallel.mesh.sharded_dense_step) — but only when the
    per-shard row bucket is a win. Here n_slots=8 over a 4-way mesh makes
    r_s * d >= n_slots for any live set, so _grid_geometry must fall back
    to the full sharded grid; events stay oracle-exact either way."""
    from gome_tpu.parallel import make_mesh

    mesh = make_mesh(4)
    eng = BatchEngine(
        BookConfig(cap=16, max_fills=4), n_slots=8, max_t=8, mesh=mesh
    )
    orders = multi_symbol_stream(n=60, n_symbols=3, seed=2, cancel_prob=0.1)
    got = _run_columnar(eng, orders, chunk=60)
    assert got == _oracle_events(orders)


def test_grid_geometry_ratchets_are_grow_only():
    """Compiled grid shapes must not oscillate across pow2 buckets as the
    live-lane count / depth hovers at a boundary — one fresh XLA compile
    costs more than thousands of frames of matching (the service bench's
    mid-run-compile regression)."""
    import numpy as np

    from gome_tpu.engine import BatchEngine, BookConfig

    eng = BatchEngine(BookConfig(cap=16, max_fills=4), n_slots=128, max_t=8)
    shapes = []
    for live_n in (9, 17, 9, 33, 9, 17):
        use_dense, n_rows, _, _ = eng._grid_geometry(
            np.arange(live_n, dtype=np.int64)
        )
        assert use_dense
        shapes.append(n_rows)
    assert shapes == [16, 32, 32, 64, 64, 64]  # never shrinks
    # Ratchet capped below n_slots: growing past it falls back to full.
    use_dense, n_rows, _, _ = eng._grid_geometry(np.arange(127, dtype=np.int64))
    assert not use_dense and n_rows == eng.n_slots


# --- rows and depth are two decisions (full grids run deep too) -----------

def _hot_lane_orders(n, seed=12, symbol="hot", oid0=0):
    rng = np.random.default_rng(seed)
    return [
        Order(
            uuid="u", oid=str(oid0 + i), symbol=symbol,
            side=Side(int(rng.integers(0, 2))),
            price=100 + int(rng.integers(-5, 6)),
            volume=int(rng.integers(1, 10)),
        )
        for i in range(n)
    ]


def _run_frame(eng, orders):
    """One ORDER frame through the served path's two calls; returns the
    events and the (rows, depth, dense) of every grid it dispatched."""
    from gome_tpu.bus import colwire
    from gome_tpu.engine.frames import resolve_frame, submit_frame

    cols = colwire.decode_order_frame(colwire.encode_orders(orders))
    before = set(eng.combos())
    pend = submit_frame(eng, cols)
    depths = [shape[0] for _, shape in pend.items]
    new = set(eng.combos()) - before
    # Every grid of this engine is a full one: row == lane, no lane_ids.
    assert all(c[0] == eng.n_slots and c[3] is False for c in new), new
    return resolve_frame(eng, pend).to_results(), depths


def _full_classes(eng, n_rows=None):
    """The fixed depth classes a full grid of this engine may take."""
    n_rows = eng.n_slots if n_rows is None else n_rows
    return {
        eng._grid_depth(n_rows, need, 64, first, False)
        for need in (1, eng.max_t, eng.max_t + 1, 8 * eng.max_t + 1, 10**6)
        for first in (True, False)
    }


def test_full_grid_runs_deep_when_n_slots_equals_live_lanes():
    """An engine provisioned with exactly its live lanes (the row bucket
    reaches n_slots, so _grid_geometry gives the full grid) still runs a
    hot lane's 600 ops in at most 2 device calls: the depth of a grid does
    not hang on whether its rows are indirected. Events equal the oracle's
    and a max_t-deep, 150-call replay's."""
    orders = _hot_lane_orders(600)
    eng = BatchEngine(BookConfig(cap=128, max_fills=16), n_slots=8, max_t=4)
    got, depths = _run_frame(eng, orders)
    assert eng.stats.device_calls <= 2
    assert depths == [1024]
    assert got == _oracle_events(orders)
    eng.verify_books()

    shallow = BatchEngine(
        BookConfig(cap=128, max_fills=16), n_slots=8, max_t=4, dense=False
    )
    replay = []
    for i in range(0, len(orders), 4):  # a frame of max_t ops: one [8 x 4]
        replay += shallow.process(orders[i : i + 4])
    assert shallow.stats.device_calls == 150  # 600 / max_t
    assert got == replay


def test_full_grid_depth_is_held_by_the_row_budget():
    """A full grid wide enough for the record-tensor budget to bind stays
    at most t_mem deep — by the arithmetic the dense branch always
    applied, not by a flag: 10,240 lanes x K=16 hold it to 64 (and the
    quarter class, 16, drops out under max_t), 8 lanes get the ceiling."""
    wide = BatchEngine(
        BookConfig(cap=16, max_fills=16), n_slots=10240, max_t=32
    )
    assert _full_classes(wide) == {32, 64}
    assert wide._grid_depth(10240, 32, 64, True, False) == 32
    assert wide._grid_depth(10240, 1500, 64, True, False) == 64
    narrow = BatchEngine(BookConfig(cap=16, max_fills=16), n_slots=8,
                         max_t=32)
    assert _full_classes(narrow) == {32, 256, 1024}

    # End to end: 256 lanes x max_fills 256 leave t_mem = 256, so a
    # 300-op lane takes a full grid of 256 and a tail of the quarter
    # class, never one grid of 1,024. (Sides alternate at one price, so
    # the book stays a few orders deep and cap 16 never escalates.)
    orders = [
        Order(uuid="u", oid=str(i), symbol="hot", side=Side(i % 2),
              price=100, volume=1 + i % 3)
        for i in range(300)
    ]
    eng = BatchEngine(
        BookConfig(cap=16, max_fills=256), n_slots=256, max_t=4,
        dense=False,
    )
    got, depths = _run_frame(eng, orders)
    assert depths == [256, 64]
    assert got == _oracle_events(orders)


def test_full_grid_depth_has_no_floor():
    """A small frame after a deep one returns to the max_t class: a full
    grid's rows are fixed, so there is nothing for a grow-only floor to
    steady, and one would pad every later tick to the deepest frame the
    process ever saw."""
    eng = BatchEngine(BookConfig(cap=128, max_fills=16), n_slots=8, max_t=4)
    deep = _hot_lane_orders(600)
    small = _hot_lane_orders(3, seed=5, oid0=10_000)
    got_deep, d_deep = _run_frame(eng, deep)
    got_small, d_small = _run_frame(eng, small)
    assert d_deep == [1024] and d_small == [4]
    assert eng.geometry_floors()["t_floor"] == {}
    assert got_deep + got_small == _oracle_events(deep + small)


def test_full_grid_depths_stay_in_the_fixed_classes():
    """Over a mixed run (frames of 5 to 700 ops over 8 lanes) every full
    grid's depth is one of the fixed classes {max_t, 8*max_t, cap_t//4,
    cap_t}: the class depends on `need` alone, so a warm-up that has met
    the three shapes has met them all."""
    eng = BatchEngine(BookConfig(cap=128, max_fills=16), n_slots=8, max_t=4)
    stream = multi_symbol_stream(
        n=1700, n_symbols=8, seed=4, zipf_a=1.0, cancel_prob=0.2
    )
    seen, got, at = set(), [], 0
    for n in (5, 40, 700, 12, 300, 3, 640):
        evs, depths = _run_frame(eng, stream[at : at + n])
        got.extend(evs)
        seen.update(depths)
        at += n
    assert seen <= {4, 32, 256, 1024} == _full_classes(eng)
    assert len(seen) >= 3
    assert got == _oracle_events(stream[:at])
    eng.verify_books()


def _parent_dense_depth(eng, n_rows, need, cls, first):
    """The dense branch of _pack_class_train as it stood before depth
    became its own function, verbatim (floors in `eng._dense_t_floor`)."""
    from gome_tpu.engine.batch import _REC_ELEM_BUDGET, _next_pow2

    t_mem = max(
        eng.max_t,
        _next_pow2(
            _REC_ELEM_BUDGET // max(n_rows * eng.config.max_fills, 1) + 1
        )
        // 2,
    )
    cap_t = max(8, min(max(eng.dense_t_max, eng.max_t), t_mem))
    if first:
        t_floor = eng._dense_t_floor.get(cls, 8)
        t_grid = min(max(_next_pow2(need), t_floor), cap_t)
        eng._dense_t_floor[cls] = max(t_floor, t_grid)
        return t_grid
    cands = sorted({
        min(max(8, eng.max_t), cap_t),
        min(max(8, 8 * eng.max_t), cap_t),
        min(max(8, cap_t // 4), cap_t),
        cap_t,
    })
    return next((c for c in cands if c >= min(need, cap_t)), cap_t)


@pytest.mark.parametrize("max_t,dense_t_max,max_fills", [
    (32, 1024, 16), (4, 1024, 16), (8, 64, 4), (32, 8192, 16), (64, 16, 8),
])
def test_dense_depth_rule_is_what_it_was(max_t, dense_t_max, max_fills):
    """The dense branch chooses the depths it chose before, ratchets
    included, on any (rows, need, class, first) sequence."""
    def mk():
        return BatchEngine(
            BookConfig(cap=256, max_fills=max_fills), n_slots=16,
            max_t=max_t, dense_t_max=dense_t_max,
        )
    new, old = mk(), mk()
    rng = np.random.default_rng(max_t * 7 + max_fills)
    for _ in range(400):
        n_rows = 8 << int(rng.integers(0, 12))
        need = int(rng.integers(1, 5000))
        cls = (64, 256)[int(rng.integers(0, 2))]
        first = bool(rng.integers(0, 2))
        assert new._grid_depth(n_rows, need, cls, first, True) == \
            _parent_dense_depth(old, n_rows, need, cls, first)
        assert new._dense_t_floor == old._dense_t_floor


def test_dense_combos_of_a_wide_zipf_flow_are_what_they_were():
    """A spot10k-like flow (4,096 lanes, Zipf(1.3), 4,096-order frames:
    a wide class-64 grid plus a hot-lane train with a tail) records the
    shape combos the tree before this change recorded on the same input:
    none of its grids is a full one, and the dense branch is untouched."""
    from gome_tpu.bus import colwire
    from gome_tpu.engine.frames import resolve_frame, submit_frame

    orders = multi_symbol_stream(
        n=3 * 4096, n_symbols=4096, seed=26, zipf_a=1.3, cancel_prob=0.3
    )
    eng = BatchEngine(
        BookConfig(cap=256, max_fills=16, dtype=jnp.int32),
        n_slots=4096, max_t=32,
    )
    for i in range(0, len(orders), 4096):
        cols = colwire.decode_order_frame(
            colwire.encode_orders(orders[i : i + 4096])
        )
        resolve_frame(eng, submit_frame(eng, cols))
    assert eng.stats.device_calls == 9
    assert eng.combos() == [  # recorded at commit cf9c19d, same script
        (8, 256, 256, True, 256, 16, 4096, 1024, 8),
        (8, 256, 256, True, 256, 16, 4096, 2048, 8),
        (8, 1024, 256, True, 4096, 16, 4096, 1024, 8),
        (8, 1024, 256, True, 4096, 16, 4096, 2048, 8),
        (16, 1024, 256, True, 4096, 16, 4096, 2048, 8),
        (1024, 128, 64, True, 4096, 16, 4096, 1024, 8),
        (1024, 128, 64, True, 4096, 16, 4096, 2048, 8),
    ]
    assert eng.geometry_floors()["t_floor"] == {64: 128, 256: 1024}
    assert eng.geometry_floors()["rows_floor"] == {64: 1024, 256: 16}
