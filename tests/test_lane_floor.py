"""The lane floor (ISSUE 43): a venue states its lanes (n_slots) and the
engine provisions the device's lane axis to the compiled kernel's row floor
(ops.blockable_rows), so every n_slots has a full grid the kernel can block.
n_slots stays what counts symbols; lane_rows is what the book stack and a
full grid carry. Everything here runs the kernel through the Pallas
interpreter on the CPU; tests/test_tpu_compile.py compiles the same shapes
for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.bus import colwire
from gome_tpu.engine import BatchEngine, BookConfig
from gome_tpu.engine import batch as B
from gome_tpu.engine import frames
from gome_tpu.ops.pallas_match import blockable_rows, plan_block_s
from gome_tpu.oracle import OracleEngine
from gome_tpu.parallel import make_mesh
from gome_tpu.types import Order, Side
from gome_tpu.utils.streams import multi_symbol_stream

CFG = BookConfig(cap=64, max_fills=8, dtype=jnp.int32)


def engine(n_slots, **kw):
    kw.setdefault("max_t", 8)
    return BatchEngine(CFG, n_slots=n_slots, kernel="pallas",
                       pallas_interpret=True, **kw)


def frame_cols(orders, chunk):
    return [
        colwire.decode_order_frame(colwire.encode_orders(orders[i:i + chunk]))
        for i in range(0, len(orders), chunk)
    ]


def serve(eng, orders, chunk=64):
    """Frames through submit_frame / resolve_frame, one in flight."""
    out = []
    for cols in frame_cols(orders, chunk):
        out.extend(frames.resolve_frame(
            eng, frames.submit_frame(eng, cols)).to_results())
    return out


def oracle_events(orders):
    oracle = OracleEngine()
    return [ev for o in orders for ev in oracle.process(o)]


# --- the rule --------------------------------------------------------------


@pytest.mark.parametrize("s", list(range(1, 301)) + [
    383, 384, 385, 1000, 1024, 1025, 10240, 10241, 65536])
def test_the_floor_is_the_smallest_row_count_the_kernel_blocks(s):
    """blockable_rows(s) is blockable and nothing from s up to it is: the
    one rule beside plan_block_s, which refuses every other count by it."""
    rows = blockable_rows(s)
    assert rows >= s
    assert plan_block_s(rows, 64)[1] != "unblockable_rows"
    for r in range(s, rows):
        assert plan_block_s(r, 64) == (None, "unblockable_rows"), r
    assert blockable_rows(rows) == rows  # a floor of a floor is itself


@pytest.mark.parametrize("n_slots, rows", [
    (1, 8), (3, 8), (8, 8), (9, 16), (100, 104), (256, 256), (300, 384),
    (1000, 1024), (10240, 10240)])
def test_an_engine_that_runs_the_kernel_provisions_the_floor(n_slots, rows):
    eng = BatchEngine(BookConfig(cap=4, max_fills=4, dtype=jnp.int32),
                      n_slots=n_slots, kernel="pallas", pallas_interpret=True)
    assert (eng.n_slots, eng.lane_rows) == (n_slots, rows)
    assert eng.books.count.shape == (rows, 2)
    # the per-lane host vectors stay the venue's lanes
    assert eng.lane_span == n_slots
    assert len(eng.count_ub()) == len(eng.price_base) == n_slots
    # and a full grid is planned on the kernel, at the floor
    use_dense, n_rows, ids, _row_of = eng._grid_geometry(np.arange(n_slots))
    assert (use_dense, n_rows, ids) == (False, rows, None)
    assert plan_block_s(n_rows, 4)[0] is not None


def test_an_engine_that_never_runs_the_kernel_has_no_floor():
    eng = BatchEngine(CFG, n_slots=3)
    assert (eng.lane_rows, eng.lane_span) == (3, 3)
    assert eng.books.count.shape == (3, 2)


@pytest.mark.parametrize("n_slots, per_shard", [(4, 8), (8, 8), (36, 16)])
def test_under_a_mesh_the_floor_is_each_shards(n_slots, per_shard):
    eng = engine(n_slots, mesh=make_mesh(4))
    assert eng.lane_rows == 4 * per_shard
    assert eng.books.count.shape == (4 * per_shard, 2)
    # a lane is its row of the stack: the k-th symbol's is in shard k mod 4
    lanes = eng._lane_of(np.arange(n_slots))
    np.testing.assert_array_equal(lanes // per_shard, np.arange(n_slots) % 4)
    np.testing.assert_array_equal(eng._symbol_ids(lanes), np.arange(n_slots))


# --- the engine against the oracle, event for event -------------------------


@pytest.mark.parametrize("n_slots, n_symbols", [
    (1, 1), (3, 3), (9, 9), (300, 40)])
def test_every_grid_of_a_venue_of_any_lane_count_runs_the_kernel(
        n_slots, n_symbols):
    orders = multi_symbol_stream(n=400, n_symbols=n_symbols, seed=5,
                                 cancel_prob=0.2)
    eng = engine(n_slots)
    assert serve(eng, orders) == oracle_events(orders)
    eng.verify_books()
    st = eng.stats
    assert st.scan_giveways == {} and st.frame_fallbacks == 0
    assert set(st.grids_by_kernel) <= {"interpret_full", "interpret_dense"}
    assert eng.n_slots == n_slots and st.lane_growths == 0
    # the rows past the venue's lanes took no op and hold nothing
    books = jax.device_get(eng.books)
    assert int(np.asarray(books.count)[n_slots:].sum()) == 0
    assert int(np.asarray(books.next_seq)[n_slots:].sum()) == 0
    # a full grid's rows are the floor, in the recorded combos too
    full = {c[0] for c in eng.combos() if not c[3]}
    assert full <= {eng.lane_rows}
    # views keep the venue's lanes
    assert np.asarray(eng.lane_books().count).shape == (n_slots, 2)


def test_one_lane_thousands_deep_is_full_grids_at_the_depth_ceiling():
    """hotpair1's shape in small: one lane, a frame deeper than
    dense_t_max, so the frame is full [8 x t] grids one after another."""
    orders = multi_symbol_stream(n=600, n_symbols=1, seed=9, cancel_prob=0.3)
    eng = engine(1, dense_t_max=64)
    assert serve(eng, orders, chunk=200) == oracle_events(orders)
    assert {c[:2] for c in eng.combos()} == {(8, 64), (8, 8)}
    assert eng.stats.grids_by_kernel == {"interpret_full": 12}


def test_the_exact_path_runs_at_the_floor_too():
    orders = multi_symbol_stream(n=200, n_symbols=3, seed=2, cancel_prob=0.2)
    eng = engine(3)
    got = []
    for cols in frame_cols(orders, 50):
        got.extend(frames.process_frame(eng, cols).to_results())
    assert got == oracle_events(orders)
    assert eng.stats.grids_by_kernel == {"interpret_full": 4}
    eng.verify_books()


def test_a_cap_escalation_at_one_lane_keeps_the_floor():
    """One lane resting past its storage cap: the stack grows its slot
    axis, the rows stay the floor and the lanes stay one."""
    orders = [Order(uuid="u", oid=f"r{i}", symbol="s", side=Side.SALE,
                    price=1000 + i, volume=1) for i in range(100)]
    eng = engine(1)
    for cols in frame_cols(orders, 100):
        assert frames.apply_frame_fast(eng, cols).to_results() == []
    assert eng.config.cap > 64 and eng.stats.cap_escalations >= 1
    assert eng.books.price.shape == (8, 2, eng.config.cap)
    assert int(eng.lane_books().count[0, 1]) == 100
    eng.verify_books()


# --- growth ----------------------------------------------------------------


def test_a_venue_that_grows_inside_its_floor_moves_nothing(monkeypatch):
    """1 -> 2 lanes: both fit the 8 rows, so the stack is not laid out
    anew; only the host's per-lane vectors grow."""
    def quotes(symbol):
        return [Order(uuid="u", oid=f"{symbol}{i}", symbol=symbol,
                      side=Side(i % 2), price=1000 + (i % 2) * 10 - i % 5,
                      volume=2) for i in range(40)]

    eng = engine(1)
    orders = quotes("a") + quotes("b") + quotes("a")
    placed = []
    monkeypatch.setattr(
        eng, "_place", lambda books: placed.append(books) or books)
    assert serve(eng, orders, chunk=40) == oracle_events(orders)
    assert (eng.n_slots, eng.lane_rows, eng.stats.lane_growths) == (2, 8, 1)
    assert placed == [] and eng.books.count.shape == (8, 2)
    assert len(eng.price_base) == len(eng.count_ub()) == 2
    eng.verify_books()


@pytest.mark.parametrize("n_slots, n_symbols, lanes, rows", [
    (1, 2, 2, 8), (1, 9, 16, 16), (8, 9, 16, 16), (3, 40, 48, 48)])
def test_growth_doubles_the_venues_lanes_and_provisions_anew(
        n_slots, n_symbols, lanes, rows):
    orders = multi_symbol_stream(n=400, n_symbols=n_symbols, seed=11,
                                 cancel_prob=0.2)
    eng = engine(n_slots)
    assert serve(eng, orders, chunk=48) == oracle_events(orders)
    assert (eng.n_slots, eng.lane_rows) == (lanes, rows)
    assert eng.stats.lane_growths >= 1 and eng.stats.scan_giveways == {}
    assert len(eng.price_base) == len(eng.count_ub()) == lanes
    eng.verify_books()


# --- snapshots hold the venue's lanes ---------------------------------------


def _same_books(a, b, n):
    for x, y in zip(jax.tree.leaves(a.lane_books()),
                    jax.tree.leaves(b.lane_books())):
        np.testing.assert_array_equal(np.asarray(x)[:n], np.asarray(y)[:n])


def test_a_state_of_one_lane_restores_into_an_engine_of_eight_and_back():
    orders = multi_symbol_stream(n=300, n_symbols=1, seed=3, cancel_prob=0.2)
    one = engine(1)
    head = serve(one, orders[:200])
    state = one.export_state()
    assert state["n_slots"] == 1
    assert np.asarray(state["books"]["count"]).shape == (1, 2)
    assert len(state["price_base"]) == 1
    eight = engine(8)
    eight.import_state(state)
    assert (eight.n_slots, eight.lane_rows) == (1, 8)
    # ... and into an engine with no floor, and from it back
    plain = BatchEngine(CFG, n_slots=8, max_t=8)
    plain.import_state(eight.export_state())
    assert (plain.n_slots, plain.lane_rows) == (1, 1)
    back = engine(1)
    back.import_state(plain.export_state())
    tails = [serve(e, orders[200:]) for e in (one, eight, plain, back)]
    assert head + tails[0] == oracle_events(orders)
    assert tails[0] == tails[1] == tails[2] == tails[3]
    for e in (eight, plain, back):
        e.verify_books()
        _same_books(one, e, 1)
    # eight lanes' state into an engine of one: it becomes eight
    wide = engine(8)
    wide_orders = multi_symbol_stream(n=200, n_symbols=8, seed=6)
    serve(wide, wide_orders)
    narrow = engine(1)
    narrow.import_state(wide.export_state())
    assert (narrow.n_slots, narrow.lane_rows) == (8, 8)
    _same_books(wide, narrow, 8)


def test_a_pre_rebasing_state_restores_at_the_floor():
    eng = engine(3)
    serve(eng, [Order(uuid="u", oid="a", symbol="s", side=Side.SALE,
                      price=1000, volume=5)])
    state = eng.export_state()
    for k in ("price_base", "base_set", "env_lo", "env_hi"):
        del state[k]
    state["books"]["price"] = (
        np.asarray(state["books"]["price"]).astype(np.int64)
        + eng.price_base[:, None, None]).astype(np.int32)
    old = engine(3)
    old.import_state(state)
    assert old._base_set.tolist() == [True, False, False]
    assert (old._env_lo[0], old._env_hi[0]) == (1000, 1000)
    got = serve(old, [Order(uuid="v", oid="b", symbol="s", side=Side.BUY,
                            price=1000, volume=5)])
    assert [ev.match_volume for ev in got] == [5]


@pytest.mark.parametrize("mesh_devices", [0, 4])
def test_a_cut_of_a_padded_stack_holds_the_lanes_and_restores(
        tmp_path, mesh_devices):
    """The durable path at a lane count with a floor: the snapshot the
    writer put on disk has n_slots lanes, and a boot on it replays to the
    uninterrupted run."""
    from test_durable_cut import (
        BOOK_LEAVES, CHUNK, assert_same_run, feed, make_svc, reference_run,
        stream)
    from gome_tpu.persist import SnapshotStore

    n_slots = 4 if mesh_devices else 3
    kw = dict(n_slots=n_slots, kernel="pallas", dtype="int32",
              mesh_devices=mesh_devices)

    def interpreted(svc):
        svc.engine.batch._pallas_interpret = True
        return svc

    orders = stream(8, n_symbols=n_slots)
    svc = interpreted(make_svc(tmp_path, every_n=6, depth=2, **kw))
    assert svc.engine.batch.lane_rows == (32 if mesh_devices else 8)
    svc.persist.restore_latest()
    feed(svc, orders)
    svc.consumer.drain()
    assert svc.persist.wait(30) and svc.persist.snapshots_taken == 1
    manifest, arrays = SnapshotStore(str(tmp_path / "snap")).load_latest()
    assert manifest["n_slots"] == n_slots
    stopped = reference_run(orders[:6 * CHUNK], n_slots=n_slots,
                            dtype="int32")
    state = stopped.engine.batch.export_state()
    for leaf in BOOK_LEAVES:
        np.testing.assert_array_equal(arrays[leaf], state["books"][leaf])
    booted = interpreted(make_svc(tmp_path, every_n=6, depth=0, **dict(
        kw, mesh_devices=0)))
    assert booted.persist.restore_latest()
    booted.consumer.drain()
    assert_same_run(booted, reference_run(orders, n_slots=n_slots,
                                          dtype="int32"), orders)
    assert booted.engine.batch.stats.scan_giveways == {}


# --- a mesh ---------------------------------------------------------------


@pytest.mark.parametrize("n_slots, n_symbols", [(4, 4), (4, 11), (36, 36)])
def test_four_shards_of_a_lane_each_run_the_kernel_per_chip(
        n_slots, n_symbols):
    """n_slots 4 on four CPU devices: a lane a shard, each shard's block
    padded to 8 rows; the events are the oracle's, the symbol ids the
    one-chip engine's, and growth provisions every shard anew."""
    orders = multi_symbol_stream(n=400, n_symbols=n_symbols, seed=8,
                                 cancel_prob=0.2)
    four = engine(n_slots, mesh=make_mesh(4))
    one = engine(n_slots)
    got4, got1 = [], []
    for cols in frame_cols(orders, 64):
        b4 = frames.resolve_frame(four, frames.submit_frame(four, cols))
        b1 = frames.resolve_frame(one, frames.submit_frame(one, cols))
        np.testing.assert_array_equal(b4.columns["symbol_id"],
                                      b1.columns["symbol_id"])
        got4.extend(b4.to_results())
        got1.extend(b1.to_results())
    assert got4 == got1 == oracle_events(orders)
    assert four.stats.scan_giveways == {} == one.stats.scan_giveways
    assert four.n_slots % 4 == 0 and four.n_slots >= n_symbols
    four.verify_books()
    _same_books(four, one, min(four.n_slots, one.n_slots))
    # what a mesh engine exports restores into one without a mesh
    plain = engine(1)
    plain.import_state(four.export_state())
    assert plain.n_slots == four.n_slots
    _same_books(four, plain, four.n_slots)


# --- what the instrumentation says of it -----------------------------------


def test_a_full_grids_span_notes_the_lanes_under_its_rows(monkeypatch):
    noted = []

    class spy(B.span):
        def __init__(self, name, **meta):
            noted.append((name, meta))
            super().__init__(name, **meta)

    monkeypatch.setattr(B, "span", spy)
    monkeypatch.setattr(frames, "span", spy)
    orders = multi_symbol_stream(n=120, n_symbols=3, seed=1)
    eng = engine(3)
    serve(eng, orders[:60])  # the fast path's dispatch
    for cols in frame_cols(orders[60:], 60):  # and the exact path's
        frames.process_frame(eng, cols)
    grids = [meta for name, meta in noted if name == "grid_dispatch"]
    assert len(grids) >= 2
    for meta in grids:
        assert (meta["grid"], meta["rows"], meta["lanes"]) == ("full", 8, 3)
    # a dense grid's rows are live lanes' and carry no such note
    wide = engine(300)
    noted.clear()
    serve(wide, multi_symbol_stream(n=60, n_symbols=5, seed=1))
    dense = [meta for name, meta in noted if name == "grid_dispatch"]
    assert dense and all(
        meta["grid"] == "dense" and "lanes" not in meta for meta in dense)


def test_metrics_carry_the_rows_beside_the_lanes():
    from gome_tpu.utils.metrics import REGISTRY

    eng = engine(1)
    frames.export_metrics(eng)
    text = REGISTRY.render()
    assert "gome_engine_lanes 1" in text
    assert "gome_engine_lane_rows 8" in text
    serve(eng, multi_symbol_stream(n=40, n_symbols=9, seed=1))
    text = REGISTRY.render()
    assert "gome_engine_lanes 16" in text
    assert "gome_engine_lane_rows 16" in text


def test_the_stop_line_names_rows_and_lanes(caplog):
    import logging

    from gome_tpu.config import BusConfig, Config, EngineConfig
    from gome_tpu.service import EngineService

    svc = EngineService(Config(
        engine=EngineConfig(cap=32, n_slots=3, max_t=8, dtype="int32",
                            kernel="pallas"),
        bus=BusConfig(backend="memory", match_wire="frame")))
    with caplog.at_level(logging.INFO):
        svc.stop()
    assert "the book stack holds 8 rows for the venue's 3 lanes" in caplog.text
