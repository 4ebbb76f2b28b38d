"""Self-trade prevention, rule expire_taker (PR 41): an add stops at its
owner's first resting order and what is left of it expires.

The rule is oracle/book.py's docstring; the step decides it on the device
(engine/step.py `_match`). Held here: the engine against the oracle event for
event and book for book, on the scan path and the interpreted Pallas kernel,
through every way a step is reached (the scalar host path, the exact list
path, dense and full grids, a small frame's one program, a large frame's
three calls, four CPU devices under a mesh), on seeded flows of three users
and on the edge cases by name; both escalations re-run under the rule; the
venue without the rule on the same flows; the counters; the config's check;
a snapshot's rule; and a short fuzz."""

import dataclasses
import importlib.util
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.bus import colwire
from gome_tpu.config import EngineConfig
from gome_tpu.engine import BatchEngine, BookConfig, frames, init_book, step
from gome_tpu.engine.host import Interner, OpContext, decode_events, encode_op
from gome_tpu.engine.step import EXPIRED_STP
from gome_tpu.oracle import OracleEngine
from gome_tpu.types import (
    ORDER_KINDS,
    SELF_TRADE_RULES,
    Action,
    Order,
    OrderType,
    Side,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT, MARKET, IOC, FOK, POST = (
    OrderType.LIMIT, OrderType.MARKET, OrderType.IOC, OrderType.FOK,
    OrderType.POST_ONLY,
)
BUY, SALE = Side.BUY, Side.SALE
STP = "expire_taker"


def add(oid, side, price, volume, kind=LIMIT, uuid="a", sym="s0"):
    return Order(uuid=uuid, oid=str(oid), symbol=sym, side=side, price=price,
                 volume=volume, order_type=kind)


def cancel(oid, side, price, uuid="a", sym="s0"):
    return Order(uuid=uuid, oid=str(oid), symbol=sym, side=side, price=price,
                 volume=0, action=Action.DEL)


def stp_flow(seed, n=300, n_symbols=3, users=3, base=1_000, band=4,
             lots=(1, 30)):
    """A seeded flow of every kind and cancels from a few users, a few
    levels wide: most adds meet resting orders and a third of those are the
    taker's own."""
    rng = random.Random(seed)
    orders, targets = [], []
    for i in range(n):
        if targets and rng.random() < 0.15:
            sym, oid, side, price, uuid = rng.choice(targets)
            if rng.random() < 0.2:
                price += 1  # a wrong price misses
            orders.append(cancel(oid, side, price, uuid, sym))
            continue
        kind = rng.choice([LIMIT, LIMIT, LIMIT, MARKET, IOC, FOK, POST])
        side = Side(rng.randrange(2))
        sym = f"s{rng.randrange(n_symbols)}"
        price = base + rng.randint(-band, band)
        uuid = f"u{rng.randrange(users)}"
        orders.append(add(i, side, price, rng.randint(*lots), kind, uuid,
                          sym))
        if kind is not MARKET:
            targets.append((sym, str(i), side, price, uuid))
    return orders


def oracle_of(orders, rule=STP):
    oracle = OracleEngine(self_trade=rule)
    events = []
    for o in orders:
        events.extend(oracle.process(o))
    return events, oracle


def expired_of(stats):
    return (stats.expired_ioc, stats.fok_killed, stats.post_only_blocked,
            stats.stp_expired)


def self_trades(events):
    return [e for e in events
            if e.match_volume and e.node.uuid == e.match_node.uuid]


def oracle_book(oracle, sym="s0"):
    """[(oid, uuid, price, lots)] per side, in priority order."""
    book = oracle.book(sym)
    return [[(o.oid, o.uuid, o.price, o.volume) for o in book.orders(side)]
            for side in (BUY, SALE)]


def engine_book(eng, sym="s0"):
    lane = eng.symbol_lane(sym)
    if lane is None:
        return [[], []]
    books = eng.lane_books()
    return [[
        (eng.oids.table[int(books.oid[lane, side, j])],
         eng.uids.table[int(books.uid[lane, side, j])],
         int(books.price[lane, side, j]), int(books.lots[lane, side, j]))
        for j in range(int(books.count[lane, side]))
    ] for side in (0, 1)]


# -- the ways a step is reached -----------------------------------------------

KERNELS = {
    "scan": dict(kernel="scan"),
    "interpret": dict(kernel="pallas", pallas_interpret=True),
}


def engine_of(kernel, rule=STP, cap=16, max_fills=2, n_slots=8, max_t=8,
              dtype=jnp.int32, **kw):
    return BatchEngine(
        BookConfig(cap=cap, max_fills=max_fills, dtype=dtype,
                   self_trade=rule),
        n_slots=n_slots, max_t=max_t, **KERNELS[kernel], **kw)


def run_exact(eng, orders, chunk=64):
    out = []
    for i in range(0, len(orders), chunk):
        out.extend(eng.process(orders[i:i + chunk]))
    return out


def run_fast(eng, orders, chunk=64):
    out = []
    for i in range(0, len(orders), chunk):
        cols = colwire.decode_order_frame(
            colwire.encode_orders(orders[i:i + chunk]))
        out.extend(frames.apply_frame_fast(eng, cols).to_results())
    return out


def _mesh4():
    from gome_tpu.parallel import make_mesh

    return make_mesh(4)


#: path -> (engine keywords, how to run, what the engine's counters must
#: then say of the way its grids went)
PATHS = {
    "exact": (dict(), run_exact, lambda st: st.fast_frames == 0),
    # three live lanes of 64: compact grids over them
    "dense": (dict(dense=True, n_slots=64), run_fast,
              lambda st: any(k.endswith("_dense") for k in st.grids_by_kernel)
              and st.fast_grids_one_program > 0),
    "full": (dict(dense=False), run_fast,
             lambda st: all(k.endswith("_full") for k in st.grids_by_kernel)
             and st.fast_grids_one_program > 0),
    "three_calls": (dict(), run_fast,
                    lambda st: st.fast_frames > 0
                    and st.fast_grids_one_program == 0),
    "mesh4": (dict(), run_fast,
              lambda st: st.fast_frames > 0
              and st.fast_grids_one_program == 0),
}


def engine_on(path, kernel, monkeypatch, **kw):
    """An engine whose grids go the way `path` names, and its runner."""
    more, run, went = PATHS[path]
    if path == "three_calls":  # no frame is small: scatter, step, compaction
        monkeypatch.setattr(frames, "ONE_PHASE_MAX_BYTES", 0)
    if path == "mesh4":
        more = dict(mesh=_mesh4())
    return engine_of(kernel, **more, **kw), run, went


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_a_seeded_flow_of_three_users_matches_the_oracle_under_the_rule(
        kernel, path, monkeypatch):
    orders = stp_flow(7, n=240)
    want, oracle = oracle_of(orders)
    eng, run, went = engine_on(path, kernel, monkeypatch)
    assert run(eng, orders) == want
    eng.verify_books()
    assert went(eng.stats), (eng.stats.grids_by_kernel,
                             eng.stats.fast_grids_one_program)
    for sym in ("s0", "s1", "s2"):
        assert engine_book(eng, sym) == oracle_book(oracle, sym)
    assert expired_of(eng.stats) == expired_of(oracle.stats)
    assert min(expired_of(eng.stats)) > 0 and eng.stats.stp_expired > 10
    assert want and not self_trades(want)


class ScalarHarness:
    """The scalar host path: engine/host.py's encode_op and decode_events
    round the single-op jitted step, one book per symbol."""

    def __init__(self, config):
        self.config = config
        self.books = {}
        self.oids, self.uids = Interner(), Interner()
        self.stp_expired = 0

    def process(self, order):
        book = self.books.get(order.symbol)
        if book is None:
            book = init_book(self.config)
        op = encode_op(order, self.oids, self.uids,
                       dtype=np.dtype(self.config.dtype))
        self.books[order.symbol], out = step(self.config, book, op)
        out = jax.device_get(out)
        self.stp_expired += int(out.expired) == EXPIRED_STP
        return decode_events(OpContext(order), out, self.oids, self.uids)


@pytest.mark.parametrize("rule", SELF_TRADE_RULES)
def test_the_scalar_host_path_matches_the_oracle_under_either_rule(rule):
    orders = stp_flow(9, n=200)
    want, oracle = oracle_of(orders, rule)
    harness = ScalarHarness(
        BookConfig(cap=64, max_fills=32, dtype=jnp.int32, self_trade=rule))
    got = []
    for o in orders:
        got.extend(harness.process(o))
    assert got == want
    assert harness.stp_expired == oracle.stats.stp_expired
    assert (harness.stp_expired > 10) == (rule == STP)
    assert bool(self_trades(want)) == (rule == "none")


# -- the rule's edge cases, by name --------------------------------------------


def fills_of(events):
    return [(e.node.oid, e.match_node.oid, e.match_volume) for e in events
            if e.match_volume]


EDGE_CASES = {}


def edge_case(fn):
    EDGE_CASES[fn.__name__] = fn
    return fn


@edge_case
def own_order_at_the_head_a_limit_add_vanishes(run):
    events, book, stats = run([
        add(1, SALE, 100, 5, uuid="a"), add(2, SALE, 101, 5, uuid="b"),
        add(3, BUY, 101, 4, uuid="a"),       # meets its own order 1 first
        cancel(3, BUY, 101, uuid="a"),       # never rested: a miss
    ])
    assert events == [] and stats == (0, 0, 0, 1)
    assert book == [[], [("1", "a", 100, 5), ("2", "b", 101, 5)]]


@edge_case
def own_order_in_the_middle_the_fills_before_it_stand(run):
    events, book, stats = run([
        add(1, SALE, 100, 3, uuid="b"), add(2, SALE, 100, 4, uuid="a"),
        add(3, SALE, 100, 9, uuid="b"),
        add(4, BUY, 100, 10, uuid="a"),      # fills 1, stops at 2, spares 3
    ])
    assert fills_of(events) == [("4", "1", 3)]
    assert events[0].node.volume == 7        # the taker's remainder, expired
    assert book == [[], [("2", "a", 100, 4), ("3", "b", 100, 9)]]
    assert stats == (0, 0, 0, 1)


@edge_case
def own_order_behind_where_the_volume_runs_out_nothing_differs(run):
    events, book, stats = run([
        add(1, SALE, 100, 3, uuid="b"), add(2, SALE, 100, 4, uuid="b"),
        add(3, SALE, 100, 9, uuid="a"),
        add(4, BUY, 100, 7, uuid="a"),       # exactly what lies ahead
        add(5, BUY, 99, 2, uuid="a"),        # does not cross: rests
    ])
    assert fills_of(events) == [("4", "1", 3), ("4", "2", 4)]
    assert book == [[("5", "a", 99, 2)], [("3", "a", 100, 9)]]
    assert stats == (0, 0, 0, 0)


@edge_case
def own_order_at_a_worse_level_stops_the_walk_there(run):
    events, book, stats = run([
        add(1, BUY, 100, 2, uuid="b"), add(2, BUY, 99, 5, uuid="a"),
        add(3, BUY, 98, 5, uuid="b"),
        add(4, SALE, 98, 6, uuid="a"),       # takes 1, stops at 2, spares 3
    ])
    assert fills_of(events) == [("4", "1", 2)]
    assert book == [[("2", "a", 99, 5), ("3", "b", 98, 5)], []]
    assert stats == (0, 0, 0, 1)


@edge_case
def a_market_add_stops_at_its_owners_order(run):
    events, book, stats = run([
        add(1, SALE, 100, 2, uuid="b"), add(2, SALE, 105, 5, uuid="a"),
        add(3, SALE, 110, 5, uuid="b"),
        add(4, BUY, 0, 20, MARKET, uuid="a"),
    ])
    assert fills_of(events) == [("4", "1", 2)]
    assert book == [[], [("2", "a", 105, 5), ("3", "b", 110, 5)]]
    assert stats == (0, 0, 0, 1)


@edge_case
def an_ioc_add_stopped_by_the_rule_counts_under_the_rule_alone(run):
    events, book, stats = run([
        add(1, SALE, 100, 2, uuid="b"), add(2, SALE, 100, 5, uuid="a"),
        add(3, BUY, 100, 4, IOC, uuid="a"),  # 2 filled, 2 expire at order 2
        add(4, BUY, 100, 9, IOC, uuid="b"),  # takes order 2, 4 lots dropped
    ])
    assert fills_of(events) == [("3", "1", 2), ("4", "2", 5)]
    assert book == [[], []] and stats == (1, 0, 0, 1)


@edge_case
def fok_killed_by_lots_that_lie_behind_its_owners_order(run):
    events, book, stats = run([
        add(1, SALE, 100, 3, uuid="b"), add(2, SALE, 100, 1, uuid="a"),
        add(3, SALE, 100, 9, uuid="b"),
        add(4, BUY, 100, 4, FOK, uuid="a"),  # C holds 13, 3 of them ahead
    ])
    assert events == [] and stats == (0, 1, 0, 0)
    assert book == [[], [("1", "b", 100, 3), ("2", "a", 100, 1),
                         ("3", "b", 100, 9)]]


@edge_case
def fok_filled_by_lots_ahead_of_its_owners_order(run):
    events, book, stats = run([
        add(1, SALE, 100, 3, uuid="b"), add(2, SALE, 100, 1, uuid="a"),
        add(3, BUY, 100, 3, FOK, uuid="a"),
    ])
    assert fills_of(events) == [("3", "1", 3)]
    assert book == [[], [("2", "a", 100, 1)]] and stats == (0, 0, 0, 0)


@edge_case
def post_only_blocked_by_its_owners_order_alone(run):
    events, book, stats = run([
        add(1, SALE, 100, 5, uuid="a"),
        add(2, BUY, 100, 3, POST, uuid="a"),  # must not rest into a cross
        add(3, BUY, 99, 3, POST, uuid="a"),   # does not cross: rests
    ])
    assert events == [] and stats == (0, 0, 1, 0)
    assert book == [[("3", "a", 99, 3)], [("1", "a", 100, 5)]]


@edge_case
def a_cancel_takes_no_notice_of_the_owner(run):
    events, book, stats = run([
        add(1, BUY, 100, 5, uuid="a"),
        cancel(1, BUY, 100, uuid="b"),
    ])
    assert [e.match_volume for e in events] == [0]
    assert book == [[], []] and stats == (0, 0, 0, 0)


@edge_case
def an_own_order_beyond_the_record_budget(run):
    # three makers ahead of the own order, two records a step: the
    # fill-record escalation re-runs the lane, and has to stop where the
    # first run stopped
    events, book, stats = run([
        add(1, SALE, 100, 1, uuid="b"), add(2, SALE, 100, 1, uuid="c"),
        add(3, SALE, 100, 1, uuid="b"), add(4, SALE, 100, 1, uuid="a"),
        add(5, SALE, 100, 1, uuid="b"),
        add(6, BUY, 100, 5, uuid="a"),
    ])
    assert fills_of(events) == [("6", "1", 1), ("6", "2", 1), ("6", "3", 1)]
    assert book == [[], [("4", "a", 100, 1), ("5", "b", 100, 1)]]
    assert stats == (0, 0, 0, 1)


@pytest.mark.parametrize("path", [run_exact, run_fast],
                         ids=["exact", "fast"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_edge_case(case, kernel, path):
    """Each case states its own outcome; the oracle has to agree with it and
    the engine with the oracle, on either kernel and either path."""

    def run(orders):
        want, oracle = oracle_of(orders)
        eng = engine_of(kernel)
        got = path(eng, orders, chunk=3)  # a frame boundary inside most
        assert got == want and not self_trades(got)
        eng.verify_books()
        assert engine_book(eng) == oracle_book(oracle)
        assert expired_of(eng.stats) == expired_of(oracle.stats)
        return got, engine_book(eng), expired_of(eng.stats)

    EDGE_CASES[case](run)


# -- both escalations re-run under the rule -----------------------------------


@pytest.mark.parametrize("path", [run_exact, run_fast],
                         ids=["exact", "fast"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_the_record_escalation_and_a_lane_past_its_cap_class_keep_the_rule(
        kernel, path):
    """The books start 64 slots a side. s0 opens 100 deep a side and s1 90
    deep on one: the side fills up inside a frame and the cap escalation
    re-runs the grid on grown books, after which both lanes run above the
    64-slot class; takers cross a dozen makers against 4 records (the
    fill-record escalation re-runs the lane), with own orders among and
    beyond them."""
    rng = random.Random(5)
    orders = [
        add(10_000 + 2 * i + (side is SALE), side,
            1_000 + (3 + i % 7) * (1 - 2 * (side is BUY)), 2,
            uuid=f"u{i % 7 % 3}")            # one owner a level, in turn
        for i in range(100) for side in (BUY, SALE)
    ]
    orders += [  # sweeps of up to two levels, a level of 14 makers or so
        add(20_000 + i, Side(i % 2), 1_000 + (1 - 2 * (i % 2)) * 6,
            rng.randint(5, 60), rng.choice([LIMIT, IOC, FOK, MARKET]),
            uuid=f"u{rng.randrange(3)}")
        for i in range(40)
    ]
    orders += [add(30_000 + i, BUY, 500 - i % 5, 1, uuid=f"u{i % 3}",
                   sym="s1") for i in range(90)]
    orders += [add(31_000 + i, SALE, 495, 30, rng.choice([LIMIT, FOK, IOC]),
                   uuid=f"u{i % 3}", sym="s1") for i in range(12)]
    want, oracle = oracle_of(orders)
    eng = engine_of(kernel, cap=64, max_fills=4)
    assert path(eng, orders, chunk=128) == want
    eng.verify_books()
    st = eng.stats
    assert st.fill_record_escalations > 0
    assert st.grid_cap_escalations + st.cap_escalations > 0
    assert eng.config.cap > 64 and eng.config.self_trade == STP
    assert expired_of(st) == expired_of(oracle.stats) and st.stp_expired > 5
    for sym in ("s0", "s1"):
        assert engine_book(eng, sym) == oracle_book(oracle, sym)
    assert not self_trades(want)


# -- the venue without the rule -------------------------------------------------


@pytest.mark.parametrize("path", [run_exact, run_fast],
                         ids=["exact", "fast"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_under_none_the_same_flow_gives_todays_events_self_trades_among_them(
        kernel, path):
    orders = stp_flow(7, n=240)
    want, oracle = oracle_of(orders, "none")
    eng = engine_of(kernel, rule="none")
    assert path(eng, orders) == want
    assert expired_of(eng.stats) == expired_of(oracle.stats)
    assert eng.stats.stp_expired == 0 and len(self_trades(want)) > 10
    assert want != oracle_of(orders, STP)[0]
    # its totals are as wide as they always were, the rule's one wider
    assert frames.n_totals(eng.config) == 7
    assert frames.n_totals(engine_of(kernel).config) == 8


def test_under_none_the_step_traces_no_owner_compare():
    """The rule is static: the default's step holds no minimum."""
    from gome_tpu.engine.book import DeviceOp

    def text(rule):
        cfg = BookConfig(cap=8, max_fills=2, dtype=jnp.int32,
                         self_trade=rule)
        op = DeviceOp(*(jnp.zeros((), jnp.int32) for _ in DeviceOp._fields))
        return str(jax.make_jaxpr(
            lambda b, o: step.__wrapped__(cfg, b, o))(init_book(cfg), op))

    assert "reduce_min" not in text("none")
    assert "reduce_min" in text(STP)


# -- the counters ---------------------------------------------------------------


def test_the_rules_counter_is_on_metrics_in_the_stats_and_in_the_stop_line(
        caplog):
    from gome_tpu.utils.metrics import REGISTRY

    eng = engine_of("scan")
    frames.export_metrics(eng)
    run_fast(eng, [
        add(1, SALE, 100, 3, uuid="a"), add(2, BUY, 100, 5, uuid="a"),
        add(3, BUY, 100, 5, IOC, uuid="b"), add(4, SALE, 101, 1, uuid="a"),
        add(5, BUY, 101, 1, IOC, uuid="a"),
    ])
    assert expired_of(eng.stats) == (1, 0, 0, 2)
    text = REGISTRY.render()
    for line in (
        'gome_orders_expired_total{kind="stp"} 2',
        'gome_orders_expired_total{kind="ioc"} 1',
        'gome_orders_expired_total{kind="fok"} 0',
    ):
        assert line in text, line
    assert EXPIRED_STP not in ORDER_KINDS and EXPIRED_STP > 0


def test_the_services_stop_line_prints_the_rule_and_its_count(caplog):
    import logging

    from gome_tpu.config import Config
    from gome_tpu.service.app import EngineService

    cfg = Config(engine=EngineConfig(
        cap=16, max_fills=4, n_slots=8, max_t=8, dtype="int32",
        self_trade=STP))
    svc = EngineService(cfg)
    assert svc.engine.batch.config.self_trade == STP
    svc.engine.batch.stats.stp_expired = 3
    with caplog.at_level(logging.INFO):
        svc.stop()
    assert any("3 stopped at their owner's order (self_trade expire_taker)"
               in r.getMessage() for r in caplog.records)


def test_step_stats_counts_what_the_rule_expired():
    events, oracle = oracle_of([
        add(1, SALE, 100, 3, uuid="a"), add(2, BUY, 100, 5, uuid="a"),
    ])
    assert events == [] and oracle.stats.stp_expired == 1
    events, oracle = oracle_of([
        add(1, SALE, 100, 3, uuid="a"), add(2, BUY, 100, 5, uuid="a"),
    ], "none")
    assert len(events) == 1 and oracle.stats.stp_expired == 0


# -- the config, and what a snapshot says of the rule ---------------------------


@pytest.mark.parametrize("value", ["expire_maker", "EXPIRE_TAKER", "", None,
                                   True])
def test_the_config_rejects_any_other_value(value):
    with pytest.raises(ValueError, match="engine.self_trade"):
        EngineConfig(self_trade=value)
    with pytest.raises(ValueError, match="engine.self_trade"):
        BookConfig(self_trade=value)
    with pytest.raises(ValueError, match="engine.self_trade"):
        OracleEngine(self_trade=value)


def test_the_rule_comes_from_the_config_file_and_defaults_to_none(tmp_path):
    from gome_tpu.config import load_config

    assert EngineConfig().self_trade == "none"
    assert EngineConfig().book_config() == BookConfig(
        cap=256, max_fills=16, dtype=jnp.int64)
    path = tmp_path / "config.yaml"
    path.write_text("engine:\n  dtype: int32\n  self_trade: expire_taker\n")
    book = load_config(str(path)).engine.book_config()
    assert book.self_trade == STP and book.dtype == jnp.int32
    path.write_text("engine:\n  self_trade: cancel_both\n")
    with pytest.raises(ValueError, match="engine.self_trade"):
        load_config(str(path))


def test_a_snapshot_restores_only_under_the_rule_it_was_written_under():
    orders = stp_flow(3, n=80)
    eng = engine_of("scan")
    run_fast(eng, orders)
    state = eng.export_state()
    assert state["self_trade"] == STP
    twin = engine_of("scan")
    twin.import_state(state)
    assert engine_book(twin) == engine_book(eng)
    more = stp_flow(4, n=40)
    assert run_fast(twin, more) == run_fast(eng, more)
    with pytest.raises(ValueError, match="self_trade"):
        engine_of("scan", rule="none").import_state(state)
    old = {k: v for k, v in state.items() if k != "self_trade"}
    with pytest.raises(ValueError, match="written under engine.self_trade"):
        engine_of("scan").import_state(old)  # from before the rule: "none"
    engine_of("scan", rule="none").import_state(old)


def test_the_rule_survives_every_copy_the_engine_makes_of_its_config():
    cfg = BookConfig(cap=64, max_fills=4, dtype=jnp.int32, self_trade=STP)
    assert dataclasses.replace(cfg, cap=256, max_fills=32).self_trade == STP
    assert hash(cfg) != hash(dataclasses.replace(cfg, self_trade="none"))


def test_the_simulators_default_venue_has_no_rule():
    from gome_tpu.sim.env import EnvConfig

    assert EnvConfig().book.self_trade == "none"


# -- a short fuzz under the rule ------------------------------------------------

_spec = importlib.util.spec_from_file_location(
    "gome_fuzz_stp", os.path.join(ROOT, "scripts", "fuzz.py"))
_fuzz = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fuzz)


@pytest.mark.parametrize("seed", range(500, 506))
def test_fuzz_case_under_the_rule(seed):
    line = _fuzz.run_case(seed, self_trade=STP)
    print(line)
    assert "self_trade=expire_taker" in line
