"""Orders that carry a time in force (PR 34): IOC, FOK and POST_ONLY adds.

The rules are oracle/book.py's docstring. Held here: the oracle against the
plain reference of the `spot10k_tif` venue (which imports nothing of the
program) event for event on seeded flows; the engine on the scan path and on
the interpreted Pallas kernel against the oracle, exact and fast path, over
cap classes and record budgets, with the edge cases by name; the gateway's two
admission paths and every codec on every kind number; what the packer counts
toward a lane's resting bound and price envelope; and the expired counts that
ride a frame's totals."""

import os
import random

import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.api import order_pb2 as pb
from gome_tpu.bus import MemoryQueue, QueueBus, colwire
from gome_tpu.bus.codec import decode_order, encode_order
from gome_tpu.bus.ordercodec import decode_orders_batch
from gome_tpu.engine import BatchEngine, BookConfig
from gome_tpu.engine import frames
from gome_tpu.engine.prepool import LocalPrePool
from gome_tpu.engine.step import LOT_MAX32
from gome_tpu.oracle import OracleEngine
from gome_tpu.service.gateway import OrderGateway, orders_from_columns
from gome_tpu.types import (
    ORDER_KINDS,
    Action,
    Order,
    OrderType,
    Side,
    may_rest,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT, MARKET, IOC, FOK, POST = (
    OrderType.LIMIT, OrderType.MARKET, OrderType.IOC, OrderType.FOK,
    OrderType.POST_ONLY,
)
BUY, SALE = Side.BUY, Side.SALE


def add(oid, side, price, volume, kind=LIMIT, sym="s0", uuid="u"):
    return Order(uuid=uuid, oid=str(oid), symbol=sym, side=side, price=price,
                 volume=volume, order_type=kind)


def cancel(oid, side, price, kind=LIMIT, sym="s0", uuid="u"):
    return Order(uuid=uuid, oid=str(oid), symbol=sym, side=side, price=price,
                 volume=0, action=Action.DEL, order_type=kind)


def tif_flow(seed, n=300, n_symbols=3, base=1_000, band=4, lots=(1, 30)):
    """A seeded flow of all five kinds and cancels, a few levels wide so
    that most adds meet resting orders; cancels aim at any earlier add that
    is not a market order (one aimed at an IOC or FOK add misses)."""
    rng = random.Random(seed)
    orders, targets = [], []
    for i in range(n):
        if targets and rng.random() < 0.2:
            sym, oid, side, price = rng.choice(targets)
            if rng.random() < 0.2:
                price += 1  # a wrong price misses
            kind = rng.choice([LIMIT, FOK])  # a cancel ignores its kind
            orders.append(cancel(oid, side, price, kind, sym))
            continue
        kind = rng.choice([LIMIT, LIMIT, MARKET, IOC, FOK, POST, POST])
        side = Side(rng.randrange(2))
        sym = f"s{rng.randrange(n_symbols)}"
        price = base + rng.randint(-band, band)
        orders.append(add(i, side, price, rng.randint(*lots), kind, sym,
                          uuid=f"u{rng.randrange(3)}"))
        if kind is not MARKET:
            targets.append((sym, str(i), side, price))
    return orders


def oracle_of(orders):
    oracle = OracleEngine()
    events = []
    for o in orders:
        events.extend(oracle.process(o))
    return events, oracle


def expired_of(stats):
    return (stats.expired_ioc, stats.fok_killed, stats.post_only_blocked)


# -- (a) the oracle against the venue's plain reference ----------------------


@pytest.fixture(scope="module")
def venue_reference():
    from benchmark import spec

    return spec.load_module(
        "spot10k_tif_reference_under_test",
        os.path.join(ROOT, "benchmark", "configs", "spot10k_tif_reference.py"),
    )


@pytest.mark.parametrize("seed", [1, 2, 3, 5, 8, 2147483659])
def test_the_oracle_and_the_venues_plain_reference_agree_event_for_event(
        seed, venue_reference):
    orders = tif_flow(seed, n=600)
    sym_id = {f"s{i}": i for i in range(3)}
    cols = dict(
        sym=[sym_id[o.symbol] for o in orders],
        uid=[int(o.uuid[1:] or 0) if o.uuid != "u" else 9 for o in orders],
        oid=[int(o.oid) for o in orders],
        side=[int(o.side) for o in orders],
        kind=[int(o.order_type) for o in orders],
        cancel=[o.action is Action.DEL for o in orders],
        price=[o.price for o in orders],
        volume=[o.volume for o in orders],
    )
    uid_of = {o.uuid: u for o, u in zip(orders, cols["uid"])}
    want = venue_reference.run(cols)
    oracle = OracleEngine()
    got = []
    for i, o in enumerate(orders):
        for e in oracle.process(o):
            t, m = e.node, e.match_node
            got.append((
                i, sym_id[t.symbol], uid_of[t.uuid], int(t.oid), int(t.side),
                t.price, t.volume, uid_of[m.uuid], int(m.oid), int(m.side),
                m.price, m.volume, e.match_volume,
            ))
    assert got == want
    assert len(want) > 200 and min(expired_of(oracle.stats)) > 5
    # the plain Book, which knows none of the kinds, is another venue
    from benchmark import reference

    assert reference.run(cols) != want


# -- (b) the engine, scan and interpreted kernel, against the oracle ----------

KERNELS = {
    "scan": dict(kernel="scan"),
    "interpret": dict(kernel="pallas", pallas_interpret=True),
}


def engine_of(kernel, cap=16, max_fills=2, n_slots=8, max_t=8,
              dtype=jnp.int32):
    return BatchEngine(BookConfig(cap=cap, max_fills=max_fills, dtype=dtype),
                       n_slots=n_slots, max_t=max_t, **KERNELS[kernel])


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


@pytest.mark.parametrize("path", [run_exact, run_fast],
                         ids=["exact", "fast"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("cap, max_fills, deep", [
    (16, 2, 0),      # one cap class; most takers outrun the records
    (256, 16, 0),    # the ladder 64 / 256, every lane in class 64
    (256, 4, 100),   # s0 opens 100 deep a side: it runs in class 256
], ids=["cap16_k2", "cap256_k16", "cap256_k4_deep"])
def test_seeded_flows_of_every_kind_match_the_oracle(cap, max_fills, deep,
                                                     kernel, path):
    # quotes outside the flow's band, which only market orders reach
    orders = [
        add(10_000 + i, side, 1_000 + (10 + i % 5) * (1 - 2 * (side is BUY)),
            30, POST if i % 3 else LIMIT)
        for i in range(deep) for side in (BUY, SALE)
    ] + tif_flow(11, n=300, n_symbols=3 if not deep else 1)
    want, oracle = oracle_of(orders)
    eng = engine_of(kernel, cap=cap, max_fills=max_fills)
    assert path(eng, orders) == want
    eng.verify_books()
    assert expired_of(eng.stats) == expired_of(oracle.stats)
    assert min(expired_of(eng.stats)) > 0
    if deep:  # the lane stayed above the 64-slot class all through
        assert int(np.asarray(eng.books.count).max()) > 64


def book_of(eng, sym="s0"):
    """[(oid, price, lots)] per side of one symbol, in priority order."""
    lane = eng.symbol_lane(sym)
    books = eng.lane_books()
    out = []
    for side in (0, 1):
        n = int(books.count[lane, side])
        out.append([
            (eng.oids.table[int(books.oid[lane, side, j])],
             int(books.price[lane, side, j]), int(books.lots[lane, side, j]))
            for j in range(n)
        ])
    return out


def fills_of(events):
    return [(e.node.oid, e.match_node.oid, e.match_volume) for e in events
            if e.match_volume]


EDGE_CASES = {}


def edge_case(fn):
    EDGE_CASES[fn.__name__] = fn
    return fn


@edge_case
def fok_with_avail_equal_to_volume(run):
    events, book, stats = run([
        add(1, SALE, 100, 3), add(2, SALE, 101, 4),
        add(3, BUY, 101, 7, FOK),
    ])
    assert fills_of(events) == [("3", "1", 3), ("3", "2", 4)]
    assert book == [[], []] and stats == (0, 0, 0)


@edge_case
def fok_with_avail_one_short_of_volume(run):
    events, book, stats = run([
        add(1, SALE, 100, 3), add(2, SALE, 101, 4), add(9, SALE, 102, 50),
        add(3, BUY, 101, 8, FOK),   # 102 does not cross: C holds 7
    ])
    assert events == [] and stats == (0, 1, 0)
    assert book == [[], [("1", 100, 3), ("2", 101, 4), ("9", 102, 50)]]


@edge_case
def fok_that_fills_through_more_makers_than_max_fills(run):
    makers = [add(i, SALE, 100 + i % 2, 1) for i in range(1, 6)]
    events, book, stats = run(makers + [add(7, BUY, 101, 5, FOK)])
    assert sorted(fills_of(events)) == [("7", str(i), 1) for i in range(1, 6)]
    assert book == [[], []] and stats == (0, 0, 0)


@edge_case
def fok_into_an_empty_side(run):
    events, book, stats = run([
        add(1, BUY, 100, 5, FOK), add(2, SALE, 100, 5),
    ])
    assert events == [] and stats == (0, 1, 0)
    assert book == [[], [("2", 100, 5)]]  # the killed add left nothing


@edge_case
def post_only_at_the_touch_price_is_blocked(run):
    events, book, stats = run([
        add(1, SALE, 100, 5), add(2, BUY, 100, 3, POST),
    ])
    assert events == [] and stats == (0, 0, 1)
    assert book == [[], [("1", 100, 5)]]


@edge_case
def post_only_one_tick_behind_rests_at_its_levels_tail(run):
    events, book, stats = run([
        add(1, SALE, 100, 5), add(2, BUY, 99, 4),
        add(3, BUY, 99, 6, POST),           # rests behind order 2
        add(4, SALE, 99, 5, MARKET),         # takes 2 whole, then 3
    ])
    assert fills_of(events) == [("4", "2", 4), ("4", "3", 1)]
    assert book == [[("3", 99, 5)], [("1", 100, 5)]] and stats == (0, 0, 0)


@edge_case
def ioc_partly_filled_then_cancelled_misses(run):
    events, book, stats = run([
        add(1, SALE, 100, 3), add(2, BUY, 100, 5, IOC),
        cancel(2, BUY, 100),                # never rested: a miss, no event
        add(3, SALE, 100, 2),               # meets no phantom bid
    ])
    assert fills_of(events) == [("2", "1", 3)] and len(events) == 1
    assert events[0].node.volume == 2      # the taker's remainder, dropped
    assert book == [[], [("3", 100, 2)]] and stats == (1, 0, 0)


@edge_case
def a_kind_on_a_del_is_ignored(run):
    events, book, stats = run([
        add(1, BUY, 100, 5), add(2, SALE, 101, 5),
        cancel(1, BUY, 100, FOK), cancel(2, SALE, 101, POST),
    ])
    assert [e.match_volume for e in events] == [0, 0]
    assert [e.node.volume for e in events] == [5, 5]
    assert book == [[], []] and stats == (0, 0, 0)


@edge_case
def int32_saturation_of_avail(run):
    big = LOT_MAX32
    events, book, stats = run([
        # three makers of the ceiling: C's true sum passes 2**31
        add(1, SALE, 100, big), add(2, SALE, 100, big), add(3, SALE, 101, big),
        add(4, BUY, 101, big, FOK),          # avail saturates, >= volume
        # below the clamp the sum is exact: 2 x (big // 2) = big - 1
        add(5, BUY, 90, big // 2), add(6, BUY, 90, big // 2),
        add(7, SALE, 90, big, FOK),          # one lot short: killed
        add(8, SALE, 90, big - 1, FOK),      # exactly avail: fills both
    ])
    assert fills_of(events) == [
        ("4", "1", big), ("8", "5", big // 2), ("8", "6", big // 2)]
    assert book == [[], [("2", 100, big), ("3", 101, big)]]
    assert stats == (0, 1, 0)


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
        assert got == want
        eng.verify_books()
        assert expired_of(eng.stats) == expired_of(oracle.stats)
        return got, book_of(eng), expired_of(eng.stats)

    EDGE_CASES[case](run)


# -- (c) the gateway and the codecs on every kind number ----------------------


def gateway(columnar):
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    pool = LocalPrePool()
    gw = OrderGateway(
        bus, accuracy=8,
        mark=lambda o: pool.add((o.symbol, o.uuid, o.oid)),
        unmark=lambda o: pool.discard((o.symbol, o.uuid, o.oid)),
        mark_frame=pool.mark_frame if columnar else None,
        unmark_frame=pool.unmark_frame if columnar else None,
        columnar=columnar,
    )
    return gw, bus


def emitted(bus):
    out = []
    for msg in bus.order_queue.read_from(0, 1000):
        if msg.body[:1] == b"G":
            out.extend(orders_from_columns(
                colwire.decode_order_frame(msg.body)))
        else:
            out.append(decode_order(msg.body))
    return out


@pytest.mark.parametrize("kind", [*range(8), 11, 14, 127, -1])
def test_scalar_and_columnar_admission_give_one_verdict_per_kind(kind):
    """2, 5 and 7 are no kinds, nor is a number whose low three bits are
    one (11, 14: types.known_kinds takes off an 8-entry table) or a negative
    one; MARKET alone may come without a price; a cancel is held to the same
    kinds and then ignores them."""
    reqs = [
        pb.OrderRequest(uuid="u", oid=f"k{kind}p", symbol="s", transaction=0,
                        price=1.5, volume=2.0, kind=kind),
        pb.OrderRequest(uuid="u", oid=f"k{kind}z", symbol="s", transaction=1,
                        price=0.0, volume=2.0, kind=kind),
        pb.OrderRequest(uuid="u", oid=f"k{kind}c", symbol="s", transaction=1,
                        price=1.5, volume=0.0, kind=kind),
    ]
    is_cancel = [False, False, True]
    known = kind in ORDER_KINDS
    want = [known, known and kind == MARKET, known]
    scalar, bus_s = gateway(False)
    verdicts = [
        (scalar.DeleteOrder if c else scalar.DoOrder)(r, None).code == 0
        for r, c in zip(reqs, is_cancel)
    ]
    assert verdicts == want
    batch = pb.OrderBatchRequest(orders=reqs, cancel=is_cancel)
    for columnar in (False, True):
        gw, bus = gateway(columnar)
        resp = gw.DoOrderBatch(batch, None)
        assert resp.code == 0 and resp.accepted == sum(want)
        assert sorted(resp.reject_index) == [
            i for i, ok in enumerate(want) if not ok]
        out = emitted(bus)
        assert [int(o.order_type) for o in out] == [kind] * sum(want)
    assert all("rejected" in r.message for r in resp.rejects)


@pytest.mark.parametrize("kind", ORDER_KINDS)
def test_a_kind_number_passes_every_codec_unchanged(kind):
    req = pb.OrderRequest.FromString(
        pb.OrderRequest(uuid="u", oid="o", symbol="s", price=1.0, volume=1.0,
                        kind=kind).SerializeToString())
    assert req.kind == kind and pb.OrderKind.Name(kind) == OrderType(kind).name
    orders = [add(i, BUY, 100 + i, 1 + i, OrderType(kind)) for i in range(3)]
    bodies = [encode_order(o) for o in orders]
    assert [decode_order(b) for b in bodies] == orders
    assert decode_orders_batch(bodies) == orders  # the native codec's path
    cols = colwire.decode_order_frame(colwire.encode_orders(orders))
    assert cols["kind"].tolist() == [kind] * 3
    assert frames.orders_from_frame(cols) == orders


def test_an_unassigned_kind_number_declines_in_the_order_codecs():
    body = encode_order(add(1, BUY, 100, 1)).replace(b"}", b',"Kind":2}')
    with pytest.raises(ValueError):
        decode_order(body)
    with pytest.raises(ValueError):
        decode_orders_batch([body])


# -- (d) what the packer counts: the resting bound and the price envelope -----


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
def test_only_kinds_that_can_rest_feed_count_ub_and_the_price_envelope(
        native, monkeypatch):
    """On the native packer and on its numpy twin, which clamp a taker's
    far limit alike (step.TAKER_PRICE_MAX32)."""
    from gome_tpu.engine import nativehost

    if native and not nativehost.available():
        pytest.skip("native toolchain unavailable")
    if not native:
        monkeypatch.setattr(nativehost, "available", lambda: False)
    assert [k for k in OrderType if may_rest(k)] == [LIMIT, POST]
    assert may_rest(np.array(ORDER_KINDS)).tolist() == [
        True, False, False, False, True]
    eng = engine_of("scan", cap=256, max_fills=4)  # a cap ladder: 64, 256
    frames.process_frame(eng, colwire.orders_to_cols(
        [add(1, BUY, 1_000, 5), add(2, SALE, 1_010, 5)]))
    lane = eng.symbol_lane("s0")
    ub0 = int(eng.count_ub()[lane])
    env0 = (int(eng._env_lo[lane]), int(eng._env_hi[lane]))
    assert ub0 == 1 and env0 == (1_000, 1_010)

    def submit(orders):
        pend = frames.submit_frame(eng, colwire.orders_to_cols(orders))
        seen = int(eng.count_ub()[lane])  # at pack time, the frame in flight
        frames.resolve_frame(eng, pend)
        return seen, (int(eng._env_lo[lane]), int(eng._env_hi[lane]))

    far = 1 << 40  # no rebased int32 price holds it
    takers = [add(10 + i, BUY, far, 1, kind) for i, kind in
              enumerate([IOC, FOK, MARKET] * 20)]
    takers += [add(90, SALE, 1, 1, IOC), add(91, SALE, 1, 1, FOK)]
    assert submit(takers) == (ub0, env0)
    eng.verify_books()
    # and they matched by their true limits, clamped or not
    # (60 lots bought from order 2's 5, two sold to order 1)
    assert book_of(eng) == [[("1", 1_000, 3)], []]
    posts = [add(100 + i, BUY, 990 - i, 1, POST) for i in range(7)]
    seen, env = submit(posts)
    assert seen == int(eng.count_ub()[lane]) == 1 + 7
    assert env == (984, 1_010)
    assert eng.stats.adds_by_kind == {
        int(LIMIT): 2, int(MARKET): 20, int(IOC): 21, int(FOK): 21,
        int(POST): 7}


# -- (e) the expired counts ride the totals; the buffers are still reused ----


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_expired_in_the_totals_is_the_oracles_count_and_buffers_are_reused(
        kernel):
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.engine.pipeline import FramePipeline

    orders = tif_flow(21, n=640, n_symbols=4)
    want, oracle = oracle_of(orders)
    meng = MatchEngine(config=BookConfig(cap=64, max_fills=8,
                                         dtype=jnp.int32),
                       n_slots=8, max_t=8, **KERNELS[kernel])
    for o in orders:
        meng.mark(o)
    pipe = FramePipeline(meng, depth=2)
    got = []
    for i in range(0, len(orders), 64):
        cols = colwire.decode_order_frame(
            colwire.encode_orders(orders[i:i + 64]))
        for _tok, batch in pipe.feed(cols):
            got.extend(batch.to_results())
    for _tok, batch in pipe.flush():
        got.extend(batch.to_results())
    assert got == want
    st = meng.batch.stats
    assert expired_of(st) == expired_of(oracle.stats)
    assert min(expired_of(st)) > 5
    adds = [o for o in orders if o.action is Action.ADD]
    assert st.adds_by_kind == {
        int(k): sum(o.order_type is k for o in adds) for k in OrderType}
    # PR 33's book-keeping: a frame's buffers are handed back and taken again
    assert st.fast_frames == 10 and st.fast_frames_reused >= 6
    width = frames.n_totals(meng.batch.config)
    assert width == 7
    held = [s for sets in meng.batch._event_buffers.values() for s in sets]
    assert held and all(s[2].shape[1] == width for s in held)


def test_the_order_kind_counters_are_on_metrics():
    from gome_tpu.utils.metrics import REGISTRY

    eng = engine_of("scan")
    frames.export_metrics(eng)
    run_fast(eng, [add(1, SALE, 100, 3), add(2, BUY, 100, 5, IOC),
                   add(3, BUY, 100, 5, FOK), add(4, SALE, 101, 1),
                   add(5, BUY, 101, 1, POST)])
    text = REGISTRY.render()
    for line in (
        'gome_orders_admitted_total{kind="ioc"} 1',
        'gome_orders_admitted_total{kind="limit"} 2',
        'gome_orders_expired_total{kind="ioc"} 1',
        'gome_orders_expired_total{kind="fok"} 1',
        'gome_orders_expired_total{kind="post_only"} 1',
    ):
        assert line in text, line
