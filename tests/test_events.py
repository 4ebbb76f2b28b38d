"""Columnar decode path (engine/events.py): exact equivalence with the
oracle, as columns and as MatchResult objects, plus wire-format byte
parity."""


from gome_tpu.bus.codec import encode_match_result
from gome_tpu.engine import BatchEngine, BookConfig
from gome_tpu.oracle import OracleEngine
from gome_tpu.utils.streams import mixed_stream


def _fresh_engines(**kw):
    mk = lambda: BatchEngine(
        BookConfig(cap=32, max_fills=4), n_slots=8, max_t=16, **kw
    )
    return mk(), mk()


def test_columnar_equals_object_decode():
    """Same mixed stream (fills, partial fills, cancels, market orders)
    through both list forms -> the oracle's MatchResult list, whether the
    engine materialises the objects (process) or the caller does
    (process_columnar().to_results())."""
    orders = mixed_stream(n=220, seed=13, cancel_prob=0.25, market_prob=0.1)
    obj_engine, col_engine = _fresh_engines()
    obj_events, col_events = [], []
    for i in range(0, len(orders), 50):
        chunk = orders[i : i + 50]
        obj_events.extend(obj_engine.process(chunk))
        col_events.extend(col_engine.process_columnar(chunk).to_results())
    oracle = OracleEngine()
    expected = [ev for o in orders for ev in oracle.process(o)]
    assert obj_events == col_events == expected
    assert len(obj_events) > 0


def test_columnar_matches_oracle():
    orders = mixed_stream(n=150, seed=4, cancel_prob=0.2)
    oracle = OracleEngine()
    expected = []
    for o in orders:
        expected.extend(oracle.process(o))
    engine = BatchEngine(BookConfig(cap=64, max_fills=8), n_slots=8, max_t=32)
    got = []
    for i in range(0, len(orders), 40):
        got.extend(engine.process_columnar(orders[i : i + 40]).to_results())
    assert got == expected


def test_columnar_survives_fill_record_escalation():
    """An op crossing more resting orders than max_fills forces the per-lane
    escalation re-run; the columnar splice must carry the wider records."""
    from gome_tpu.types import Order, Side

    engine = BatchEngine(BookConfig(cap=32, max_fills=2), n_slots=2, max_t=32)
    orders = [
        Order(uuid="m", oid=f"a{i}", symbol="s", side=Side.SALE,
              price=100 + i, volume=5)
        for i in range(8)
    ] + [
        Order(uuid="t", oid="big", symbol="s", side=Side.BUY,
              price=200, volume=38)
    ]
    batch = engine.process_columnar(orders)
    events = batch.to_results()
    assert len(events) == 8  # all eight makers filled
    assert engine.stats.fill_record_escalations >= 1
    assert [e.match_node.oid for e in events] == [f"a{i}" for i in range(8)]
    # taker remainder after each fill decreases to 38 - 40 < 0 -> last fill
    # partial? 8x5 = 40 > 38: final maker partially filled
    assert events[-1].match_volume == 3


def test_columnar_two_lanes_escalate_with_different_budgets():
    """Two lanes escalating fill records in the same grid with DIFFERENT
    grown budgets K' (regression: the override splice assumed one width)."""
    from gome_tpu.types import Order, Side

    engine = BatchEngine(BookConfig(cap=64, max_fills=2), n_slots=2, max_t=64)
    orders = []
    # lane a: 17 resting makers, taker crosses all -> K' = 32
    orders += [
        Order(uuid="m", oid=f"a{i}", symbol="a", side=Side.SALE,
              price=100 + i, volume=2)
        for i in range(17)
    ]
    # lane b: 5 resting makers, taker crosses all -> K' = 8
    orders += [
        Order(uuid="m", oid=f"b{i}", symbol="b", side=Side.SALE,
              price=100 + i, volume=2)
        for i in range(5)
    ]
    orders.append(Order(uuid="t", oid="ta", symbol="a", side=Side.BUY,
                        price=200, volume=100))
    orders.append(Order(uuid="t", oid="tb", symbol="b", side=Side.BUY,
                        price=200, volume=100))

    col = BatchEngine(BookConfig(cap=64, max_fills=2), n_slots=2, max_t=64)
    obj_events = engine.process(orders)
    col_events = col.process_columnar(orders).to_results()
    assert col_events == obj_events
    assert sum(1 for e in obj_events if e.match_node.oid.startswith("a")) == 17
    assert sum(1 for e in obj_events if e.match_node.oid.startswith("b")) == 5


def test_json_lines_byte_parity_with_codec():
    orders = mixed_stream(n=120, seed=7, cancel_prob=0.3, market_prob=0.05)
    obj_engine, col_engine = _fresh_engines()
    obj_events = obj_engine.process(orders)
    batch = col_engine.process_columnar(orders)
    expected = [encode_match_result(e) for e in obj_events]
    assert batch.to_json_lines() == expected


def test_orchestrator_columnar_admission_parity():
    """MatchEngine.process_columnar applies the same pre-pool admission as
    process (ADD dropped when cancelled-before-consume)."""
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.types import Action, Order, Side

    mk = lambda: MatchEngine(BookConfig(cap=32, max_fills=4), n_slots=4)
    a, b = mk(), mk()
    orders = mixed_stream(n=120, seed=3, cancel_prob=0.2)
    for e in (a, b):
        for o in orders:
            e.mark(o)
    # cancel-before-consume: unmark one ADD before processing
    victim = next(o for o in orders if o.action is Action.ADD)
    for e in (a, b):
        e.pre_pool.discard((victim.symbol, victim.uuid, victim.oid))
    obj = a.process(orders)
    col = b.process_columnar(orders).to_results()
    assert obj == col
    assert a.stats.dropped_no_prepool == b.stats.dropped_no_prepool == 1


def test_empty_batch():
    engine = BatchEngine(BookConfig(cap=16, max_fills=4), n_slots=2)
    batch = engine.process_columnar([])
    assert len(batch) == 0
    assert batch.to_results() == []
    assert batch.to_json_lines() == []
