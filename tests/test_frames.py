"""Frame path (engine.frames + bus.colwire): wire codec round-trips and
differential parity — every way in (an encoded frame on the exact or the
compacted path, a list of Orders through the list forms) runs the one frame
packer and produces the oracle's events."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.bus import colwire
from gome_tpu.engine import BatchEngine, BookConfig
from gome_tpu.engine.frames import process_frame
from gome_tpu.oracle import OracleEngine
from gome_tpu.types import Action, Order, OrderType, Side
from gome_tpu.utils.streams import multi_symbol_stream


def orders_to_frame(orders):
    """Encode a list of Orders as one ORDER frame (what a batching gateway
    or the columnar load client produces) — the library implementation,
    re-exported under the name older tests import."""
    return colwire.encode_orders(orders)


def run_frames(eng, orders, chunk, fast=False):
    from gome_tpu.engine.frames import apply_frame_fast

    out = []
    for i in range(0, len(orders), chunk):
        payload = orders_to_frame(orders[i : i + chunk])
        assert colwire.is_frame(payload)
        cols = colwire.decode_order_frame(payload)
        run = (
            (lambda c: apply_frame_fast(eng, c))
            if fast
            else (lambda c: process_frame(eng, c))
        )
        out.extend(run(cols).to_results())
    return out


def run_lists(eng, orders, chunk):
    out = []
    for i in range(0, len(orders), chunk):
        out.extend(eng.process_columnar(orders[i : i + chunk]).to_results())
    return out


def _oracle(orders):
    oracle = OracleEngine()
    out = []
    for o in orders:
        out.extend(oracle.process(o))
    return out


@pytest.mark.parametrize(
    "n_slots,chunk,fast",
    [(64, 97, False), (8, 50, False), (64, 97, True), (8, 50, True)],
)
def test_frame_path_matches_list_form_and_oracle(n_slots, chunk, fast):
    orders = multi_symbol_stream(n=400, n_symbols=6, seed=21, cancel_prob=0.2)
    a = BatchEngine(BookConfig(cap=32, max_fills=8), n_slots=n_slots, max_t=8)
    b = BatchEngine(BookConfig(cap=32, max_fills=8), n_slots=n_slots, max_t=8)
    got_f = run_frames(a, orders, chunk, fast=fast)
    got_l = run_lists(b, orders, chunk)
    assert got_f == got_l == _oracle(orders)
    a.verify_books()
    _assert_same_books(a, b)


def _assert_same_books(a, b):
    """Two engines' books equal leaf for leaf. The oid/uid leaves hold
    interner ids, which depend on the order a dictionary lists its
    strings in (a producer's business): compared through the tables."""
    ba, bb = a.lane_books(), b.lane_books()
    for name in ("price", "lots", "seq", "count", "next_seq"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ba, name)), np.asarray(getattr(bb, name))
        )
    for leaf, ta, tb in (
        ("oid", a.oids.table, b.oids.table),
        ("uid", a.uids.table, b.uids.table),
    ):
        xa = np.asarray(getattr(ba, leaf), np.int64)
        xb = np.asarray(getattr(bb, leaf), np.int64)
        sa = np.array(ta, dtype=object)[xa]
        sb = np.array(tb, dtype=object)[xb]
        active = np.asarray(ba.lots) > 0
        assert (sa[active] == sb[active]).all(), leaf


# --- one way in: the list forms are conveniences over the frame path --------


def _wire_stream(traced):
    orders = multi_symbol_stream(n=120, n_symbols=5, seed=30, cancel_prob=0.2)
    orders.append(
        Order(uuid="ü", oid="mkt-é", symbol="s0", side=Side.BUY, price=0,
              volume=7, order_type=OrderType.MARKET)
    )
    if traced:
        orders = [
            dataclasses.replace(o, trace=f"{i:04x}@{i}.5") if i % 3 == 0
            else o
            for i, o in enumerate(orders)
        ]
    return orders


@pytest.mark.parametrize(
    "traced,digest",
    [
        (False,
         "8b72f572b1c18f982b567b71afb3e45492c43c842d1bf284301dc92730987d1f"),
        (True,
         "73c90a7e49fd0d058fccab6f80b1f618a148925c6a2221c41656ab30676c850f"),
    ],
)
def test_orders_to_cols_equals_decoded_frame(traced, digest):
    """orders_to_cols builds, without bytes, the dict decode_order_frame
    returns for the same orders — key for key, dtype for dtype — and
    encode_orders still writes the bytes it wrote before it was split
    (digests taken on the parent commit, 76cd419)."""
    orders = _wire_stream(traced)
    payload = colwire.encode_orders(orders)
    assert hashlib.sha256(payload).hexdigest() == digest
    want = colwire.decode_order_frame(payload)
    got = colwire.orders_to_cols(orders)
    assert list(got) == list(want)
    assert ("trace" in got) == traced
    for key, w in want.items():
        g = got[key]
        if isinstance(w, np.ndarray):
            assert isinstance(g, np.ndarray) and g.dtype == w.dtype, key
            np.testing.assert_array_equal(g, w)
        else:
            assert type(g) is type(w) and g == w, key
    empty = colwire.orders_to_cols([])
    back = colwire.decode_order_frame(colwire.encode_orders([]))
    assert empty["n"] == back["n"] == 0
    assert empty["oids"].dtype == back["oids"].dtype


_LIST_FORMS = {
    # name -> (engine class is MatchEngine?, chunk, call)
    "BatchEngine.process": (False, 97, lambda e, part: e.process(part)),
    "BatchEngine.process_columnar": (
        False, 97, lambda e, part: e.process_columnar(part).to_results()),
    "MatchEngine.process": (True, 97, lambda e, part: e.process(part)),
    "MatchEngine.process_columnar": (
        True, 97, lambda e, part: e.process_columnar(part).to_results()),
    "MatchEngine.process_one": (
        True, 1, lambda e, part: e.process_one(part[0])),
}


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int64], ids=["i32", "i64"])
@pytest.mark.parametrize("form", sorted(_LIST_FORMS))
def test_list_forms_are_the_frame_path(form, dtype, monkeypatch):
    """Every list-of-Order entry packs its batch with the frame packer
    (one pack_frame_grids call a batch) and gives the oracle's events and,
    leaf for leaf, the books of frames.process_frame on the encoded
    frames of the same stream."""
    from gome_tpu.engine import frames
    from gome_tpu.engine.orchestrator import MatchEngine

    is_match, chunk, call = _LIST_FORMS[form]
    orders = multi_symbol_stream(n=300, n_symbols=5, seed=17, cancel_prob=0.2)
    orders.append(
        Order(uuid="t", oid="mkt", symbol=orders[0].symbol, side=Side.BUY,
              price=0, volume=9, order_type=OrderType.MARKET)
    )
    cfg = BookConfig(cap=32, max_fills=8, dtype=dtype)
    ref = BatchEngine(cfg, n_slots=16, max_t=8)
    want = run_frames(ref, orders, chunk)
    assert want == _oracle(orders)

    packs = []
    real_pack = frames.pack_frame_grids

    def counting_pack(eng, a, **kw):
        packs.append(a["n"])
        return real_pack(eng, a, **kw)

    monkeypatch.setattr(frames, "pack_frame_grids", counting_pack)
    if is_match:
        eng = MatchEngine(config=cfg, n_slots=16, max_t=8)
        for o in orders:
            eng.mark(o)
        batch_eng = eng.batch
    else:
        eng = batch_eng = BatchEngine(cfg, n_slots=16, max_t=8)
    got = []
    for i in range(0, len(orders), chunk):
        got.extend(call(eng, orders[i : i + chunk]))
    assert got == want
    assert packs == [
        len(orders[i : i + chunk]) for i in range(0, len(orders), chunk)
    ]
    assert batch_eng.stats.orders == len(orders)
    batch_eng.verify_books()
    _assert_same_books(batch_eng, ref)


def test_list_form_failure_restores_marks():
    """A raised batch through a list form leaves the pre-pool and the books
    as they were before it: the at-least-once consumer replays a failed
    batch, and a replayed ADD must not die as unmarked because the failed
    attempt popped its key."""
    from gome_tpu.engine.orchestrator import MatchEngine

    orders = multi_symbol_stream(n=120, n_symbols=4, seed=3, cancel_prob=0.2)
    head, tail = orders[:60], orders[60:]
    eng = MatchEngine(
        config=BookConfig(cap=32, max_fills=8, dtype=jnp.int32),
        n_slots=8, max_t=8,
    )
    for o in orders:
        eng.mark(o)
    got = eng.process(head)
    poison = Order(uuid="p", oid="poison", symbol=tail[0].symbol,
                   side=Side.BUY, price=tail[0].price, volume=0)
    eng.mark(poison)
    marks = set(eng.pre_pool)
    books = eng.batch.export_state()["books"]
    stats = dataclasses.asdict(eng.stats)
    bad = tail[:30] + [poison] + tail[30:]
    for form in (eng.process_columnar, eng.process):
        with pytest.raises(ValueError, match="volume must be positive"):
            form(bad)
        assert set(eng.pre_pool) == marks
        after = eng.batch.export_state()["books"]
        for leaf, before in books.items():
            np.testing.assert_array_equal(after[leaf], before, leaf)
    assert dataclasses.asdict(eng.stats) == stats
    # The replay without the poison order is the stream the oracle saw.
    eng.unmark(poison)
    got += eng.process_columnar(tail).to_results()
    assert got == _oracle(orders)
    assert set(eng.pre_pool) == set()


def test_every_entry_dispatches_device_grids():
    """No public entry hands BatchEngine._step a grid built on the host:
    the frame packer scatters every grid on the device, so `_step`'s
    donate switch (numpy ops -> the donating twins) is never true. What
    ROADMAP C5 rests on."""
    import jax

    from gome_tpu.engine import frames
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.engine.pipeline import FramePipeline

    orders = multi_symbol_stream(n=240, n_symbols=6, seed=8, cancel_prob=0.2)
    # A sweep over more resting orders than cap and max_fills: the exact
    # path's escalation replays go through _step too.
    orders += [
        Order(uuid="u", oid=f"deep{i}", symbol="deep", side=Side.SALE,
              price=100 + i, volume=1)
        for i in range(24)
    ] + [Order(uuid="u", oid="sweep", symbol="deep", side=Side.BUY,
               price=300, volume=1000)]
    seen = {}

    def recording(eng, entry):
        real = eng._step

        def step(books, ops, *a, **k):
            seen.setdefault(entry, []).append(type(ops.action))
            return real(books, ops, *a, **k)

        eng._step = step

    def cols_of(part):
        return colwire.decode_order_frame(orders_to_frame(part))

    cfg = BookConfig(cap=8, max_fills=4)

    def mk_batch(part):
        return BatchEngine(cfg, n_slots=16, max_t=4)

    def mk_match(part):
        eng = MatchEngine(config=cfg, n_slots=16, max_t=4)
        for o in part:
            eng.mark(o)
        return eng

    def pipeline(e, part):
        pipe = FramePipeline(e, depth=2)
        for i in range(0, len(part), 50):
            pipe.feed(cols_of(part[i : i + 50]))
        pipe.flush()

    def precompile(e, part):
        e.process(part[:40])  # exact path: records no combo
        frames.apply_frame_fast(e, cols_of(part[40:90]))
        assert frames.precompile_combos(e, e.combos()) >= 1

    entries = {
        "BatchEngine.process": (mk_batch, lambda e, p: e.process(p)),
        "BatchEngine.process_columnar": (
            mk_batch, lambda e, p: e.process_columnar(p)),
        "frames.process_frame": (
            mk_batch, lambda e, p: frames.process_frame(e, cols_of(p))),
        "frames.apply_frame_fast": (
            mk_batch, lambda e, p: frames.apply_frame_fast(e, cols_of(p))),
        "frames.precompile_combos": (mk_batch, precompile),
        "MatchEngine.process": (mk_match, lambda e, p: e.process(p)),
        "MatchEngine.process_columnar": (
            mk_match, lambda e, p: e.process_columnar(p)),
        "MatchEngine.process_one": (
            mk_match, lambda e, p: [e.process_one(o) for o in p[:20]]),
        "MatchEngine.process_frame(fast=True)": (
            mk_match, lambda e, p: e.process_frame(cols_of(p), fast=True)),
        "MatchEngine.process_frame(fast=False)": (
            mk_match, lambda e, p: e.process_frame(cols_of(p), fast=False)),
        "FramePipeline": (mk_match, pipeline),
    }
    for entry, (make, run) in entries.items():
        eng = make(orders)
        recording(getattr(eng, "batch", eng), entry)
        run(eng, orders)
    assert set(seen) == set(entries)
    for entry, kinds in seen.items():
        assert kinds, entry
        assert all(issubclass(k, jax.Array) for k in kinds), (entry, kinds)
        assert not any(issubclass(k, np.ndarray) for k in kinds), entry


def test_frame_path_int32_rebasing_and_dropped_dels():
    BTC = 10_000_000_000_000
    rng = np.random.default_rng(5)
    orders = []
    for i in range(250):
        is_del = i > 20 and rng.random() < 0.2
        orders.append(
            Order(
                uuid=f"u{int(rng.integers(0, 3))}",
                oid=str(int(rng.integers(1, i)) if is_del else i),
                symbol=f"sym{int(rng.integers(0, 4))}",
                side=Side(int(rng.integers(0, 2))),
                price=BTC + int(rng.integers(-2000, 2000)),
                volume=int(rng.integers(1, 30)),
                action=Action.DEL if is_del else Action.ADD,
            )
        )
    # One in-contract wrong-price cancel (the poison scenario).
    orders.append(
        Order(uuid="u0", oid="0", symbol="sym0", side=Side.BUY,
              price=50_000_000, volume=0, action=Action.DEL)
    )
    eng = BatchEngine(
        BookConfig(cap=64, max_fills=8, dtype=jnp.int32), n_slots=64, max_t=8
    )
    got = run_frames(eng, orders, 80)
    assert got == _oracle(orders)
    assert eng.stats.cancels_missed >= 1
    eng.verify_books()


def test_fast_path_falls_back_on_escalation():
    """apply_frame_fast must detect tripped budgets (book overflow, record
    truncation) via the compaction totals and re-run exactly."""
    orders = [
        Order(uuid="u", oid=str(i), symbol="s", side=Side.SALE,
              price=100 + i, volume=1)
        for i in range(40)  # overflows cap=8
    ]
    orders.append(
        Order(uuid="u", oid="sweep", symbol="s", side=Side.BUY, price=300,
              volume=1000)  # 40 fills > max_fills=4
    )
    eng = BatchEngine(BookConfig(cap=8, max_fills=4), n_slots=16, max_t=4)
    got = run_frames(eng, orders, len(orders), fast=True)
    assert got == _oracle(orders)
    assert eng.stats.cap_escalations >= 1
    eng.verify_books()


def test_frame_path_deep_single_symbol_and_escalations():
    rng = np.random.default_rng(9)
    orders = [
        Order(uuid="u", oid=str(i), symbol="hot",
              side=Side(int(rng.integers(0, 2))),
              price=100 + int(rng.integers(-3, 4)),
              volume=int(rng.integers(1, 8)))
        for i in range(500)
    ]
    # sweep order crossing far more than max_fills resting orders
    orders.append(
        Order(uuid="u", oid="sweep", symbol="hot", side=Side.BUY,
              price=200, volume=100000)
    )
    eng = BatchEngine(BookConfig(cap=16, max_fills=4), n_slots=64, max_t=4)
    got = run_frames(eng, orders, len(orders))
    assert got == _oracle(orders)
    assert eng.stats.cap_escalations >= 1
    eng.verify_books()


def test_frame_market_orders():
    orders = [
        Order(uuid="m", oid="r1", symbol="s", side=Side.SALE, price=105,
              volume=10),
        Order(uuid="m", oid="r2", symbol="s", side=Side.SALE, price=110,
              volume=10),
        Order(uuid="t", oid="mkt", symbol="s", side=Side.BUY, price=0,
              volume=15, order_type=OrderType.MARKET),
    ]
    eng = BatchEngine(BookConfig(cap=16, max_fills=8), n_slots=16, max_t=8)
    got = run_frames(eng, orders, 3)
    assert got == _oracle(orders)
    assert [e.match_volume for e in got] == [10, 5]


def test_event_frame_round_trip():
    """EventBatch -> EVENT frame -> EventBatch: identical events and
    identical reference-JSON serialization."""
    orders = multi_symbol_stream(n=200, n_symbols=4, seed=2, cancel_prob=0.2)
    eng = BatchEngine(BookConfig(cap=32, max_fills=8), n_slots=32, max_t=8)
    batch = eng.process_columnar(orders)
    payload = colwire.encode_event_frame(batch)
    assert colwire.is_frame(payload)
    back = colwire.decode_event_frame(payload)
    assert back.to_results() == batch.to_results()
    assert back.to_json_lines() == batch.to_json_lines()


def test_service_frame_path_end_to_end():
    """ORDER frames through the real consumer (admission incl. the
    cancel-before-consume race) with EVENT-frame publishing, decoded by
    the match feed — parity with the oracle."""
    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.consumer import OrderConsumer
    from gome_tpu.service.matchfeed import MatchFeed

    orders = multi_symbol_stream(n=300, n_symbols=5, seed=13, cancel_prob=0.2)
    engine = MatchEngine(
        config=BookConfig(cap=32, max_fills=8), n_slots=64, max_t=8
    )
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=64, batch_wait_s=0, match_wire="frame"
    )
    feed = MatchFeed(bus, log_events=False)
    for o in orders:
        engine.mark(o)
    for i in range(0, len(orders), 70):
        bus.order_queue.publish(orders_to_frame(orders[i : i + 70]))
    n = consumer.drain()
    assert n == len(orders)
    # decode the EVENT frames back to MatchResults
    got = []
    from gome_tpu.bus.colwire import decode_event_frame

    for m in bus.match_queue.read_from(0, 10000):
        got.extend(decode_event_frame(m.body).to_results())
    assert got == _oracle(orders)
    feed.drain()
    assert feed.events_seen == len(got)


def test_frame_admission_cancel_race():
    """An ADD whose mark was cleared by an earlier cancel must drop at
    frame admission (engine.go:58-62 semantics)."""
    from gome_tpu.engine.orchestrator import MatchEngine

    engine = MatchEngine(
        config=BookConfig(cap=16, max_fills=4), n_slots=16, max_t=8
    )
    add = Order(uuid="u", oid="1", symbol="s", side=Side.BUY, price=100,
                volume=5)
    kill = Order(uuid="u", oid="1", symbol="s", side=Side.BUY, price=100,
                 volume=0, action=Action.DEL)
    engine.mark(add)
    # cancel consumed first clears the mark; the queued ADD then dies
    from gome_tpu.bus import colwire

    batch = engine.process_frame(
        colwire.decode_order_frame(orders_to_frame([kill, add]))
    )
    assert len(batch) == 0
    assert engine.stats.dropped_no_prepool == 1
    assert int(np.asarray(engine.books.count).sum()) == 0


def test_event_frame_non_ascii_ids():
    """UTF-8 ids survive both frame codecs (np 'S' conversion is
    ASCII-only on str inputs; the packers must encode first)."""
    orders = [
        Order(uuid="пользователь", oid="ордер-1", symbol="эфир2usdt",
              side=Side.SALE, price=100, volume=5),
        Order(uuid="用户", oid="订单-2", symbol="эфир2usdt",
              side=Side.BUY, price=100, volume=3),
    ]
    eng = BatchEngine(BookConfig(cap=16, max_fills=4), n_slots=16, max_t=4)
    batch = process_frame(
        eng, colwire.decode_order_frame(orders_to_frame(orders))
    )
    back = colwire.decode_event_frame(colwire.encode_event_frame(batch))
    assert back.to_results() == batch.to_results() == _oracle(orders)
    assert back.to_results()[0].match_node.uuid == "пользователь"


def test_order_frame_codec_edge_cases():
    # empty batch
    payload = orders_to_frame([])
    cols = colwire.decode_order_frame(payload)
    assert cols["n"] == 0
    # single order, long ids
    o = Order(uuid="user-" + "x" * 40, oid="order-" + "y" * 60,
              symbol="somesym2usdt", side=Side.BUY, price=123, volume=7)
    cols = colwire.decode_order_frame(orders_to_frame([o]))
    assert cols["symbols"] == ["somesym2usdt"]
    assert cols["uuids"][cols["uuid_idx"][0]] == o.uuid
    assert cols["oids"][0].decode() == o.oid
    assert cols["price"][0] == 123 and cols["volume"][0] == 7


def test_fast_path_cap_below_max_fills():
    """cap < max_fills clamps the step's record axis K to cap (step.py's
    `rec` slice) — the fast compact path must decode with the ARRAY K and
    escalate when an op's fills exceed it, not config.max_fills
    (fuzz-found: mis-decoded fill positions and silently truncated
    records). Exercised per-frame against the oracle."""
    import jax.numpy as jnp

    orders = []
    for i in range(12):
        orders.append(
            Order(uuid="u", oid=f"r{i}", symbol="s", side=Side.SALE,
                  price=100 + i, volume=2)
        )
    # Sweeps crossing more than cap resting orders: records must escalate
    # (n_fills > K=cap) and the decoded events must still be exact.
    orders.append(
        Order(uuid="u", oid="sweep", symbol="s", side=Side.BUY, price=200,
              volume=11)
    )
    orders += [
        Order(uuid="u", oid=f"p{i}", symbol="s2", side=Side(int(i % 2)),
              price=150 + (i % 2), volume=3)
        for i in range(8)
    ]
    eng = BatchEngine(
        BookConfig(cap=4, max_fills=8, dtype=jnp.int32), n_slots=2, max_t=8
    )
    got = run_frames(eng, orders, 7, fast=True)
    assert got == _oracle(orders)
    eng.verify_books()


def test_lane_growth_survives_rollback_retry():
    """A frame that (a) auto-grows the lane axis and (b) trips the fast
    path's fills-buffer budget must still succeed via the exact fallback:
    the rollback shrinks n_slots back, and the retry's lane map must
    re-grow rather than reuse cached lane ids past the restored stack
    (regression: the identity-cached lane map skipped _lane()'s growth
    side effect after _restore)."""
    from gome_tpu.engine.frames import apply_frame_fast

    eng = BatchEngine(
        BookConfig(cap=256, max_fills=256), n_slots=2, max_t=512
    )
    # Rest 200 one-lot asks on s0 (fills floor stays minimal: no fills).
    rest = [
        Order(
            uuid="u", oid=f"a{i}", symbol="s0", side=Side.SALE,
            price=1000, volume=1, action=Action.ADD,
            order_type=OrderType.LIMIT,
        )
        for i in range(200)
    ]
    cols = colwire.decode_order_frame(orders_to_frame(rest))
    apply_frame_fast(eng, cols)
    # One frame: a 200-lot sweep on s0 (200 fills >> the 64-slot fills
    # buffer for n_ops=4 -> _NeedExact -> rollback -> exact retry) PLUS
    # three new symbols that force lane growth 2 -> 8 in the same frame.
    sweep = [
        Order(
            uuid="u", oid="big", symbol="s0", side=Side.BUY,
            price=1000, volume=200, action=Action.ADD,
            order_type=OrderType.LIMIT,
        )
    ] + [
        Order(
            uuid="u", oid=f"n{i}", symbol=f"new{i}", side=Side.BUY,
            price=1000, volume=1, action=Action.ADD,
            order_type=OrderType.LIMIT,
        )
        for i in range(3)
    ]
    cols2 = colwire.decode_order_frame(orders_to_frame(sweep))
    batch = apply_frame_fast(eng, cols2)
    fills = [e for e in batch.to_results() if not e.is_cancel]
    assert len(fills) == 200
    assert eng.n_slots >= 4  # growth stuck after the retry
    assert eng.stats.fills == 200
    # The sweep grid's op class (64) ratcheted its fills floor past 200.
    assert eng.geometry_floors()["fills_buf"][64] == 256


def test_geometry_manifest_precompile_round_trip(tmp_path):
    """VERDICT r4 #1: a persisted shape manifest (floors + dispatched
    combos) replays in a FRESH engine with all-padding inputs, leaves its
    state untouched, and makes the live flow's shapes pre-seen — then the
    same orders produce identical events to an engine without any
    precompile."""
    from gome_tpu.engine.frames import precompile_combos
    from gome_tpu.engine.orchestrator import MatchEngine

    def mk():
        return MatchEngine(
            config=BookConfig(cap=32, max_fills=8, dtype=jnp.int64),
            n_slots=64, max_t=8,
        )

    orders = multi_symbol_stream(
        n=600, n_symbols=24, seed=5, zipf_a=1.2, cancel_prob=0.3
    )

    # Run 1: record the manifest.
    e1 = mk()
    for o in orders:
        e1.mark(o)
    frame = colwire.decode_order_frame(orders_to_frame(orders))
    ev1 = e1.process_frame(frame, fast=True).to_results()
    assert e1.batch.combo_count(), "fast path recorded no shape combos"
    path = str(tmp_path / "geometry.json")
    e1.save_geometry(path)

    # Run 2: fresh engine loads + precompiles, then must (a) be unchanged
    # by the replay and (b) produce identical events.
    e2 = mk()
    n = e2.load_geometry(path)
    assert n == e1.batch.combo_count()
    assert int(np.asarray(e2.books.count).sum()) == 0  # replay mutated nothing
    assert e2.batch.stats.orders == 0
    # Floors were prewarmed: the same flow chooses the recorded shapes.
    g1, g2 = e1.batch.geometry_floors(), e2.batch.geometry_floors()
    for k in ("rows_floor", "t_floor", "fills_buf", "cancels_buf"):
        for cls, v in g1[k].items():
            assert g2[k].get(cls, 0) >= v, (k, cls)
    for o in orders:
        e2.mark(o)
    ev2 = e2.process_frame(frame, fast=True).to_results()
    assert ev1 == ev2
    # The flow minted no shapes beyond the manifest (zero first-seen
    # traces in the "timed region").
    assert set(e2.batch.combos()) <= set(
        map(tuple, e1.batch.shape_manifest()["combos"])
    )

    # Missing/corrupt files are best-effort no-ops.
    e3 = mk()
    assert e3.load_geometry(str(tmp_path / "absent.json")) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert e3.load_geometry(str(bad)) == 0
    # Direct combo replay with a dense combo on a fresh engine also works.
    assert precompile_combos(e3.batch, e1.batch.shape_manifest()["combos"]) >= 1


def test_geometry_manifest_stale_or_oversized_is_best_effort(tmp_path):
    """A readable manifest that is incompatible (combo arity from another
    version) must be a no-op, not a boot crash; and a mesh request larger
    than the device pool raises loudly instead of silently shrinking."""
    import json

    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.parallel import make_mesh

    e = MatchEngine(
        config=BookConfig(cap=32, max_fills=8, dtype=jnp.int64),
        n_slots=64, max_t=8,
    )
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({
        "floors": {"rows_floor": {"32": 8}},
        "combos": [[8, 8, 32]],  # wrong arity: an older version's layout
    }))
    assert e.load_geometry(str(stale)) == 0  # best-effort, no raise

    with pytest.raises(ValueError, match="devices"):
        make_mesh(64)  # only 8 virtual devices exist


# --- the event buffers' life and the one-phase fetch (ISSUE 33) -------------


def _mesh(mesh_devices):
    if not mesh_devices:
        return None
    from gome_tpu.parallel import make_mesh

    return make_mesh(mesh_devices)


def _engine(mesh_devices, **kw):
    kw.setdefault("n_slots", 16)
    kw.setdefault("max_t", 8)
    return BatchEngine(BookConfig(cap=128, max_fills=8, dtype=jnp.int32),
                       mesh=_mesh(mesh_devices), **kw)


@pytest.fixture
def device_work(monkeypatch):
    """Two lists that fill while JAX works: the name of every primitive
    dispatched eagerly (apply_primitive asks xla_primitive_callable for its
    program at each call) and every lowering or backend compile
    (jax.monitoring; a retrace alone is not one)."""
    from jax._src import dispatch, monitoring

    eager, lowered = [], []
    real = dispatch.xla_primitive_callable

    def counting(prim, **params):
        eager.append(prim.name)
        return real(prim, **params)

    monkeypatch.setattr(dispatch, "xla_primitive_callable", counting)

    def on_event(name, _secs, **_kw):
        if name.endswith(("jaxpr_to_mlir_module_duration",
                          "backend_compile_duration")):
            lowered.append(name)

    monitoring.register_event_duration_secs_listener(on_event)
    yield eager, lowered
    monitoring.unregister_event_duration_listener(on_event)


@pytest.fixture
def device_calls(monkeypatch):
    """Two lists that fill with what the host asks of the device: the name
    of every program executed, jitted or eager (the C++ fast path of a
    jitted call is off meanwhile, so every execution passes
    ExecuteReplicated.__call__; a signature that took the fast path before
    the fixture stays unseen, so a test clears the caches of the programs
    it counts), and the number of arrays of every explicit device_put
    (jax.device_put, or the primitive bound eagerly; an eager jnp.asarray
    of a host array is one of the two)."""
    from jax._src import dispatch, pjit
    from jax._src.interpreters import pxla

    programs, puts = [], []
    real_call = pxla.ExecuteReplicated.__call__

    def counting_call(self, *args):
        programs.append(self.name)
        return real_call(self, *args)

    monkeypatch.setattr(pxla.ExecuteReplicated, "__call__", counting_call)
    monkeypatch.setattr(pjit, "_get_fastpath_data", lambda *a, **kw: None)
    real_impl, real_put = dispatch.device_put_p.impl, jax.device_put

    def counting_impl(*xs, **params):
        puts.append(len(xs))
        return real_impl(*xs, **params)

    def counting_put(x, *a, **kw):
        puts.append(len(jax.tree.leaves(x)))
        return real_put(x, *a, **kw)

    monkeypatch.setattr(dispatch.device_put_p, "impl", counting_impl)
    monkeypatch.setattr(jax, "device_put", counting_put)
    return programs, puts


@pytest.mark.parametrize("mesh_devices", [0, 4], ids=["one_chip", "mesh4"])
def test_steady_frames_make_no_eager_op_and_lower_nothing(
    mesh_devices, device_work, device_calls, monkeypatch
):
    """After warm-up a steady stream's frames cost the device its
    programs and the host one wait: no primitive is dispatched eagerly by
    submit_frame or resolve_frame (the parent made six a frame, the three
    jnp.zeros of the event buffers), nothing is lowered, every frame's
    buffers are an earlier frame's and every frame resolves in one phase,
    with one device_get (the parent made four). On one chip a frame of
    one grid executes ONE program and puts nothing on the device beside it
    (the parent executed four: scatter, step, compaction, the count
    reduction); under a mesh a small frame keeps the three calls and the
    placement of its grid (BatchEngine._step), as a large one."""
    from collections import deque

    from gome_tpu.engine import frames
    from gome_tpu.engine.frames import resolve_frame, submit_frame

    orders = multi_symbol_stream(
        n=60 * 34, n_symbols=12, seed=5, cancel_prob=0.3
    )
    eng = _engine(mesh_devices)
    eager, lowered = device_work
    programs, puts = device_calls
    frames._grid_program.clear_cache()
    fetches = []
    real_get = jax.device_get

    def counting_get(tree):
        fetches.append(len(tree))
        return real_get(tree)

    monkeypatch.setattr(jax, "device_get", counting_get)
    in_flight = deque()
    for k in range(34):
        if k == 14:  # warm: every shape met, three sets in rotation
            eager.clear(), lowered.clear(), fetches.clear()
            programs.clear(), puts.clear()
            before = dataclasses.replace(eng.stats)
        in_flight.append(
            submit_frame(eng, colwire.orders_to_cols(orders[k * 60:][:60]))
        )
        if len(in_flight) > 2:
            resolve_frame(eng, in_flight.popleft())
    assert eager == []
    assert lowered == []
    assert eng.stats.device_calls - before.device_calls == 20
    one_program = (eng.stats.fast_grids_one_program
                   - before.fast_grids_one_program)
    if mesh_devices:
        assert one_program == 0 and "jit(_grid_program)" not in programs
        assert programs.count("jit(compact_accum)") == 20
    else:
        assert one_program == 20
        assert programs == ["jit(_grid_program)"] * 20
        assert puts == []
    # One blocking fetch a frame: totals, both matrices and counts_max.
    assert fetches == [4] * 20
    frames = eng.stats.fast_frames - before.fast_frames
    assert frames == 20
    assert eng.stats.fast_frames_reused - before.fast_frames_reused == 20
    assert eng.stats.fast_frames_one_phase - before.fast_frames_one_phase == 20
    assert eng.stats.frame_fallbacks == before.frame_fallbacks
    # Depth 2: three sets of the one shape exist, one waits with the engine.
    assert [len(v) for v in eng._event_buffers.values()] == [1]
    while in_flight:
        resolve_frame(eng, in_flight.popleft())
    eng.verify_books()


@pytest.mark.parametrize("mesh_devices", [0, 4], ids=["one_chip", "mesh4"])
def test_one_and_two_phase_frames_equal_the_exact_path(
    mesh_devices, monkeypatch
):
    """One stream whose frames lie just under and just over the one-phase
    rule (64 kept ops: the two matrices hold exactly the rule's bytes;
    65: the next buffer class), two in flight: the same EventBatch
    columns, books and counters as process_frame, frame for frame."""
    from collections import deque

    from gome_tpu.engine import frames

    wide = 4  # int32 books
    small = (len(frames._FILL_FIELDS) + len(frames._CANCEL_FIELDS)) * 64 * wide
    monkeypatch.setattr(frames, "ONE_PHASE_MAX_BYTES", small)
    orders = multi_symbol_stream(
        n=129 * 8, n_symbols=12, seed=9, cancel_prob=0.25
    )
    chunks, i = [], 0
    while i < len(orders):
        n = 64 if len(chunks) % 2 == 0 else 65
        chunks.append(orders[i : i + n])
        i += n
    fast, exact = _engine(mesh_devices), _engine(mesh_devices)
    in_flight, got, phases = deque(), [], []
    for chunk in chunks + [None, None]:
        if chunk is not None:
            pend = frames.submit_frame(fast, colwire.orders_to_cols(chunk))
            phases.append(pend.one_phase)
            in_flight.append(pend)
        if len(in_flight) > 2 or (chunk is None and in_flight):
            got.append(frames.resolve_frame(fast, in_flight.popleft()))
    want = [process_frame(exact, colwire.orders_to_cols(c)) for c in chunks]
    assert phases[:4] == [True, False, True, False]
    assert 0 < fast.stats.fast_frames_one_phase < fast.stats.fast_frames
    assert fast.stats.fast_frames_reused > 0
    assert fast.stats.frame_fallbacks == 0
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.columns.keys() == w.columns.keys()
        for name in w.columns:
            np.testing.assert_array_equal(g.columns[name], w.columns[name])
    for name in ("orders", "fills", "cancels", "cancels_missed"):
        assert getattr(fast.stats, name) == getattr(exact.stats, name), name
    fast.verify_books()
    _assert_same_books(fast, exact)


def test_precompiled_manifest_leaves_a_live_run_nothing_to_lower(
    tmp_path, monkeypatch, device_work
):
    """precompile_combos lowers exactly what a live frame dispatches: a
    fresh engine that loaded a recorded manifest runs the recorded flow,
    cold buffers and reused ones, one-phase frames and two-phase ones,
    with 0 lowerings and 0 backend compiles."""
    from gome_tpu.engine import frames
    from gome_tpu.engine.orchestrator import MatchEngine

    monkeypatch.setattr(frames, "ONE_PHASE_MAX_BYTES", 9 * 64 * 8)

    def mk():
        return MatchEngine(
            config=BookConfig(cap=32, max_fills=8, dtype=jnp.int64),
            n_slots=64, max_t=8,
        )

    orders = multi_symbol_stream(
        n=1200, n_symbols=24, seed=5, zipf_a=1.2, cancel_prob=0.3
    )
    # Small frames and large ones, so both fetches and several buffer
    # shapes are in the manifest; each size three times, so sets are
    # reused.
    sizes = [60, 60, 60, 60, 300, 300, 300, 60]
    chunks, i = [], 0
    for n in sizes:
        chunks.append(colwire.orders_to_cols(orders[i : i + n]))
        i += n

    def run(engine):
        for o in orders[:i]:
            engine.mark(o)
        return [
            engine.process_frame(c, fast=True).to_results() for c in chunks
        ]

    e1 = mk()
    ev1 = run(e1)
    assert 0 < e1.stats.fast_frames_one_phase < e1.stats.fast_frames
    path = str(tmp_path / "geometry.json")
    e1.save_geometry(path)

    e2 = mk()
    assert e2.load_geometry(path) == e1.batch.combo_count()
    _eager, lowered = device_work
    lowered.clear()
    ev2 = run(e2)
    assert lowered == []
    assert ev2 == ev1
    assert e2.stats.fast_frames_reused > 0
    assert e2.stats.frame_fallbacks == e1.stats.frame_fallbacks


# --- a small frame's grid is one program (ISSUE 35) -------------------------


def _hot_and_tail_frames(n_frames, seed=3):
    """Frames of 56 to 60 orders for a venue of two cap classes: a listing rests
    80 bids in `hot` (over the 64-slot class), then every frame adds four
    bids there and cancels the four of the frame before, beside 52 orders
    over ten tail symbols. With cap 256 and n_slots 64 each frame packs two
    dense grids: the tail's at class 64 and hot's at class 256."""
    def hot(oid, price, action=Action.ADD):
        return Order(uuid="h", oid=oid, symbol="hot", side=Side.BUY,
                     price=price, volume=5, action=action)

    tail = multi_symbol_stream(
        n=52 * n_frames, n_symbols=10, seed=seed, cancel_prob=0.3
    )
    out = [[hot(f"l{i}", 50_000_000 - i) for i in range(80)]]
    for k in range(n_frames):
        frame = [hot(f"f{k}.{j}", 40_000_000 - j) for j in range(4)]
        if k:
            frame += [hot(f"f{k - 1}.{j}", 40_000_000 - j, Action.DEL)
                      for j in range(4)]
        out.append(frame + tail[52 * k:][:52])
    return out


@pytest.mark.parametrize("mesh_devices", [0, 4], ids=["one_chip", "mesh4"])
@pytest.mark.parametrize("size", ["small", "large"])
def test_a_frame_of_two_classes_is_one_grid_when_small_and_two_when_large(
    size, mesh_devices, device_work, device_calls, monkeypatch
):
    """Lanes of the 64-slot and of the 256-slot class in every frame. A
    SMALL frame (under the one-phase rule) packs them as one dense grid at
    class 256 (ISSUE 44): after warm-up submit_frame executes ONE program a
    frame and puts nothing on the device beside it, eager or explicit
    (until ISSUE 44 one program a class, two a frame; until ISSUE 35 seven
    programs a frame and an upload of each grid's lane ids). Under a mesh
    the one grid keeps a large frame's calls: scatter, the sharded step
    behind its shard_put, compaction. A LARGE frame (the same frames with
    the rule set under them) packs one grid a class as it always did, three
    calls each, and counts as neither one-phase nor merged."""
    from gome_tpu.engine import frames

    if size == "large":
        monkeypatch.setattr(frames, "ONE_PHASE_MAX_BYTES", 0)
    per_frame = 1 if size == "small" else 2
    chunks = _hot_and_tail_frames(30)
    eng = BatchEngine(
        BookConfig(cap=256, max_fills=8, dtype=jnp.int32),
        n_slots=64, max_t=16, mesh=_mesh(mesh_devices),
    )
    eager, lowered = device_work
    programs, puts = device_calls
    frames._grid_program.clear_cache()
    for k, chunk in enumerate(chunks):
        if k == 12:  # warm: the floors settled, sets in rotation
            eager.clear(), lowered.clear(), programs.clear(), puts.clear()
            before = dataclasses.replace(
                eng.stats, grids_by_kernel=dict(eng.stats.grids_by_kernel)
            )
        pend = frames.submit_frame(eng, colwire.orders_to_cols(chunk))
        assert len(pend.items) == (per_frame if k else 1)  # k 0: the listing
        seen = len(programs)
        frames.resolve_frame(eng, pend)
        if size == "small":
            assert len(programs) == seen  # resolve executes nothing
    n = len(chunks) - 12
    assert lowered == []
    assert eng.stats.device_calls - before.device_calls == per_frame * n
    one_program = (eng.stats.fast_grids_one_program
                   - before.fast_grids_one_program)
    merged = eng.stats.fast_frames_merged - before.fast_frames_merged
    if size == "large":
        assert one_program == 0 and merged == 0
        assert eng.stats.fast_frames_one_phase == 0
        assert "jit(_grid_program)" not in programs
        assert programs.count("jit(scatter)") == 2 * n
        assert programs.count("jit(compact_accum)") == 2 * n
    elif mesh_devices:
        assert eager == [] and merged == n
        assert one_program == 0 and "jit(_grid_program)" not in programs
        assert programs.count("jit(scatter)") == n
        assert programs.count("jit(compact_accum)") == n
        assert len(puts) == 2 * n  # the grid's ids and its ops, placed
    else:
        assert eager == [] and merged == n
        assert one_program == n
        assert programs == ["jit(_grid_program)"] * n
        assert puts == []
    assert (eng.stats.grids_by_kernel["scan_dense"]
            - before.grids_by_kernel["scan_dense"]) == per_frame * n
    assert eng.stats.frame_fallbacks == 0
    eng.verify_books()


#: The Pallas kernel's code path where no compiled kernel runs (the
#: interpreter): the step bodies the chip's programs hold.
_INTERPRET = dict(kernel="pallas", pallas_interpret=True)


def _case_under_and_over_the_rule():
    """Full grids, frames of 64 and 65 kept ops: with the rule at a
    64-wide buffer pair's bytes they alternate between one program a grid
    and three calls."""
    orders = multi_symbol_stream(
        n=129 * 4, n_symbols=12, seed=11, cancel_prob=0.25
    )
    chunks, i = [], 0
    while i < len(orders):
        n = 64 if len(chunks) % 2 == 0 else 65
        chunks.append(orders[i : i + n])
        i += n
    rule = (7 + 2) * 64 * 4
    return dict(cap=128, n_slots=16, max_t=8), chunks, rule


def _case_dense_grid():
    orders = multi_symbol_stream(
        n=60 * 8, n_symbols=6, seed=12, cancel_prob=0.3
    )
    chunks = [orders[i : i + 60] for i in range(0, len(orders), 60)]
    return dict(cap=64, n_slots=64, max_t=16, **_INTERPRET), chunks, None


def _case_two_classes():
    return dict(cap=256, n_slots=64, max_t=16), _hot_and_tail_frames(8), None


def _case_order_kinds():
    from test_order_kinds import tif_flow

    orders = tif_flow(seed=7, n=480, n_symbols=3)
    chunks = [orders[i : i + 60] for i in range(0, len(orders), 60)]
    return dict(cap=128, n_slots=32, max_t=8, **_INTERPRET), chunks, None


def _case_fills_overflow_the_buffer():
    """Twice: 16 asks of one lot rest in each of five symbols, then two
    bids a symbol take eight each: 80 fills from a frame of 10 kept ops,
    whose buffer class holds 64. The first such frame trips, rewinds to
    the exact path and raises the class's floor; the second fits."""
    def order(oid, sym, side, volume):
        return Order(uuid="u", oid=oid, symbol=f"s{sym}", side=side,
                     price=1_000, volume=volume)

    chunks = []
    for r in range(2):
        chunks.append([order(f"a{r}.{s}.{i}", s, Side.SALE, 1)
                       for s in range(5) for i in range(16)])
        chunks.append([order(f"b{r}.{s}.{i}", s, Side.BUY, 8)
                       for s in range(5) for i in range(2)])
    return dict(cap=128, n_slots=16, max_t=8), chunks, None


_ONE_PROGRAM_CASES = {
    "under_and_over_the_rule": _case_under_and_over_the_rule,
    "dense_grid": _case_dense_grid,
    "two_classes": _case_two_classes,
    "order_kinds": _case_order_kinds,
    "fills_overflow_the_buffer": _case_fills_overflow_the_buffer,
}


@pytest.mark.parametrize("mesh_devices", [0, 4], ids=["one_chip", "mesh4"])
@pytest.mark.parametrize("case", sorted(_ONE_PROGRAM_CASES))
def test_one_program_frames_equal_the_exact_path(
    case, mesh_devices, monkeypatch
):
    """A small frame's grids run as one program each (frames._grid_program;
    under a mesh as three calls) and a large frame's as three calls:
    either way the same EventBatch
    columns, books, EngineStats (kinds and expiries included) and grids by
    kernel as process_frame, frame for frame; a frame that trips is re-run
    on the exact path and costs its grids once more."""
    from gome_tpu.engine import frames

    kw, chunks, rule = _ONE_PROGRAM_CASES[case]()
    if rule is not None:
        monkeypatch.setattr(frames, "ONE_PHASE_MAX_BYTES", rule)

    def mk(kw):
        cfg = BookConfig(cap=kw.pop("cap"), max_fills=8, dtype=jnp.int32)
        return BatchEngine(cfg, mesh=_mesh(mesh_devices), **kw)

    fast, exact = mk(dict(kw)), mk(dict(kw))
    for k, chunk in enumerate(chunks):
        got = frames.apply_frame_fast(fast, colwire.orders_to_cols(chunk))
        want = process_frame(exact, colwire.orders_to_cols(chunk))
        assert got.columns.keys() == want.columns.keys()
        for name in want.columns:
            np.testing.assert_array_equal(
                got.columns[name], want.columns[name], err_msg=f"{k} {name}"
            )
    for name in ("orders", "fills", "cancels", "cancels_missed",
                 "adds_by_kind", "expired_ioc", "fok_killed",
                 "post_only_blocked", "scan_giveways"):
        assert getattr(fast.stats, name) == getattr(exact.stats, name), name
    assert fast.stats.fast_frames == len(chunks)
    if mesh_devices:  # a mesh places every grid through BatchEngine._step
        assert fast.stats.fast_grids_one_program == 0
    # Grids by the kernel that ran them: process_frame's, and a tripped
    # frame's once more (its one grid, dispatched and then re-run exactly).
    tripped = int(case == "fills_overflow_the_buffer")
    assert fast.stats.frame_fallbacks == tripped
    # (A small frame of two classes is one grid where process_frame packs
    # one a class: every frame of that case but the listing.)
    merged = len(chunks) - 1 if case == "two_classes" else 0
    assert fast.stats.fast_frames_merged == merged
    for name in ("grids_by_kernel", "ops_by_kernel"):
        ours, theirs = getattr(fast.stats, name), getattr(exact.stats, name)
        assert ours.keys() == theirs.keys(), name
        if not tripped and not (merged and name == "grids_by_kernel"):
            assert ours == theirs, name
    assert (sum(fast.stats.grids_by_kernel.values())
            == sum(exact.stats.grids_by_kernel.values()) + tripped - merged)
    if case == "under_and_over_the_rule":
        assert (0 < fast.stats.fast_frames_one_phase
                < fast.stats.fast_frames)
        if not mesh_devices:
            assert (0 < fast.stats.fast_grids_one_program
                    < fast.stats.device_calls)
    elif not mesh_devices:
        assert (fast.stats.fast_grids_one_program
                == fast.stats.device_calls - tripped)  # the exact re-run's
    if case == "two_classes":
        assert fast.stats.device_calls == len(chunks)
    if "kernel" in kw and not mesh_devices:
        assert all(k.startswith("interpret_")
                   for k in fast.stats.grids_by_kernel)
    if case == "order_kinds":
        assert set(fast.stats.adds_by_kind) == {0, 1, 3, 4, 6}
        assert min(fast.stats.expired_ioc, fast.stats.fok_killed,
                   fast.stats.post_only_blocked) > 0
    if tripped:
        assert fast.geometry_floors()["fills_buf"][64] == 128
    fast.verify_books()
    _assert_same_books(fast, exact)


@pytest.mark.parametrize("kernel", ["scan", "interpret"])
def test_precompile_replays_one_program_under_the_rule_and_three_calls_over_it(
    kernel, device_work, device_calls
):
    """A manifest with combos on both sides of the one-phase rule, two
    60-order frames' under it and a 1,100-order frame's over it: precompile_combos executes the
    one program for each of the first and scatter, step and compaction for
    the last, and the live frames after it lower nothing, on fresh buffers
    and on reused ones."""
    from gome_tpu.engine import frames

    orders = multi_symbol_stream(
        n=2320, n_symbols=12, seed=21, cancel_prob=0.3
    )
    chunks = [orders[:60], orders[60:1160], orders[1160:1220], orders[1220:]]

    def mk():
        return BatchEngine(
            BookConfig(cap=64, max_fills=8, dtype=jnp.int32), n_slots=16,
            max_t=8, **(_INTERPRET if kernel == "interpret" else {}),
        )

    def run(eng):
        return [frames.apply_frame_fast(eng, colwire.orders_to_cols(c))
                for c in chunks]

    first = mk()
    want = run(first)
    manifest = first.shape_manifest()
    small = [frames._one_phase(4, c[6], c[7]) for c in manifest["combos"]]
    # (the second small frame meets the depth floor the large one raised)
    assert small == [True, True, False]
    assert first.stats.fast_grids_one_program == 2 < first.stats.device_calls

    eng = mk()
    eng.prewarm_geometry(**{
        k: v for k, v in manifest["floors"].items() if k != "cap"
    })
    _eager, lowered = device_work
    programs, _puts = device_calls
    for fn in (frames._grid_program, frames.compact_accum):
        fn.clear_cache()
    programs.clear()
    assert frames.precompile_combos(eng, manifest["combos"]) == 3
    # (after them the count reduction and the large frame's prefix slices)
    assert programs[:2] == ["jit(_grid_program)"] * 2
    assert programs[2] == "jit(scatter)"  # then the large combo's step
    assert programs[4] == "jit(compact_accum)"
    assert "jit(_grid_program)" not in programs[2:]
    assert eng.stats.grids_by_kernel == {}  # a replay counts no grid
    lowered.clear()
    got = run(eng)
    assert lowered == []
    assert eng.stats.fast_frames_reused > 0
    for g, w in zip(got, want):
        for name in w.columns:
            np.testing.assert_array_equal(g.columns[name], w.columns[name])


def test_the_one_program_counter_is_on_metrics():
    """gome_fast_grids_one_program_total over gome_device_calls_total: 1
    after small frames, under 1 once a large one went the three calls."""
    from gome_tpu.engine import frames
    from gome_tpu.utils.metrics import REGISTRY

    eng = _engine(0)
    frames.export_metrics(eng)
    orders = multi_symbol_stream(n=1220, n_symbols=12, seed=4)
    for chunk in (orders[:60], orders[60:120], orders[120:]):
        frames.apply_frame_fast(eng, colwire.orders_to_cols(chunk))
    text = REGISTRY.render()
    assert "gome_fast_grids_one_program_total 2" in text
    assert f"gome_device_calls_total {eng.stats.device_calls}" in text
    assert eng.stats.device_calls > 2
