"""Cross-frame pipelining (engine.pipeline.FramePipeline + the consumer's
pipeline_depth): the pipelined executor must produce the IDENTICAL event
stream and book state as the synchronous frame path, including through
budget escalations mid-pipeline, hard failures (at-least-once replay with
pre-pool-mark restoration), and publish failures of resolved frames."""

import dataclasses
import threading
import time

import numpy as np
import pytest

from gome_tpu.bus import MemoryQueue, QueueBus
from gome_tpu.engine import frames as engine_frames
from gome_tpu.engine.book import BookConfig
from gome_tpu.engine.orchestrator import MatchEngine
from gome_tpu.engine.pipeline import FramePipeline
from gome_tpu.oracle import OracleEngine
from gome_tpu.service.consumer import OrderConsumer
from gome_tpu.types import Order, Side
from gome_tpu.utils.streams import multi_symbol_stream

from test_frames import orders_to_frame


def _frames_for(orders, chunk):
    from gome_tpu.bus import colwire

    payloads = []
    for i in range(0, len(orders), chunk):
        payloads.append(orders_to_frame(orders[i : i + chunk]))
        assert colwire.is_frame(payloads[-1])
    return payloads


def _make(engine_kw, depth):
    engine = MatchEngine(**engine_kw)
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=4, batch_wait_s=0, match_wire="json",
        pipeline_depth=depth,
    )
    return engine, bus, consumer


def _run(engine_kw, orders, chunk, depth):
    engine, bus, consumer = _make(engine_kw, depth)
    for o in orders:
        engine.mark(o)
    for p in _frames_for(orders, chunk):
        bus.order_queue.publish(p)
    n = consumer.drain()
    msgs = bus.match_queue.read_from(0, 1 << 20)
    return engine, n, [m.body for m in msgs]


def _assert_books_equal(a: MatchEngine, b: MatchEngine):
    ba, bb = a.batch.lane_books(), b.batch.lane_books()
    for name in ("price", "lots", "seq", "count", "next_seq"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ba, name)), np.asarray(getattr(bb, name))
        )
    assert a.pre_pool == b.pre_pool


def _oracle_lines(orders):
    # The consumer stamps every published event with the matchfeed seq
    # (ISSUE 11 exactly-once), so the expected wire carries the same
    # contiguous "Seq" fields the reference-shaped body lacks.
    from dataclasses import replace

    from gome_tpu.bus import encode_match_result

    oracle = OracleEngine()
    out = []
    for o in orders:
        for r in oracle.process(o):
            out.append(encode_match_result(replace(r, seq=len(out))))
    return out


ENGINE_KW = dict(
    config=BookConfig(cap=32, max_fills=8), n_slots=16, max_t=8
)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_pipelined_consumer_matches_synchronous(depth):
    orders = multi_symbol_stream(n=300, n_symbols=5, seed=11, cancel_prob=0.2)
    sync_eng, n_sync, sync_events = _run(ENGINE_KW, orders, 40, 0)
    pipe_eng, n_pipe, pipe_events = _run(ENGINE_KW, orders, 40, depth)
    assert n_pipe == n_sync == len(orders)
    assert pipe_events == sync_events == _oracle_lines(orders)
    _assert_books_equal(pipe_eng, sync_eng)
    pipe_eng.batch.verify_books()


def test_pipelined_escalation_mid_pipeline():
    """A frame in the middle of the in-flight span trips device budgets
    (book overflow + record truncation): the pipeline must rewind, re-run
    exactly, resubmit the later frames, and still match the oracle."""
    orders = [
        Order(uuid="u", oid=str(i), symbol="s", side=Side.SALE,
              price=100 + i, volume=1)
        for i in range(40)  # overflows cap=8
    ]
    orders.append(
        Order(uuid="u", oid="sweep", symbol="s", side=Side.BUY, price=300,
              volume=1000)  # 40 fills > max_fills=4
    )
    orders += [
        Order(uuid="u", oid=f"post{i}", symbol="s2",
              side=Side(int(i % 2)), price=200 + (i % 3), volume=2)
        for i in range(30)
    ]
    kw = dict(config=BookConfig(cap=8, max_fills=4), n_slots=8, max_t=4)
    sync_eng, _, sync_events = _run(kw, orders, 10, 0)
    pipe_eng, _, pipe_events = _run(kw, orders, 10, 3)
    assert pipe_events == sync_events == _oracle_lines(orders)
    assert pipe_eng.stats.cap_escalations >= 1
    _assert_books_equal(pipe_eng, sync_eng)
    pipe_eng.batch.verify_books()


def test_pipeline_hard_failure_restores_marks_and_replays(monkeypatch):
    """A hard failure at resolve time must leave no trace: books rewound to
    the failed frame's checkpoint, its and every later in-flight frame's
    pre-pool marks restored — so the consumer's at-least-once replay from
    the uncommitted offset converges to the synchronous result."""
    orders = multi_symbol_stream(n=200, n_symbols=4, seed=3, cancel_prob=0.15)
    sync_eng, _, sync_events = _run(ENGINE_KW, orders, 25, 0)

    engine, bus, consumer = _make(ENGINE_KW, 2)
    for o in orders:
        engine.mark(o)
    for p in _frames_for(orders, 25):
        bus.order_queue.publish(p)

    real = engine_frames.resolve_frame
    fail = {"left": 2}

    def flaky(eng, pend):
        if fail["left"] > 0:
            fail["left"] -= 1
            raise RuntimeError("injected resolve failure")
        return real(eng, pend)

    monkeypatch.setattr(engine_frames, "resolve_frame", flaky)
    total = 0
    end = bus.order_queue.end_offset()
    for _ in range(200):
        total += consumer.step_with_policy()
        if bus.order_queue.committed() >= end:
            break
    assert bus.order_queue.committed() == end
    assert total == len(orders)
    msgs = bus.match_queue.read_from(0, 1 << 20)
    assert [m.body for m in msgs] == sync_events
    _assert_books_equal(engine, sync_eng)
    engine.batch.verify_books()


def test_pipeline_submit_failure_restores_own_marks(monkeypatch):
    """feed() failing at submit must restore THAT frame's consumed marks and
    leave earlier in-flight frames untouched."""
    orders = multi_symbol_stream(n=60, n_symbols=3, seed=7, cancel_prob=0.1)
    engine = MatchEngine(**ENGINE_KW)
    for o in orders:
        engine.mark(o)
    pipe = FramePipeline(engine, depth=4)
    from gome_tpu.bus import colwire

    payloads = _frames_for(orders, 20)
    cols0 = colwire.decode_order_frame(payloads[0])
    pipe.feed(cols0, token=0)
    marks_after_first = set(engine.pre_pool)

    def boom(eng, cols):
        raise RuntimeError("injected submit failure")

    monkeypatch.setattr(engine_frames, "submit_frame", boom)
    cols1 = colwire.decode_order_frame(payloads[1])
    with pytest.raises(RuntimeError):
        pipe.feed(cols1, token=1)
    # Frame 1's marks restored; frame 0 still in flight with its marks
    # consumed.
    assert engine.pre_pool == marks_after_first
    assert len(pipe) == 1


def test_pipeline_abort_restores_in_flight_span():
    orders = multi_symbol_stream(n=80, n_symbols=3, seed=9, cancel_prob=0.1)
    engine = MatchEngine(**ENGINE_KW)
    for o in orders:
        engine.mark(o)
    marks0 = set(engine.pre_pool)
    pipe = FramePipeline(engine, depth=8)
    from gome_tpu.bus import colwire

    for i, p in enumerate(_frames_for(orders, 20)):
        pipe.feed(colwire.decode_order_frame(p), token=i)
    assert len(pipe) == 4
    pipe.abort()
    assert len(pipe) == 0
    assert engine.pre_pool == marks0
    ref = MatchEngine(**ENGINE_KW)
    for o in orders:
        ref.mark(o)
    _assert_books_equal(engine, ref)


def test_pipelined_publish_failure_aborts_and_replays():
    """The match queue failing while a resolved frame publishes must not
    wedge the consumer: the in-flight span aborts (marks restored) and the
    replay converges. Events of the frame whose publish failed are lost —
    the same window the synchronous path has (publish-after-process)."""

    class FlakyQueue(MemoryQueue):
        def __init__(self, name):
            super().__init__(name)
            self.fail_left = 1

        def publish_batch(self, bodies):
            if self.fail_left > 0 and bodies:
                self.fail_left -= 1
                raise RuntimeError("injected publish failure")
            return super().publish_batch(bodies)

    orders = multi_symbol_stream(n=150, n_symbols=4, seed=5, cancel_prob=0.1)
    engine = MatchEngine(**ENGINE_KW)
    bus = QueueBus(MemoryQueue("doOrder"), FlakyQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=4, batch_wait_s=0, match_wire="json",
        pipeline_depth=2,
    )
    for o in orders:
        engine.mark(o)
    for p in _frames_for(orders, 30):
        bus.order_queue.publish(p)
    end = bus.order_queue.end_offset()
    for _ in range(200):
        consumer.step_with_policy()
        if bus.order_queue.committed() >= end:
            break
    assert bus.order_queue.committed() == end
    engine.batch.verify_books()
    # Books equal the synchronous end state (the failed frame WAS applied;
    # only its events were lost to the failed publish).
    sync_eng, _, _ = _run(ENGINE_KW, orders, 30, 0)
    ba, bb = engine.batch.lane_books(), sync_eng.batch.lane_books()
    for name in ("price", "lots", "count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ba, name)), np.asarray(getattr(bb, name))
        )


def test_checkpoint_restorable_twice_after_interim_mutation():
    """FramePipeline's recovery restores the SAME checkpoint twice with an
    exact re-run mutating host rebasing state in between — the second
    restore must return the pristine snapshot, not the interim mutations
    (i.e. _restore must copy, never alias, the mutable arrays)."""
    import jax.numpy as jnp

    from gome_tpu.engine import BatchEngine

    BTC = 10_000_000_000_000
    eng = BatchEngine(
        BookConfig(cap=8, max_fills=4, dtype=jnp.int32), n_slots=4, max_t=4
    )
    cp = eng._checkpoint()
    base0 = eng.price_base.copy()
    set0 = eng._base_set.copy()
    eng._restore(cp)
    # Interim work (the exact re-run) rebases a lane in place.
    eng.process([
        Order(uuid="u", oid="1", symbol="btc", side=Side.BUY, price=BTC,
              volume=5)
    ])
    assert eng._base_set.any()
    eng._restore(cp)  # second restore of the SAME checkpoint
    np.testing.assert_array_equal(eng.price_base, base0)
    np.testing.assert_array_equal(eng._base_set, set0)


def test_pipelined_persist_hooks_fire_per_frame_with_frames_in_flight():
    """The persist hooks no longer wait for an empty pipeline: on_dispatch
    follows every frame's dispatch with the offset its commit will reach and
    the frames in flight (that one among them), and on_batch follows every
    frame's commit, in order, whatever is still in flight
    (tests/test_durable_cut.py holds what the Persister does with them)."""
    orders = multi_symbol_stream(n=200, n_symbols=4, seed=17, cancel_prob=0.1)
    engine = MatchEngine(**ENGINE_KW)
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    calls, dispatched = [], []
    consumer = OrderConsumer(
        engine, bus, batch_n=4, batch_wait_s=0, match_wire="json",
        pipeline_depth=2,
        on_batch=lambda n, e: calls.append(
            (n, e, len(consumer._pipe), bus.order_queue.committed())
        ),
        on_dispatch=lambda end, in_flight: dispatched.append(
            (end, in_flight, bus.order_queue.committed())
        ),
    )
    for o in orders:
        engine.mark(o)
    for p in _frames_for(orders, 25):
        bus.order_queue.publish(p)
    n = consumer.drain()
    assert n == len(orders)
    assert [c[0] for c in calls] == [25] * 8
    assert [c[3] for c in calls] == list(range(1, 9))  # one commit a frame
    assert max(c[2] for c in calls) == 2  # with frames still in flight
    assert [d[0] for d in dispatched] == list(range(1, 9))
    # in flight = dispatched and not committed, the frame itself among them
    assert all(end - committed == in_flight
               for end, in_flight, committed in dispatched)
    assert max(d[1] for d in dispatched) == 3  # depth 2, before the resolve


def test_pipeline_mixed_json_and_frames():
    """JSON messages interleaved with ORDER frames drain the pipeline first
    — global order preserved."""
    from gome_tpu.bus import encode_order

    orders = multi_symbol_stream(n=120, n_symbols=4, seed=13, cancel_prob=0.15)
    sync_eng, _, sync_events = _run(ENGINE_KW, orders, 24, 0)

    engine, bus, consumer = _make(ENGINE_KW, 2)
    for o in orders:
        engine.mark(o)
    # Frames for the first 96 orders, JSON for the rest, then one more frame.
    head, mid, tail = orders[:72], orders[72:96], orders[96:]
    for p in _frames_for(head, 24):
        bus.order_queue.publish(p)
    for o in mid:
        bus.order_queue.publish(encode_order(o))
    for p in _frames_for(tail, 24):
        bus.order_queue.publish(p)
    n = consumer.drain()
    assert n == len(orders)
    msgs = bus.match_queue.read_from(0, 1 << 20)
    assert [m.body for m in msgs] == sync_events
    _assert_books_equal(engine, sync_eng)


def test_pipelined_soak_with_persist_crash_restore(tmp_path):
    """The trickiest new interaction: cross-frame pipelining + the persist
    layer's consistent-cut snapshots + crash recovery. A pipelined service
    processes frames with snapshots riding on_batch; a crash (new service
    over the same dirs) restores and replays; the end-to-end match stream
    equals an uninterrupted unpipelined run byte-for-byte."""
    from gome_tpu.config import Config, EngineConfig, PersistConfig, BusConfig
    from gome_tpu.persist import Persister
    from gome_tpu.service.app import EngineService

    orders = multi_symbol_stream(n=1200, n_symbols=20, seed=41,
                                 cancel_prob=0.2)
    frames = _frames_for(orders, 150)

    def feed(svc, payloads, first_frame=0):
        for i, p in enumerate(payloads, start=first_frame):
            # Gateway role: mark THEN publish (main.go:42-48 order).
            for o in orders[i * 150 : i * 150 + 150]:
                svc.engine.mark(o)
            svc.bus.order_queue.publish(p)

    # Uninterrupted reference run (no pipeline, memory bus).
    ref = EngineService(
        Config(engine=EngineConfig(cap=32, max_fills=8, n_slots=32, max_t=8))
    )
    feed(ref, frames)
    ref.consumer.drain()
    ref_events = [
        m.body for m in ref.bus.match_queue.read_from(0, 1 << 20)
    ]

    def make_svc():
        cfg = Config(
            engine=EngineConfig(cap=32, max_fills=8, n_slots=32, max_t=8,
                                pipeline_depth=3),
            bus=BusConfig(backend="file", dir=str(tmp_path / "bus")),
            # every_n_batches=1: in pipelined mode the persist hook fires
            # once per pipeline-empty boundary (a whole drain is ONE
            # consistent cut), so any higher cadence may never snapshot.
            persist=PersistConfig(enabled=True, dir=str(tmp_path / "snap"),
                                  every_n_batches=1),
        )
        return EngineService(cfg, persist=Persister(cfg.persist))

    svc = make_svc()
    svc.persist.restore_latest()
    feed(svc, frames[:5])
    svc.consumer.drain()  # snapshots fire at pipeline-empty cuts
    feed(svc, frames[5:], first_frame=5)
    for _ in range(3):  # partially drain, leaving work + in-flight state
        svc.consumer.run_once()

    # Crash: fresh process over the same dirs.
    svc2 = make_svc()
    assert svc2.persist.restore_latest()
    svc2.consumer.drain()
    got = [m.body for m in svc2.bus.match_queue.read_from(0, 1 << 20)]
    assert got == ref_events
    svc2.engine.batch.verify_books()


def test_rewind_never_hands_out_a_held_or_donated_buffer_set(monkeypatch):
    """The event buffers' life across a rewind (ISSUE 33). Depth 2, so
    three frames are in flight when the oldest resolves; the fifth frame's
    fills overflow its buffer (120 fills into 64 columns), which ratchets
    the floor and raises _NeedExact with two later frames in flight. At
    every hand-out, before and after the rewind: the set's handles are
    alive (a donated set is never handed out again) and no frame in flight
    holds any of them; the tripped frame's own set and the two discarded
    frames' never come back. Events, books and counters equal the
    synchronous run's."""
    kw = dict(config=BookConfig(cap=256, max_fills=4), n_slots=8, max_t=8)

    def sells(tag, n):
        return [
            Order(uuid="m", oid=f"{tag}{i}", symbol="s", side=Side.SALE,
                  price=100, volume=1)
            for i in range(n)
        ]

    def quotes(tag, n):  # rest far from each other: no event
        return [
            Order(uuid="q", oid=f"{tag}{i}", symbol="s2",
                  side=Side(i % 2), price=300 if i % 2 else 50, volume=1)
            for i in range(n)
        ]

    orders = []
    for f in range(4):
        orders += sells(f"r{f}-", 50)
    orders += [
        Order(uuid="t", oid=f"sweep{i}", symbol="s", side=Side.BUY,
              price=100, volume=3)
        for i in range(40)
    ] + quotes("q4-", 10)  # frame 4: 120 fills from 50 ops
    for f in range(5, 12):
        orders += sells(f"r{f}-", 20) + quotes(f"q{f}-", 30)

    sync_eng, _, sync_events = _run(kw, orders, 50, 0)

    engine, bus, consumer = _make(kw, 2)
    for o in orders:
        engine.mark(o)
    for p in _frames_for(orders, 50):
        bus.order_queue.publish(p)

    real_take = engine_frames._take_buffers
    handed, retired, keep = [], set(), []  # keep: an id stays its array's

    def checked_take(eng, *shape):
        bufs, reused = real_take(eng, *shape)
        if reused:
            assert not any(b.is_deleted() for b in bufs)
            held = {
                id(arr)
                for pend, _c, _t in consumer._pipe._q
                for arr in (pend.compact or ())
            }
            assert not held & set(map(id, bufs))
            assert not retired & set(map(id, bufs))
        handed.append(reused)
        return bufs, reused

    real_resolve = engine_frames.resolve_frame

    def watched_resolve(eng, pend):
        try:
            return real_resolve(eng, pend)
        except engine_frames._NeedExact:
            # The tripped frame's set and those of the frames submitted on
            # top of it (rewound with it) must never be handed out.
            for p in [pend] + [q[0] for q in consumer._pipe._q]:
                keep.extend(p.compact[:3])
                retired.update(map(id, p.compact[:3]))
            assert len(consumer._pipe._q) == 2  # three were in flight
            raise

    monkeypatch.setattr(engine_frames, "_take_buffers", checked_take)
    monkeypatch.setattr(engine_frames, "resolve_frame", watched_resolve)
    assert consumer.drain() == len(orders)
    got = [m.body for m in bus.match_queue.read_from(0, 1 << 20)]
    assert got == sync_events == _oracle_lines(orders)
    st = engine.stats
    assert st.frame_fallbacks == 1 and len(retired) == 9
    assert engine.batch.geometry_floors()["fills_buf"][64] == 128
    # The first three frames start on fresh sets (nothing has resolved);
    # so do the two resubmitted after the rewind took three sets with it.
    assert handed[:3] == [False] * 3 and handed.count(False) <= 6
    assert st.fast_frames_reused == handed.count(True) >= 6
    _assert_books_equal(engine, sync_eng)
    engine.batch.verify_books()


def _serve_two_frames(gap_s):
    """An idle pipelined consumer and a live feed with one subscriber, on
    the frame wire with the deployed 2 ms windows; two ORDER frames go onto
    the order queue `gap_s` apart. Returns what left: the match messages'
    (seq0, events), the order queue's commits in order, the chunks the
    subscriber's queue was handed, how both queues' polls ended, and the
    engine."""
    from gome_tpu.bus.colwire import decode_event_frame
    from gome_tpu.service.matchfeed import MatchFeed
    from test_bus import _poll_returns

    orders = multi_symbol_stream(n=120, n_symbols=4, seed=29, cancel_prob=0.2)
    engine = MatchEngine(**ENGINE_KW)
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=64, match_wire="frame", pipeline_depth=2
    )
    feed = MatchFeed(bus, log_events=False)
    for o in orders:
        engine.mark(o)
    polls = [_poll_returns(q) for q in (bus.order_queue, bus.match_queue)]
    commits = []
    commit = bus.order_queue.commit
    bus.order_queue.commit = lambda off: (commits.append(off), commit(off))

    handed = []
    sub = threading.Thread(
        target=lambda: handed.extend(feed.subscribe()), daemon=True)
    sub.start()
    deadline = time.monotonic() + 60
    while not feed._subs and time.monotonic() < deadline:
        time.sleep(0.001)
    chunks = []
    put = feed._subs[0].put
    feed._subs[0].put = lambda item: (chunks.append(item), put(item))

    consumer.start()
    feed.start()
    try:
        time.sleep(0.02)  # both loops are idle, inside their polls
        first, second = _frames_for(orders, 60)
        bus.order_queue.publish(first)
        if gap_s:
            time.sleep(gap_s)
        bus.order_queue.publish(second)
        while time.monotonic() < deadline and not (
            bus.order_queue.committed() == 2
            and bus.match_queue.committed() == bus.match_queue.end_offset()
            and sum(map(len, chunks)) == len(handed) > 0
        ):
            time.sleep(0.001)
    finally:
        consumer.stop()
        feed.stop()
        sub.join(timeout=10)
    assert not sub.is_alive()
    messages = []
    for m in bus.match_queue.read_from(0, 1 << 20):
        batch = decode_event_frame(m.body)
        messages.append((batch.seq0, batch.to_results()))
    ended = {}
    for q, before in zip((bus.order_queue, bus.match_queue), polls):
        for end, n in _poll_returns(q).items():
            ended[end] = ended.get(end, 0) + n - before[end]
    return messages, commits, chunks, handed, ended, engine, orders


@pytest.mark.parametrize("gap_s", [0.0005, 0.0], ids=["apart", "together"])
def test_frames_apart_or_together_leave_the_same_way(gap_s):
    """ISSUE 37: a poll that holds an ORDER frame returns at once, so two
    frames half a millisecond apart may be read by two polls where one poll
    used to read both after its window. Either way each frame is one submit,
    one EVENT frame and one commit, and the feed hands each EVENT frame to
    the subscriber as its own chunk: the events, seqs, commits and books are
    those of the synchronous consumer (the parent's), whatever the timing."""
    from gome_tpu.api import order_pb2 as pb
    from gome_tpu.service import matchfeed

    messages, commits, chunks, handed, ended, engine, orders = (
        _serve_two_frames(gap_s))
    sync_eng, _, _ = _run(ENGINE_KW, orders, 60, 0)
    oracle = OracleEngine()
    want = [[r for o in half for r in oracle.process(o)]
            for half in (orders[:60], orders[60:])]
    assert all(want)  # both frames make events
    assert [seq0 for seq0, _ in messages] == [0, len(want[0])]
    got = [[dataclasses.replace(r, seq=None) for r in results]
           for _, results in messages]
    assert got == want
    assert commits == [1, 2]  # one commit a frame, in order
    # no poll of either loop waited for company or for its window
    assert ended["batch"] >= 2 and ended["full"] == ended["deadline"] == 0
    assert [len(c) for c in chunks] == [len(w) for w in want]
    assert handed == [raw for c in chunks for raw in c]
    assert handed == [
        matchfeed.match_result_to_pb(r).SerializeToString()
        for _, results in messages for r in results
    ]
    assert pb.MatchEvent.FromString(handed[0]).node.oid
    _assert_books_equal(engine, sync_eng)
    engine.batch.verify_books()
