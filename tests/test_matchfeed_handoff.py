"""The match feed's hand-off (ISSUE 29): the unit handed to a subscriber is
the match message, as a list of serialised MatchEvent messages; the wire is
still one MatchEvent per gRPC message, each seq at most once, in admission
order, and `matchfeed.match_result_to_pb` is the one builder."""

import dataclasses
import logging
import struct
import threading
import time

import pytest

from gome_tpu.api import order_pb2 as pb
from gome_tpu.bus import MemoryQueue, QueueBus, encode_match_result
from gome_tpu.bus.colwire import decode_event_frame, encode_event_frame
from gome_tpu.engine.batch import BatchEngine
from gome_tpu.engine.book import BookConfig
from gome_tpu.service import matchfeed
from gome_tpu.service.matchfeed import MatchFeed, SeqTracker
from gome_tpu.utils.metrics import REGISTRY
from gome_tpu.utils.streams import multi_symbol_stream

FIELDS = ("uuid", "oid", "symbol", "transaction", "price", "volume")


def _batch(seed=2, n=200):
    """Fills and cancels over four symbols, as the engine emits them."""
    orders = multi_symbol_stream(n=n, n_symbols=4, seed=seed, cancel_prob=0.3)
    eng = BatchEngine(BookConfig(cap=32, max_fills=8), n_slots=32, max_t=8)
    batch = eng.process_columnar(orders)
    results = batch.to_results()
    assert any(mr.is_cancel for mr in results)
    assert any(not mr.is_cancel for mr in results)
    return batch


def _feed(log_events=False):
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    return MatchFeed(bus, log_events=log_events), bus.match_queue


class _Context:
    """What subscribe() asks of a gRPC context."""

    def __init__(self):
        self.active = True
        self.asked = 0

    def is_active(self):
        self.asked += 1
        return self.active


def _subscribed(feed, context=None):
    """A started subscriber generator (registered in feed._subs) and the
    list its thread fills; the generator ends with the feed."""
    got = []
    t = threading.Thread(
        target=lambda: got.extend(feed.subscribe(context)), daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while not feed._subs and time.monotonic() < deadline:
        time.sleep(0.001)
    assert feed._subs
    return got, t


def _delivered(feed, publish):
    """Everything one subscriber receives for what `publish` puts on the
    match queue, and the queue items it was handed."""
    got, t = _subscribed(feed)
    items = []
    q = feed._subs[0]
    put = q.put
    q.put = lambda item: (items.append(item), put(item))
    publish()
    feed.drain()
    deadline = time.monotonic() + 10
    want = sum(len(i) for i in items)
    while len(got) < want and time.monotonic() < deadline:
        time.sleep(0.001)
    feed._stop.set()
    t.join(timeout=10)
    assert not t.is_alive()
    assert feed._subs == []
    return got, items


def _bits(x: float) -> bytes:
    return struct.pack("<d", x)


# (a) a frame's events, message for message, are what the object path builds


def test_a_frame_is_delivered_as_the_object_path_builds_it():
    batch = _batch()
    payload = encode_event_frame(batch, seq0=0)
    feed, mq = _feed()
    got, items = _delivered(feed, lambda: mq.publish(payload))
    want = [
        matchfeed.match_result_to_pb(mr)
        for mr in decode_event_frame(payload).to_results()
    ]
    assert len(items) == 1 and len(items[0]) == len(want) == len(got)
    for raw, w in zip(got, want):
        assert type(raw) is bytes
        ev = pb.MatchEvent.FromString(raw)
        for side in ("node", "match_node"):
            g, x = getattr(ev, side), getattr(w, side)
            for f in FIELDS:
                assert getattr(g, f) == getattr(x, f), (side, f)
            assert _bits(g.price) == _bits(x.price)
            assert _bits(g.volume) == _bits(x.volume)
        assert _bits(ev.match_volume) == _bits(w.match_volume)
        assert raw == w.SerializeToString()


def test_a_row_is_the_match_result_field_for_field():
    batch = _batch(seed=5)
    rows = matchfeed._frame_rows(batch)
    assert rows == [matchfeed._row_of(mr) for mr in batch.to_results()]
    for row in rows:
        assert [type(v) for v in row] == [
            str, str, str, int, float, float,
            str, str, str, int, float, float, float]


# (b) duplicates, overlaps and holes: the counts of a per-event observe walk


def _walk(runs):
    t = SeqTracker()
    fresh = [[t.observe(s) for s in run] for run in runs]
    return t.state(), fresh


@pytest.mark.parametrize("runs", [
    [range(0, 10), range(0, 10)],  # the same frame twice
    [range(0, 10), range(6, 16)],  # overlapping the previous one's tail
    [range(0, 10), range(3, 7)],  # wholly inside what was seen
    [range(0, 10), range(14, 20)],  # a hole between frames
    [range(5, 9), range(9, 12), range(0, 3)],  # mid-stream attach, rewind
    [range(0, 0), range(2, 5), range(5, 5), range(4, 9)],  # empty frames
], ids=["twice", "overlap", "inside", "hole", "attach-rewind", "empty"])
def test_observe_run_counts_as_a_per_event_walk(runs):
    want_state, fresh = _walk(runs)
    t = SeqTracker()
    seen = [t.observe_run(run) for run in runs]
    assert t.state() == want_state
    # a run's already-seen seqs are its leading ones
    assert [[False] * s + [True] * (len(r) - s)
            for s, r in zip(seen, runs)] == fresh


@pytest.mark.parametrize("second_seq0, dupes, gaps", [
    (0, "all", 0), ("tail", 7, 0), ("hole", 0, 5),
], ids=["twice", "overlap", "hole"])
def test_frames_deliver_each_seq_once(second_seq0, dupes, gaps):
    first, second = _batch(seed=2), _batch(seed=3)
    n1, n2 = len(first), len(second)
    if second_seq0 == 0:
        second, n2 = first, n1
    seq0 = {0: 0, "tail": n1 - 7, "hole": n1 + 5}[second_seq0]
    dupes = n1 if dupes == "all" else dupes
    walk = SeqTracker()
    fresh = [walk.observe(s) for s in
             [*range(n1), *range(seq0, seq0 + n2)]]
    d0 = REGISTRY.counter("gome_matchfeed_dupes_total").value()
    g0 = REGISTRY.counter("gome_matchfeed_gaps_total").value()
    feed, mq = _feed()
    got, items = _delivered(feed, lambda: (
        mq.publish(encode_event_frame(first, seq0=0)),
        mq.publish(encode_event_frame(second, seq0=seq0))))
    assert feed.seq.state() == walk.state()
    assert feed.seq_state()["dupes"] == dupes
    assert feed.seq_state()["gaps"] == gaps
    assert feed.suppressed == fresh.count(False) == dupes
    assert feed.events_seen == fresh.count(True) == len(got)
    assert REGISTRY.counter("gome_matchfeed_dupes_total").value() - d0 == dupes
    assert REGISTRY.counter("gome_matchfeed_gaps_total").value() - g0 == gaps
    both = first.to_results() + second.to_results()
    want = [matchfeed.match_result_to_pb(mr).SerializeToString()
            for mr, new in zip(both, fresh) if new]
    assert got == want
    # a frame wholly seen before hands nothing over
    assert [len(i) for i in items] == [k for k in (n1, n2 - dupes) if k]


def test_an_unstamped_frame_passes_untracked():
    batch = _batch()
    feed, mq = _feed()
    got, _ = _delivered(feed, lambda: (
        mq.publish(encode_event_frame(batch)),
        mq.publish(encode_event_frame(batch))))
    assert len(got) == 2 * len(batch)
    assert feed.seq.state()["observed"] == 0 and feed.suppressed == 0


# (c) a JSON run and a frame interleaved keep admission order


def _part(batch, lo, hi, seq0):
    cut = dataclasses.replace(
        batch, columns={k: v[lo:hi] for k, v in batch.columns.items()})
    return encode_event_frame(cut, seq0=seq0)


def test_json_runs_and_frames_interleave_in_admission_order():
    batch = _batch()
    results = [dataclasses.replace(mr, seq=k)
               for k, mr in enumerate(batch.to_results())]
    a, b = len(results) // 3, 2 * len(results) // 3
    h0 = REGISTRY.counter("gome_matchfeed_handoffs_total").value()
    e0 = REGISTRY.counter("gome_matchfeed_events_total").value()

    def publish():
        for mr in results[:a]:  # a JSON run, one event a message
            mq.publish(encode_match_result(mr))
        mq.publish(_part(batch, a, b, seq0=a))
        mq.publish(encode_match_result(results[a]))  # replayed: a dupe
        mq.publish(_part(batch, b, None, seq0=b))

    feed, mq = _feed()
    got, items = _delivered(feed, publish)
    assert got == [matchfeed.match_result_to_pb(mr).SerializeToString()
                   for mr in results]
    # the hand-off's size follows what arrived: the run, then each frame
    assert [len(i) for i in items] == [a, b - a, len(results) - b]
    assert feed.suppressed == 1 and feed.seq_state()["gaps"] == 0
    assert REGISTRY.counter("gome_matchfeed_handoffs_total").value() - h0 == 3
    assert (REGISTRY.counter("gome_matchfeed_events_total").value() - e0
            == len(results) == feed.events_seen)


def test_json_messages_that_come_one_by_one_are_hand_offs_of_one():
    results = _batch().to_results()[:5]
    feed, mq = _feed()
    got, t = _subscribed(feed)
    h0 = REGISTRY.counter("gome_matchfeed_handoffs_total").value()
    for mr in results:
        mq.publish(encode_match_result(mr))
        assert feed.run_once() == 1
    assert REGISTRY.counter("gome_matchfeed_handoffs_total").value() - h0 == 5
    deadline = time.monotonic() + 10  # the subscriber's thread takes the last
    while len(got) < len(results) and time.monotonic() < deadline:
        time.sleep(0.001)
    feed._stop.set()
    t.join(timeout=10)
    assert not t.is_alive()
    assert [pb.MatchEvent.FromString(r) for r in got] == [
        matchfeed.match_result_to_pb(mr) for mr in results]


# (d) over a real gRPC server: N raw messages for N events


def test_the_wire_is_one_parseable_match_event_per_message():
    import grpc

    from gome_tpu.config import BusConfig, Config, EngineConfig, GrpcConfig
    from gome_tpu.service import EngineService

    svc = EngineService(Config(
        grpc=GrpcConfig(host="127.0.0.1", port=0),
        engine=EngineConfig(cap=16, n_slots=4, max_t=4),
        bus=BusConfig(backend="memory", match_wire="frame"),
    ))
    svc.feed.log_events = False
    svc.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{svc._server.bound_port}")
    try:
        stream = channel.unary_stream(
            "/gome_tpu.api.Order/SubscribeMatches",
            request_serializer=None, response_deserializer=None,
        )(b"", timeout=60)
        deadline = time.monotonic() + 30
        while not svc.feed._subs and time.monotonic() < deadline:
            time.sleep(0.01)
        batch = _batch()
        svc.bus.match_queue.publish(encode_event_frame(batch, seq0=0))
        raw = [next(stream) for _ in range(len(batch))]
        stream.cancel()
    finally:
        channel.close()
        svc.stop()
    assert all(type(r) is bytes for r in raw)
    assert [pb.MatchEvent.FromString(r) for r in raw] == [
        matchfeed.match_result_to_pb(mr) for mr in batch.to_results()]


# (e) the builder is looked up through the module at call time


@pytest.mark.parametrize("wire", ["frame", "json"])
def test_replacing_the_builder_after_import_alters_what_is_delivered(
        monkeypatch, wire):
    inner = matchfeed.match_result_to_pb
    seen = []

    def altered(mr):
        ev = inner(mr)
        seen.append(ev)
        if len(seen) == 3:
            ev.match_node.price += 1.0
        return ev

    batch = _batch()
    results = batch.to_results()
    feed, mq = _feed()
    monkeypatch.setattr(matchfeed, "match_result_to_pb", altered)

    def publish():
        if wire == "frame":
            mq.publish(encode_event_frame(batch, seq0=0))
        else:
            for mr in results:
                mq.publish(encode_match_result(mr))

    got, _ = _delivered(feed, publish)
    assert len(seen) == len(results) == len(got)  # once per event
    assert all(isinstance(ev, pb.MatchEvent) for ev in seen)
    want = [inner(mr) for mr in results]
    want[2].match_node.price += 1.0
    assert [pb.MatchEvent.FromString(r) for r in got] == want


# (f) the per-event log line, for a deployment that logs at INFO


@pytest.fixture
def logged(monkeypatch):
    """The feed's per-event logging calls, with the logger's level set by
    the test and put back after it."""
    calls = []
    monkeypatch.setattr(
        matchfeed.log, "info", lambda msg, *a: calls.append(msg % a))
    level = matchfeed.log.level
    yield calls
    matchfeed.log.setLevel(level)


@pytest.mark.parametrize("level", [logging.INFO, logging.WARNING],
                         ids=["info", "warning"])
def test_events_are_logged_once_each_only_at_info(logged, level):
    batch = _batch()
    matchfeed.log.setLevel(level)
    feed, mq = _feed(log_events=True)
    mq.publish(encode_event_frame(batch, seq0=0))
    feed.drain()
    assert feed.events_seen == len(batch)
    if level == logging.WARNING:
        assert logged == []  # not one per-event logging call
        return
    assert logged == [
        "match %s: taker=%s maker=%s qty=%d" % (
            "CANCEL" if mr.is_cancel else "FILL", mr.node.oid,
            mr.match_node.oid, mr.match_volume)
        for mr in batch.to_results()
    ]


def test_log_events_off_logs_nothing_at_info(logged):
    matchfeed.log.setLevel(logging.INFO)
    feed, mq = _feed(log_events=False)
    mq.publish(encode_event_frame(_batch(), seq0=0))
    feed.drain()
    assert logged == [] and feed.events_seen


# (g) the generator ends within one queue item


@pytest.mark.parametrize("how", ["context", "stop"])
def test_the_generator_ends_within_one_queue_item(how):
    batch = _batch()
    n = len(batch)
    feed, mq = _feed()
    context = _Context()
    mq.publish(encode_event_frame(batch, seq0=0))
    mq.publish(encode_event_frame(batch, seq0=n))

    def drain_once_subscribed():
        deadline = time.monotonic() + 10
        while not feed._subs and time.monotonic() < deadline:
            time.sleep(0.001)
        feed.drain()

    t = threading.Thread(target=drain_once_subscribed, daemon=True)
    t.start()
    gen = feed.subscribe(context)
    first = next(gen)  # the first frame is in hand, the second queued
    t.join(timeout=10)
    assert not t.is_alive() and feed.events_seen == 2 * n
    asked = context.asked
    if how == "context":
        context.active = False
    else:
        feed.stop()
    rest = list(gen)
    # the item in hand is finished, the queued one never begun
    assert [first, *rest] == [
        matchfeed.match_result_to_pb(mr).SerializeToString()
        for mr in batch.to_results()]
    # looked at once per queue item (and per empty wait), not per event
    assert context.asked - asked <= 1 and asked < n
    assert feed._subs == []
