"""A frame's way between the threads (ISSUE 38): the three queue hand-offs
timed where the frame is picked up, on the scripted clock and on every bus
backend; the stream's sending as a span per queue item; the file bus's reads
and cursor writes as spans; the publish stamps' bounded memory.
"""

from __future__ import annotations

import logging
import sys
import threading
import time

import pytest

from gome_tpu.bus import FileQueue, MemoryQueue, QueueBus, base
from gome_tpu.bus.colwire import encode_event_frame
from gome_tpu.service import matchfeed
from gome_tpu.service.matchfeed import MatchFeed
from gome_tpu.utils import tracing
from gome_tpu.utils.tracing import span

from test_matchfeed_handoff import _batch
from test_spans import MS, Clock

FRAME = b"GCO2 stands for a whole ORDER frame"
BACKENDS = ["memory", "file", "cfile", "amqp"]


@pytest.fixture
def clock(monkeypatch):
    """test_spans' scripted clocks, with the bus's publish stamps on them."""
    tracing.reset()
    c = Clock(monkeypatch)
    monkeypatch.setattr(base, "_wall_ns", lambda: c.wall)
    yield c
    tracing.reset()


@pytest.fixture
def notes(monkeypatch):
    """Every note() of every span, as (span name, meta)."""
    seen = []
    monkeypatch.setattr(
        tracing.span, "note",
        lambda self, **meta: seen.append((self.name, meta)))
    return seen


@pytest.fixture
def opener(request, tmp_path):
    """`opener(name)` opens the named queue on one backend's store: a second
    call is a second object on what the first one left (a boot)."""
    kind = request.param
    opened = []
    if kind == "amqp":
        from gome_tpu.bus.amqp import AmqpQueue
        from gome_tpu.bus.fakebroker import FakeBroker

        broker = FakeBroker().start()
        request.addfinalizer(broker.stop)
        make = lambda name: AmqpQueue(name, port=broker.port)
    elif kind == "cfile":
        from gome_tpu.bus.native import NativeFileQueue, native_available

        if not native_available():
            pytest.skip("native toolchain unavailable")
        make = lambda name: NativeFileQueue(name, str(tmp_path / name))
    elif kind == "file":
        make = lambda name: FileQueue(name, str(tmp_path / name))
    else:
        make = MemoryQueue

    def open_(name="doOrder"):
        opened.append(make(name))
        return opened[-1]

    open_.kind = kind
    yield open_
    for q in opened:
        if hasattr(q, "close"):
            q.close()


def _row(name):
    row = tracing.totals().get(name)
    if row is None or not row["count"]:
        return None
    return row["count"], round(row["wall_s"] * 1e9), round(
        row["longest_s"] * 1e9)


# --- the queue hand-offs --------------------------------------------------


@pytest.mark.parametrize("opener", BACKENDS, indirect=True)
@pytest.mark.parametrize("poll, dwell, key", [
    ("consumer_poll", "order_queue_dwell", "frame"),
    ("feed_poll", "match_queue_dwell", "match"),
])
def test_a_dwell_is_pick_up_minus_publish_to_the_nanosecond(
        opener, clock, notes, poll, dwell, key):
    queue = opener()
    poller = tracing.poll_span(poll)
    assert poller.batch(queue, 8, 0) == []  # an empty poll records nothing
    assert queue.publish(FRAME) == 0
    clock.wall += 1_234_567
    assert len(poller.batch(queue, 8, 5.0)) == 1
    assert _row(dwell) == (1, 1_234_567, 1_234_567)
    polled = lambda: [meta for name, meta in notes if name == poll]
    assert polled() == [
        {"polls": 2, "ended_by": "batch", key: 0, "dwell_us": 1234}]
    queue.commit(1)
    # the next one, picked up by a reader ahead of its commits
    assert queue.publish(FRAME) == 1
    clock.wall += 89
    assert len(poller.ahead(queue, 1, 8)) == 1
    assert _row(dwell) == (2, 1_234_567 + 89, 1_234_567)
    assert polled()[1:] == [{"polls": 1, key: 1, "dwell_us": 0}]


@pytest.mark.parametrize("opener", BACKENDS, indirect=True)
def test_a_poll_records_once_the_oldest_message_never_per_message(
        opener, clock):
    queue = opener()
    for k in range(3):  # one-order JSON messages, 1 ms apart
        queue.publish(b'{"one": "order %d"}' % k)
        clock.advance(1.0)
    poller = tracing.poll_span("consumer_poll")
    assert len(poller.batch(queue, 3, 5.0)) == 3
    assert _row("order_queue_dwell") == (1, 3 * MS, 3 * MS)


@pytest.mark.parametrize("opener", BACKENDS, indirect=True)
def test_a_message_read_twice_records_nothing_the_second_time(opener, clock):
    queue = opener()
    queue.publish(FRAME)
    queue.publish(FRAME)
    clock.advance(2.0)
    poller = tracing.poll_span("consumer_poll")
    assert len(poller.batch(queue, 8, 5.0)) == 2
    assert _row("order_queue_dwell") == (1, 2 * MS, 2 * MS)
    clock.advance(5.0)
    # a rewind: from the committed offset again, then from the middle
    assert len(poller.batch(queue, 8, 5.0)) == 2
    assert len(poller.ahead(queue, 1, 8)) == 1
    assert _row("order_queue_dwell") == (1, 2 * MS, 2 * MS)


@pytest.mark.parametrize("opener", ["file", "cfile", "amqp"], indirect=True)
def test_a_message_from_before_the_queue_object_records_nothing(
        opener, clock):
    """A replay after a boot, another process's publish: the queue object
    that reads it has no instant for it."""
    first = opener()
    first.publish(FRAME)
    first.close()
    clock.advance(50.0)
    second = opener()
    poller = tracing.poll_span("consumer_poll")
    assert len(poller.batch(second, 8, 5.0)) == 1
    assert _row("order_queue_dwell") is None
    if opener.kind != "amqp":  # its own publish, at the next offset
        second.commit(1)
        assert second.publish(FRAME) == 1
        clock.advance(3.0)
        assert len(poller.batch(second, 8, 5.0)) == 1
        assert _row("order_queue_dwell") == (1, 3 * MS, 3 * MS)


@pytest.mark.parametrize("opener", BACKENDS, indirect=True)
def test_the_stamps_are_bounded_under_a_consumer_that_never_commits(
        opener, clock, monkeypatch):
    monkeypatch.setattr(base, "PUBLISH_STAMPS", 16)
    queue = opener()
    for k in range(40):  # nobody reads: a gateway's side of a shared log
        queue.publish(b"m%d" % k)
        clock.advance(1.0)
        assert len(queue._stamps._ns) <= 16
    kept = lambda: [offset for offset, _at in queue._stamps._ns]
    assert kept() == list(range(24, 40))
    # a reader that picks up and never commits holds nothing back either
    poller = tracing.poll_span("consumer_poll")
    assert len(poller.ahead(queue, 24, 8)) == 8
    assert _row("order_queue_dwell") == (1, 16 * MS, 16 * MS)
    assert kept() == list(range(32, 40))
    # the oldest went first: message 0 has no instant any more
    assert len(poller.ahead(queue, 0, 4)) == 4
    assert _row("order_queue_dwell")[0] == 1


def test_a_truncated_tail_published_anew_is_timed_from_its_new_publish(clock):
    queue = MemoryQueue("matchOrder")
    for _ in range(3):
        queue.publish(FRAME)
    clock.advance(40.0)  # a recovery drops the tail and replays it
    queue.truncate_to(1)
    assert queue.publish(FRAME) == 1
    clock.advance(1.0)
    poller = tracing.poll_span("feed_poll")
    assert len(poller.ahead(queue, 1, 8)) == 1
    assert _row("match_queue_dwell") == (1, 1 * MS, 1 * MS)


def test_the_stamps_hold_under_publishers_and_a_reader_at_once():
    """More threads than cores, a short switch interval: the stamps stay
    bounded, every dwell recorded is one poll's, and none is negative."""
    tracing.reset()
    queue = MemoryQueue("doOrder")
    n_threads, n_each = 8, 400
    polls = []

    def publish():
        for _ in range(n_each):
            queue.publish(FRAME)

    def read():
        poller = tracing.poll_span("consumer_poll")
        seen = 0
        while seen < n_threads * n_each:
            got = poller.ahead(queue, seen, 64)
            if got:
                polls.append(len(got))
                seen = got[-1].offset + 1
        poller.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=publish) for _ in range(n_threads)]
        threads.append(threading.Thread(target=read))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert sum(polls) == n_threads * n_each
    row = tracing.totals()["order_queue_dwell"]
    assert 0 < row["count"] <= len(polls)
    assert 0 <= row["longest_s"] <= row["wall_s"] < 60
    assert len(queue._stamps._ns) <= base.PUBLISH_STAMPS
    tracing.reset()


def test_a_batch_publish_stamps_every_message_of_it(clock, tmp_path):
    from gome_tpu.bus.native import NativeFileQueue, native_available

    if not native_available():
        pytest.skip("native toolchain unavailable")
    queue = NativeFileQueue("matchOrder", str(tmp_path / "matchOrder"))
    assert queue.publish_batch([b"a", b"b", b"c", b"d"]) == 0
    clock.advance(4.0)
    poller = tracing.poll_span("feed_poll")
    assert len(poller.ahead(queue, 2, 8)) == 2  # the oldest of it: message 2
    assert _row("match_queue_dwell") == (1, 4 * MS, 4 * MS)
    assert not queue._stamps._ns  # 0 and 1 went with what was picked up
    queue.close()


def test_a_poll_span_of_another_name_ends_no_hand_off(clock, notes):
    queue = MemoryQueue("unit")
    queue.publish(FRAME)
    clock.advance(1.0)
    assert len(tracing.poll_span("unit_other_idle").batch(queue, 8, 5.0)) == 1
    assert notes == [("unit_other_idle", {"polls": 1, "ended_by": "batch"})]
    assert tracing.totals()["unit_other_idle"]["count"] == 1
    assert _row("order_queue_dwell") is None
    assert len(queue._stamps._ns) == 1  # not taken either


# --- the file bus ---------------------------------------------------------


@pytest.mark.parametrize("opener", ["file", "cfile"], indirect=True)
def test_a_file_queue_times_its_reads_and_its_cursor(opener, clock, notes):
    queue = opener()
    assert queue.read_from(0, 8) == []  # an empty read opens no span
    assert _row("log_read") is None
    queue.publish(b"abc")
    queue.publish(b"defgh")
    assert len(queue.read_from(0, 8)) == 2
    assert _row("log_read")[0] == 1
    assert queue.read_from(2, 8) == []
    assert _row("log_read")[0] == 1
    (read,) = [meta for name, meta in notes if name == "log_read"]
    assert read["bytes"] == 16  # two records: 4 + 3 and 4 + 5
    queue.commit(2)
    assert _row("cursor_commit")[0] == 1
    queue.rollback(1)  # a cursor's write all the same
    assert _row("cursor_commit")[0] == 2


def test_the_memory_queue_opens_neither(clock):
    queue = MemoryQueue("doOrder")
    queue.publish(b"abc")
    assert len(queue.read_from(0, 8)) == 1
    queue.commit(1)
    queue.rollback(0)
    assert _row("log_read") is None and _row("cursor_commit") is None


# --- the handler's two leaves --------------------------------------------


def _two_chunks_waiting(monkeypatch):
    """A feed, and a subscription that holds two EVENT frames' chunks before
    its handler takes the first step: every pick-up is a get_nowait, on the
    test's own thread. Returns (feed, subscription, events a frame)."""
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    feed = MatchFeed(bus, log_events=False)
    events = _batch()
    n = len(events)
    sub = matchfeed._Subscription()
    monkeypatch.setattr(matchfeed, "_Subscription", lambda: sub)
    bus.match_queue.publish(encode_event_frame(events, seq0=0))
    bus.match_queue.publish(encode_event_frame(events, seq0=n))
    feed._subs.append(sub)
    assert feed.run_once() == 2
    feed._subs.remove(sub)
    return feed, sub, n


def test_stream_send_is_one_span_a_chunk_closed_when_grpc_has_it_all(
        clock, notes, monkeypatch):
    opened = []
    init = tracing.span.__init__
    monkeypatch.setattr(
        tracing.span, "__init__",
        lambda self, name, **meta: (opened.append((name, meta)),
                                    init(self, name, **meta))[1])
    feed, sub, n = _two_chunks_waiting(monkeypatch)
    assert n * MS // 2 > 3 * MS  # the first chunk's send is the longest
    stream = feed.subscribe()
    clock.advance(2.0)  # both chunks have waited 2 ms on the queue
    for _ in range(n):
        assert type(next(stream)) is bytes
        assert _row("stream_send") is None  # open from the first event on
        clock.advance(0.5, 0.1)
    assert feed._subs == [sub] and sub.handed == 2  # owed nothing at start
    # asked for what follows the last event: gRPC has the whole chunk
    assert type(next(stream)) is bytes
    assert sub.handed == 1
    assert _row("stream_send") == (1, n * MS // 2, n * MS // 2)
    assert tracing.totals()["stream_send"]["cpu_s"] == pytest.approx(
        n * 1e-4)
    # the subscriber goes away in the middle of the second chunk
    clock.advance(3.0)
    stream.close()
    assert _row("stream_send") == (2, n * MS // 2 + 3 * MS, n * MS // 2)
    assert sub.handed == 1  # not handed whole: the feed may not commit it
    assert feed._subs == []
    # one span a chunk, never one an event
    sends = [meta for name, meta in opened if name == "stream_send"]
    assert sends == [dict(match=0, events=n), dict(match=1, events=n)]
    # first_us: pick-up to the resumption after the first event; the
    # hand-off's dwell rides on the span, since the handler did not wait
    assert [meta for name, meta in notes if name == "stream_send"] == [
        {"dwell_us": 2000}, {"first_us": 500},
        {"dwell_us": 2000 + n * 500},
    ]
    # put to get, once per chunk, to the nanosecond
    assert _row("subscriber_queue_dwell") == (
        2, 4 * MS + n * MS // 2, 2 * MS + n * MS // 2)


def test_a_handler_that_waited_notes_the_hand_off_on_its_wait(
        clock, notes):
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    feed = MatchFeed(bus, log_events=False)
    events = _batch()
    stream = feed.subscribe()
    got = []
    t = threading.Thread(target=lambda: got.append(next(stream)))
    t.start()  # its queue is empty: it waits inside stream_wait
    deadline = time.monotonic() + 10
    while not feed._subs and time.monotonic() < deadline:
        time.sleep(0.001)
    bus.match_queue.publish(encode_event_frame(events, seq0=0))
    assert feed.run_once() == 1
    t.join(timeout=10)
    assert not t.is_alive() and len(got) == 1
    assert _row("subscriber_queue_dwell") == (1, 0, 0)  # the scripted clock
    assert ("stream_wait", {"match": 0, "dwell_us": 0}) in notes
    assert not [meta for name, meta in notes
                if name == "stream_send" and "dwell_us" in meta]
    stream.close()
    assert _row("stream_send")[0] == 1


def test_a_300ms_stream_send_is_neither_kept_nor_logged(clock, caplog):
    """It is long by arithmetic (2,200 events of a frame), on the thread
    that sets the pace: only its longest and wall minus CPU tell a stall."""
    caplog.set_level(logging.WARNING, logger="gome_tpu.tracing")
    with span("stream_send", events=2200):
        clock.advance(300.0, 120.0, 290.0)
    assert tracing.slow() == [] and not caplog.records
    row = tracing.totals()["stream_send"]
    assert (row["count"], row["longest_s"]) == (1, pytest.approx(0.3))
    assert row["wall_s"] - row["cpu_s"] == pytest.approx(0.18)
    with span("frame_fetch"):
        clock.advance(300.0, 1.0, 2.0)
    assert [r["span"] for r in tracing.slow()] == ["frame_fetch"]
    assert len(caplog.records) == 1
    assert "span=frame_fetch" in caplog.records[0].getMessage()


# --- the two loops asleep on their queues (ISSUE 42) -----------------------


def _service(backend, tmp_path):
    from gome_tpu.config import BusConfig, Config, EngineConfig, GrpcConfig
    from gome_tpu.service import EngineService

    svc = EngineService(Config(
        grpc=GrpcConfig(host="127.0.0.1", port=0),
        engine=EngineConfig(cap=32, n_slots=8, max_t=8, pipeline_depth=2),
        bus=BusConfig(backend=backend, dir=str(tmp_path / "bus"),
                      match_wire="frame"),
    ))
    svc.feed.log_events = False
    return svc


def _timers(svc) -> list[int]:
    return [q.idle_wakeups()["timer"]
            for q in (svc.bus.order_queue, svc.bus.match_queue)]


def _until(done, timeout_s=30.0, step_s=0.0005) -> float:
    """Seconds until done() held, looked at every half millisecond."""
    t0 = time.monotonic()
    while not done():
        assert time.monotonic() - t0 < timeout_s
        time.sleep(step_s)
    return time.monotonic() - t0


def test_an_idle_service_sleeps_and_its_poll_spans_cover_the_second(
        tmp_path, caplog):
    """A started service with nothing to do: each loop wakes on its timer
    about ten times in a second (a thousand before ISSUE 42), the consumer's
    and the feed's poll spans still cover the idle stretch, and the request
    that ends it is picked up on the publish's own wake-up, its two queue
    hand-offs recorded once each."""
    from gome_tpu.api import order_pb2 as pb

    svc = _service("memory", tmp_path)
    svc.consumer.run_once()  # the first step's imports, outside the second
    svc.consumer._poll.close()
    svc.start()
    try:
        time.sleep(0.15)  # both loops asleep
        tracing.reset()
        before, t0 = _timers(svc), time.monotonic()
        time.sleep(1.0)
        woken = [b - a for a, b in zip(before, _timers(svc))]
        assert all(n <= 15 for n in woken), woken
        for name in ("consumer_poll", "feed_poll"):  # never a slow span
            assert tracing.totals()[name]["longest_s"] < 0.25, name
        reqs = [
            pb.OrderRequest(
                uuid="u", oid=f"o{i}", symbol="s", price=1.0, volume=1.0,
                transaction=pb.SALE if i % 2 else pb.BUY)
            for i in range(8)
        ]
        resp = svc.gateway.DoOrderBatch(pb.OrderBatchRequest(orders=reqs), None)
        assert (resp.code, resp.accepted) == (0, 8)
        _until(lambda: svc.feed.events_seen >= 4, timeout_s=120)
        idle = time.monotonic() - t0
    finally:
        with caplog.at_level(logging.INFO, logger="gome_tpu"):
            svc.stop()
    (polls,) = [r.getMessage() for r in caplog.records
                if "polls that brought messages" in r.getMessage()]
    for q in (svc.bus.order_queue, svc.bus.match_queue):  # beside the polls
        assert f"its idle reader woken by {q.idle_wakeups()}" in polls
    rows = tracing.totals()
    # The spans open at the reset count whole, those the request closed end
    # with it: both loops were inside their poll span for the idle second.
    for name in ("consumer_poll", "feed_poll"):
        assert 0.95 < rows[name]["wall_s"] / 1.0 and \
            rows[name]["wall_s"] < idle + 0.3, (name, rows[name], idle)
    for name in ("order_queue_dwell", "match_queue_dwell"):
        assert rows[name]["count"] == 1, (name, rows[name])
        assert rows[name]["wall_s"] < 0.05, (name, rows[name])


def test_stop_wakes_an_idle_consumer_and_feed(tmp_path, monkeypatch):
    """With the sleep's bound at 30 s a stop that did not wake its sleeper
    would wait out the join's 10 s: the best of three stops of each loop
    returns in under 50 ms."""
    monkeypatch.setattr(tracing, "MERGE_POLLS_NS", 30_000_000_000)
    svc = _service("memory", tmp_path)
    svc.consumer.run_once()
    svc.consumer._poll.close()
    took = {"consumer": [], "feed": []}
    for _ in range(3):
        for name, loop in (("consumer", svc.consumer), ("feed", svc.feed)):
            loop.start()
            time.sleep(0.05)  # asleep on its queue
            t0 = time.monotonic()
            loop.stop()
            took[name].append(time.monotonic() - t0)
    assert min(took["consumer"]) < 0.05 and max(took["consumer"]) < 2, took
    assert min(took["feed"]) < 0.05 and max(took["feed"]) < 2, took


@pytest.mark.parametrize("backend", ["memory", "file"])
def test_a_hand_over_is_committed_at_once_with_no_further_publish(
        backend, tmp_path, monkeypatch):
    """The handler that has handed a chunk to gRPC whole wakes the feed, so
    the match queue's cursor follows within milliseconds, not at the feed's
    next timer (30 s here) nor at the next publish (there is none)."""
    monkeypatch.setattr(tracing, "MERGE_POLLS_NS", 30_000_000_000)
    if backend == "file":
        make = lambda name: FileQueue(name, str(tmp_path / name), fsync=False)
    else:
        make = MemoryQueue
    bus = QueueBus(make("doOrder"), make("matchOrder"))
    feed = MatchFeed(bus, log_events=False)
    events = _batch()
    n = len(events)
    got, last_at = [], []

    def handler():
        for ev in feed.subscribe():
            got.append(ev)
            if len(got) % n == 0:
                last_at.append(time.monotonic())  # it asks for the next now

    feed.start()
    reader = threading.Thread(target=handler, daemon=True)
    reader.start()
    try:
        _until(lambda: feed._subs)
        took = []
        for k in range(3):
            bus.match_queue.publish(encode_event_frame(events, seq0=k * n))
            _until(lambda: len(last_at) == k + 1)
            _until(lambda: bus.match_queue.committed() == k + 1)
            took.append(time.monotonic() - last_at[k])
            time.sleep(0.02)  # the feed is asleep again
            if k == 0:  # a file queue hears from its first append on
                timers = bus.match_queue.idle_wakeups()["timer"]
        assert min(took) < 0.01 and max(took) < 2, took
        assert bus.match_queue.idle_wakeups()["timer"] == timers
    finally:
        feed.stop()
        reader.join(timeout=5)


def test_a_feed_held_by_its_harness_neither_sleeps_on_messages_nor_skips_them(
        monkeypatch):
    """benchmark/serve.py's hold-until-subscribed: run_once replaced on the
    instance by one that returns 0 while messages wait in the match queue.
    The loop calls what the instance holds, does not sleep on a queue that
    is not empty at the read cursor (the sleep's bound is 30 s here), and
    fans out every message once the wrapper lets go."""
    monkeypatch.setattr(tracing, "MERGE_POLLS_NS", 30_000_000_000)
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    feed = MatchFeed(bus, log_events=False)
    events = _batch()
    n = len(events)
    for k in range(2):
        bus.match_queue.publish(encode_event_frame(events, seq0=k * n))
    inner, held, calls = feed.run_once, threading.Event(), [0]
    held.set()

    def run_once():
        calls[0] += 1
        if held.is_set():
            time.sleep(0.002)
            return 0
        return inner()

    feed.run_once = run_once
    feed.start()
    try:
        time.sleep(0.3)
        assert calls[0] >= 20, calls  # ~150: a pass every 2 ms, no sleep
        assert feed.events_seen == 0 and bus.match_queue.committed() == 0
        held.clear()
        _until(lambda: bus.match_queue.committed() == 2, timeout_s=5)
        assert feed.events_seen == 2 * n
        assert feed.seq_state()["gaps"] == 0 == feed.seq_state()["dupes"]
        # and with the queue empty at the cursor it sleeps, held or not
        held.set()
        time.sleep(0.05)
        calls[0] = 0
        time.sleep(0.3)
        assert calls[0] <= 1, calls
    finally:
        feed.stop()
