"""Bus backends + codec tests (gome_tpu.bus vs rabbitmq.go topology)."""

import os
import sys
import threading
import time

import pytest

from gome_tpu.bus import (
    FileQueue,
    MemoryQueue,
    decode_match_result,
    decode_order,
    encode_match_result,
    encode_order,
    make_bus,
)
from gome_tpu.config import BusConfig
from gome_tpu.types import Action, MatchResult, Order, OrderSnapshot, OrderType, Side


def _native_queue(tmp_path):
    from gome_tpu.bus.native import NativeFileQueue, native_available

    if not native_available():
        pytest.skip("native toolchain unavailable")
    return NativeFileQueue("doOrder", str(tmp_path / "doOrder"))


@pytest.fixture(params=["memory", "file", "cfile"])
def queue(request, tmp_path):
    if request.param == "memory":
        return MemoryQueue("doOrder")
    if request.param == "cfile":
        return _native_queue(tmp_path)
    return FileQueue("doOrder", str(tmp_path / "doOrder"))


def test_publish_read_commit(queue):
    offs = [queue.publish(f"m{i}".encode()) for i in range(5)]
    assert offs == [0, 1, 2, 3, 4]
    assert queue.end_offset() == 5
    msgs = queue.read_from(0, 3)
    assert [m.body for m in msgs] == [b"m0", b"m1", b"m2"]
    assert queue.committed() == 0
    queue.commit(3)
    assert queue.committed() == 3
    # non-destructive reads: earlier offsets still readable
    assert queue.read_from(1, 1)[0].body == b"m1"
    with pytest.raises(ValueError):
        queue.commit(2)  # backwards
    with pytest.raises(ValueError):
        queue.commit(99)  # past end


def test_poll_batch_returns_early_when_full(queue):
    for i in range(4):
        queue.publish(f"m{i}".encode())
    t0 = time.monotonic()
    msgs = queue.poll_batch(4, max_wait_s=5.0)
    assert len(msgs) == 4
    assert time.monotonic() - t0 < 1.0  # did not wait for the deadline


def test_poll_batch_times_out_partial(queue):
    queue.publish(b"only")
    msgs = queue.poll_batch(8, max_wait_s=0.05)
    assert [m.body for m in msgs] == [b"only"]


def test_poll_batch_wakes_on_publish(queue):
    def later():
        time.sleep(0.05)
        queue.publish(b"late")

    t = threading.Thread(target=later)
    t.start()
    msgs = queue.poll_batch(1, max_wait_s=5.0)
    t.join()
    assert [m.body for m in msgs] == [b"late"]


# --- what ends a poll_batch wait (ISSUE 37) --------------------------------
# A colwire frame is a whole batch already and ends the wait at once; one-order
# and one-event messages keep the window (max_n or max_wait_s). Each case takes
# a queue and runs under every backend here and under AMQP (test_amqp.py). The
# only wall-clock bounds are the kept tests' own: under 1 s against a 5 s wait.


def _order(oid):
    return Order(
        uuid="7", oid=oid, symbol="eth2usdt", side=Side.BUY,
        price=99_500_000, volume=1_000_000,
    )


def _frame(oid="f1"):
    from gome_tpu.bus.colwire import encode_orders, is_frame

    body = encode_orders([_order(oid)])
    assert is_frame(body)
    return body


def _single(oid):
    return encode_order(_order(oid))


def _poll_returns(queue) -> dict:
    from gome_tpu.utils.metrics import REGISTRY

    counts = {
        end: REGISTRY.counter(
            "gome_bus_poll_returns_total",
            labels={"queue": queue.name, "ended_by": end},
        ).value()
        for end in ("batch", "full", "deadline")
    }
    assert queue.poll_returns() == counts  # the registry's, by name
    return counts


def _timed_poll(queue, max_n, max_wait_s):
    """(bodies, seconds, which ending's counter moved) of one poll_batch."""
    before = _poll_returns(queue)
    t0 = time.monotonic()
    msgs = queue.poll_batch(max_n, max_wait_s=max_wait_s)
    took = time.monotonic() - t0
    moved = {
        end: n - before[end] for end, n in _poll_returns(queue).items()
        if n != before[end]
    }
    return [m.body for m in msgs], took, moved


def _publish_later(queue, *bodies):
    queue.end_offset()  # (AMQP: start the consume loop first)

    def later():
        time.sleep(0.05)
        for body in bodies:
            queue.publish(body)

    t = threading.Thread(target=later)
    t.start()
    return t


def frame_published_into_a_wait_ends_it(queue):
    frame = _frame()
    t = _publish_later(queue, frame)
    bodies, took, moved = _timed_poll(queue, 8, 5.0)
    t.join()
    assert bodies == [frame]
    assert took < 1.0  # did not wait for the deadline, nor for seven more
    assert moved == {"batch": 1} and queue.poll_ended_by == "batch"


def frame_already_there_returns_at_once(queue):
    frame = _frame()
    queue.publish(frame)
    bodies, took, moved = _timed_poll(queue, 8, 5.0)
    assert bodies == [frame] and took < 1.0
    assert moved == {"batch": 1}


def single_messages_wait_out_the_window(queue):
    queue.publish(_single("j0"))
    bodies, took, moved = _timed_poll(queue, 8, 0.2)
    assert bodies == [_single("j0")]
    assert took >= 0.2  # the window is theirs: it was not cut short
    assert moved == {"deadline": 1} and queue.poll_ended_by == "deadline"


def single_messages_return_early_at_max_n(queue):
    for i in range(4):
        queue.publish(_single(f"j{i}"))
    bodies, took, moved = _timed_poll(queue, 4, 5.0)
    assert bodies == [_single(f"j{i}") for i in range(4)] and took < 1.0
    assert moved == {"full": 1} and queue.poll_ended_by == "full"


def mixed_read_returns_at_the_frame_in_order(queue):
    sent = [_single("j0"), _single("j1"), _frame(), _single("j2")]
    for body in sent:
        queue.publish(body)
    assert queue.end_offset() == 4  # (AMQP: all four have arrived)
    bodies, took, moved = _timed_poll(queue, 8, 5.0)
    assert bodies == sent and took < 1.0
    assert moved == {"batch": 1}


def frame_joins_the_single_messages_already_waiting(queue):
    queue.publish(_single("j0"))
    assert queue.end_offset() == 1
    t = _publish_later(queue, _frame())
    bodies, took, moved = _timed_poll(queue, 8, 5.0)
    t.join()
    assert bodies == [_single("j0"), _frame()] and took < 1.0
    assert moved == {"batch": 1}


def each_ending_counts_once_a_poll_and_empty_ones_never(queue):
    before = _poll_returns(queue)
    assert queue.poll_batch(8, max_wait_s=0.01) == []  # empty: not counted
    for body in (_frame("a"), _frame("b"), _frame("c")):
        queue.publish(body)
    assert queue.end_offset() == 3
    assert len(queue.poll_batch(8, max_wait_s=5.0)) == 3  # once, not thrice
    queue.commit(3)
    for i in range(5):
        queue.publish(_single(f"j{i}"))
    assert queue.end_offset() == 8
    assert len(queue.poll_batch(2, max_wait_s=5.0)) == 2
    queue.commit(5)
    assert len(queue.poll_batch(8, max_wait_s=0.05)) == 3
    queue.commit(8)
    assert queue.poll_batch(8, max_wait_s=0) == []
    after = _poll_returns(queue)
    assert {end: after[end] - before[end] for end in after} == {
        "batch": 1, "full": 1, "deadline": 1,
    }


POLL_RULE_CASES = [
    frame_published_into_a_wait_ends_it,
    frame_already_there_returns_at_once,
    single_messages_wait_out_the_window,
    single_messages_return_early_at_max_n,
    mixed_read_returns_at_the_frame_in_order,
    frame_joins_the_single_messages_already_waiting,
    each_ending_counts_once_a_poll_and_empty_ones_never,
]


@pytest.mark.parametrize("case", POLL_RULE_CASES, ids=lambda f: f.__name__)
def test_poll_batch_wait_ends_by_rule(queue, case):
    case(queue)


def test_file_queue_survives_reopen(tmp_path):
    base = str(tmp_path / "q")
    q = FileQueue("q", base)
    for i in range(10):
        q.publish(f"msg-{i}".encode())
    q.commit(4)
    q.close()

    q2 = FileQueue("q", base)
    assert q2.end_offset() == 10
    assert q2.committed() == 4
    assert q2.read_from(4, 2)[0].body == b"msg-4"
    # and it keeps appending after the existing tail
    q2.publish(b"post-restart")
    assert q2.read_from(10, 1)[0].body == b"post-restart"


def test_file_queue_truncates_torn_tail(tmp_path):
    base = str(tmp_path / "q")
    q = FileQueue("q", base)
    q.publish(b"whole")
    q.close()
    with open(base + ".log", "ab") as f:
        f.write(b"\x00\x00\x00\xff partial")  # length says 255, body short
    q2 = FileQueue("q", base)
    assert q2.end_offset() == 1
    assert q2.read_from(0, 9)[0].body == b"whole"


# --- which file queue looks at its log (ISSUE 39) ---------------------------
# The log has one writer per queue. An object whose last write to the log was
# an append is that writer and answers from its index with no system call; an
# object that has not appended tails another process's log and looks (one
# stat) at every read. gome_bus_log_looks_total{queue=} counts the looks.


def _log_looks(queue) -> int:
    from gome_tpu.utils.metrics import REGISTRY

    n = REGISTRY.counter(
        "gome_bus_log_looks_total", labels={"queue": queue.name}
    ).value()
    assert queue.log_looks() == n  # the registry's, by name
    return n


class _StatCount:
    """Counts os.stat (so os.path.getsize / exists too) of one path and every
    os.fstat, through a patched os."""

    def __init__(self, monkeypatch, path):
        self.n = 0
        stat, fstat = os.stat, os.fstat

        def counted_stat(p, *a, **kw):
            if p == path:
                self.n += 1
            return stat(p, *a, **kw)

        def counted_fstat(fd):
            self.n += 1
            return fstat(fd)

        monkeypatch.setattr(os, "stat", counted_stat)
        monkeypatch.setattr(os, "fstat", counted_fstat)

    def during(self, call) -> int:
        before = self.n
        call()
        return self.n - before


def _written_log(tmp_path, n=3):
    """A FileQueue opened on a log of `n` records that another object wrote,
    everything committed (so a poll of it is idle)."""
    base = str(tmp_path / "q")
    first = FileQueue("q", base)
    for i in range(n):
        first.publish(f"msg-{i}".encode())
    first.commit(n)
    first.close()
    return FileQueue("q", base), base + ".log"


LOOK_CASES = {
    "read_from_empty": lambda q: q.read_from(q.committed(), 8),
    "read_from_nonempty": lambda q: q.read_from(0, 2),
    "end_offset": lambda q: q.end_offset(),
    "depth": lambda q: q.depth(),
    "idle_poll_batch": lambda q: q.poll_batch(8, max_wait_s=0.01),
}


@pytest.mark.parametrize("case", LOOK_CASES)
def test_file_queue_looks_at_its_log_only_until_it_writes(
    tmp_path, monkeypatch, case
):
    q, log_path = _written_log(tmp_path)
    stats = _StatCount(monkeypatch, log_path)
    call = lambda: LOOK_CASES[case](q)
    # It has not appended: the log may be another process's, so it looks,
    # once a read (a poll makes several reads).
    looks = _log_looks(q)
    made = stats.during(call)
    assert made == _log_looks(q) - looks
    if case == "idle_poll_batch":
        assert made >= 1
    else:
        assert made == 1
    # Its first append makes it the log's writer: no read asks again.
    q.publish(b"mine")
    q.commit(q.end_offset())
    looks = _log_looks(q)
    assert stats.during(call) == 0
    assert _log_looks(q) == looks
    # And the answers are still the log's.
    assert q.end_offset() == 4 and q.depth() == 0
    assert [m.body for m in q.read_from(2, 8)] == [b"msg-2", b"mine"]
    assert stats.n == made


def test_file_queue_writer_truncated_and_publishing_again(tmp_path, monkeypatch):
    base = str(tmp_path / "q")
    q = FileQueue("q", base)
    for i in range(5):
        q.publish(f"old-{i}".encode())
    q.commit(2)
    stats = _StatCount(monkeypatch, base + ".log")
    q.truncate_to(3)  # recovery's: the tail is published anew by the replay
    assert q.end_offset() == 3 and q.depth() == 1
    assert [q.publish(f"new-{i}".encode()) for i in range(2)] == [3, 4]
    looks, made = _log_looks(q), stats.n
    assert [m.body for m in q.read_from(0, 99)] == [
        b"old-0", b"old-1", b"old-2", b"new-0", b"new-1",
    ]
    assert q.end_offset() == 5 and q.read_from(5, 9) == []
    assert _log_looks(q) == looks and stats.n == made  # the writer again
    q.close()
    # What it left on the disk is what it said it held.
    q2 = FileQueue("q", base)
    assert [m.body for m in q2.read_from(0, 99)] == [
        b"old-0", b"old-1", b"old-2", b"new-0", b"new-1",
    ]
    assert q2.committed() == 2


def test_file_queue_torn_tail_then_writer(tmp_path, monkeypatch):
    q, log_path = _written_log(tmp_path, n=2)
    q.close()
    whole = os.path.getsize(log_path)
    with open(log_path, "ab") as f:
        f.write(b"\x00\x00\x00\xff partial")  # length says 255, body short
    q2 = FileQueue("q", log_path[: -len(".log")])
    assert os.path.getsize(log_path) == whole  # truncated at open
    stats = _StatCount(monkeypatch, log_path)
    looks = _log_looks(q2)
    assert q2.end_offset() == 2  # not yet the writer: it looks
    assert stats.n == 1 and _log_looks(q2) == looks + 1
    assert q2.publish(b"after") == 2  # lands where the torn record was cut
    assert q2.end_offset() == 3
    assert [m.body for m in q2.read_from(1, 9)] == [b"msg-1", b"after"]
    assert q2.poll_batch(8, max_wait_s=0.01, start=3) == []
    assert stats.n == 1 and _log_looks(q2) == looks + 1  # stopped looking


def test_file_queue_pollers_race_the_first_append(tmp_path):
    """The one-process venue: two threads poll one object while a third makes
    it the log's writer. No record is missed or read twice at the change from
    looking to knowing, and once it is the writer nobody looks again."""
    q = FileQueue("q", str(tmp_path / "q"))
    n, seen, stop = 200, {"a": [], "b": []}, threading.Event()

    def poller(name):
        at = 0
        while at < n and not stop.is_set():
            for m in q.poll_batch(8, max_wait_s=0.002, start=at):
                seen[name].append((m.offset, m.body))
                at = m.offset + 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=poller, args=(k,)) for k in seen]
        for t in threads:
            t.start()
        time.sleep(0.02)  # both are looking at an empty log
        assert q.publish(b"m-0") == 0
        looks = _log_looks(q)
        for i in range(1, n):
            q.publish(f"m-{i}".encode())
        for t in threads:
            t.join(timeout=30)
        stop.set()
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    want = [(i, f"m-{i}".encode()) for i in range(n)]
    assert seen == {"a": want, "b": want}
    assert _log_looks(q) == looks


def test_file_queue_reader_sees_another_objects_appends(tmp_path):
    """The split topology: one object appends, another (a second process's)
    only reads. Nobody notifies the reader, so it looks at every read and
    finds each append at its next one."""
    base = str(tmp_path / "q")
    writer, reader = FileQueue("q", base), FileQueue("q", base)
    for i in range(3):
        assert reader.end_offset() == i
        writer.publish(f"w-{i}".encode())
        looks = _log_looks(reader)
        assert [m.body for m in reader.read_from(i, 8)] == [f"w-{i}".encode()]
        assert _log_looks(reader) == looks + 1


def test_make_bus_topology(tmp_path):
    bus = make_bus(BusConfig(backend="file", dir=str(tmp_path / "bus")))
    assert bus.order_queue.name == "doOrder"  # rabbitmq.go queue names
    assert bus.match_queue.name == "matchOrder"
    bus.order_queue.publish(b"x")
    assert bus.match_queue.end_offset() == 0  # independent queues


def test_native_python_on_disk_interop(tmp_path):
    """The native and Python file queues share one on-disk format: a
    directory written by either reopens correctly under the other,
    including committed offsets and truncation."""
    from gome_tpu.bus.native import NativeFileQueue, native_available

    if not native_available():
        pytest.skip("native toolchain unavailable")
    base = str(tmp_path / "q")
    # Python writes -> native reads
    q = FileQueue("q", base)
    for i in range(6):
        q.publish(f"py-{i}".encode())
    q.commit(2)
    q.close()
    nq = NativeFileQueue("q", base)
    assert nq.end_offset() == 6 and nq.committed() == 2
    assert [m.body for m in nq.read_from(2, 2)] == [b"py-2", b"py-3"]
    # native appends + truncates -> Python reads
    nq.publish_batch([b"c-0", b"c-1", b"c-2"])
    nq.truncate_to(8)
    nq.close()
    q2 = FileQueue("q", base)
    assert q2.end_offset() == 8
    assert q2.read_from(6, 2)[0].body == b"c-0"
    assert q2.read_from(7, 1)[0].body == b"c-1"


def test_native_batch_publish_and_recovery(tmp_path):
    from gome_tpu.bus.native import NativeFileQueue, native_available

    if not native_available():
        pytest.skip("native toolchain unavailable")
    base = str(tmp_path / "q")
    nq = NativeFileQueue("q", base)
    first = nq.publish_batch([b"a" * 10, b"b" * 100, b"c"])
    assert first == 0 and nq.end_offset() == 3
    nq.commit(3)
    nq.close()
    # torn tail: native scanner truncates it away on reopen
    with open(base + ".log", "ab") as f:
        f.write(b"\x00\x00\x01\x00 torn")
    nq2 = NativeFileQueue("q", base)
    assert nq2.end_offset() == 3 and nq2.committed() == 3
    assert nq2.read_from(1, 1)[0].body == b"b" * 100
    nq2.close()


def test_order_codec_roundtrip():
    order = Order(
        uuid="7",
        oid="o123",
        symbol="eth2usdt",
        side=Side.SALE,
        price=99_500_000,
        volume=1_000_000,
        action=Action.DEL,
    )
    assert decode_order(encode_order(order)) == order


def test_order_codec_reference_shape():
    # Go-marshalled OrderNode JSON (exported field names, extra Redis-key
    # fields present) must decode; unknown fields ignored.
    body = (
        b'{"Action":1,"Uuid":"2","Oid":"11","Symbol":"eth2usdt",'
        b'"Transaction":0,"Price":50000000,"Volume":3000000,'
        b'"Accuracy":8,"NodeName":"eth2usdt:node:11","IsFirst":false}'
    )
    order = decode_order(body)
    assert order.action is Action.ADD
    assert order.side is Side.BUY
    assert order.price == 50_000_000
    assert order.order_type is OrderType.LIMIT  # absent Kind => LIMIT


def test_match_result_codec_roundtrip():
    snap = lambda oid, vol: OrderSnapshot(
        uuid="u", oid=oid, symbol="s", side=Side.BUY, price=100, volume=vol
    )
    mr = MatchResult(node=snap("t", 0), match_node=snap("m", 5), match_volume=5)
    rt = decode_match_result(encode_match_result(mr))
    assert rt == mr
    assert not rt.is_cancel
    cancel = MatchResult(node=snap("c", 7), match_node=snap("c", 7), match_volume=0)
    assert decode_match_result(encode_match_result(cancel)).is_cancel
