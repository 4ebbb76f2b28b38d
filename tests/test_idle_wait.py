"""How an idle reader of a queue waits (ISSUE 42): a queue object that hears
its publisher lets its reader sleep on the publish's own notify, with no
wake-up lost between the look at the end offset and the wait; one that does
not hear it (a file log another process writes, an AMQP broker) keeps the
reader on timed looks; wake() ends either; every ending is counted once.
"""

from __future__ import annotations

import random
import sys
import threading
import time
import uuid

import pytest

from gome_tpu.bus import FileQueue, MemoryQueue, base

BOUND_S = 30.0  # far above every limit below: a lost wake-up reads as this
HEARING = ["memory", "file", "cfile"]


def _name() -> str:
    """The counters are the process's, by the queue's name."""
    return f"q-{uuid.uuid4().hex[:8]}"


def _open(kind: str, tmp_path, name: str | None = None):
    name = name or _name()
    if kind == "memory":
        return MemoryQueue(name)
    if kind == "cfile":
        from gome_tpu.bus.native import NativeFileQueue, native_available

        if not native_available():
            pytest.skip("native toolchain unavailable")
        return NativeFileQueue(name, str(tmp_path / name), fsync=False)
    return FileQueue(name, str(tmp_path / name), fsync=False)


@pytest.fixture(params=HEARING)
def writer(request, tmp_path):
    """A queue object that has appended: its log's writer (memory: always)."""
    q = _open(request.param, tmp_path)
    q.publish(b"first")
    return q


def _timed_wait(q, start, bound_s=BOUND_S):
    t0 = time.monotonic()
    by = q.wait_idle(start, bound_s)
    return by, time.monotonic() - t0


def _waiting(q, start, bound_s=BOUND_S):
    """A thread inside q.wait_idle, and where its (woken_by, seconds) go."""
    out = []
    t = threading.Thread(
        target=lambda: out.append(_timed_wait(q, start, bound_s)), daemon=True)
    t.start()
    time.sleep(0.05)  # it sleeps by now
    assert t.is_alive()
    return t, out


def test_no_wake_up_is_lost_between_the_look_and_the_wait(writer):
    """5,000 publish-then-read hand-offs between two threads, the publisher
    pausing 0-2 ms so that its publish falls anywhere in the reader's pass.
    With the bound at 30 s a publish that slipped between the reader's look
    and its wait would be read 30 s late: every read comes within a second,
    and all but a scheduler's outliers (at most 4 in 5,000) within 50 ms."""
    q, n = writer, 5000
    first = q.end_offset()
    published = [0.0] * n
    late = [0.0] * n

    def publisher():
        rng = random.Random(42)
        for i in range(n):
            time.sleep(rng.random() * 0.002)
            published[i] = time.monotonic()
            q.publish(b"m")

    def reader():
        at = first
        while at < first + n:
            q.wait_idle(at, BOUND_S)
            now = time.monotonic()
            for m in q.read_from(at, 64):
                late[m.offset - first] = now - published[m.offset - first]
                at = m.offset + 1

    threads = [threading.Thread(target=f, daemon=True)
               for f in (reader, publisher)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # a switch between any two bytecodes
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    late.sort()
    assert late[-1] < 1.0, late[-5:]
    assert late[-5] < 0.05, late[-5:]
    woken = q.idle_wakeups()
    assert woken["timer"] == 0 and woken["wake"] == 0
    assert 0 < woken["publish"] <= n  # once a wake-up, never per message


def test_a_publish_between_the_look_and_the_wait_wakes_the_reader(writer):
    """The race itself, staged: the reader's look at the end offset returns
    what it saw before a publish that another thread makes while the look is
    still on its way back. The look is made under the lock the publish's
    notify takes, so the notify cannot come before the reader waits."""
    q = writer
    start = q.end_offset()
    real, publisher = q.end_offset, []

    def look():
        end = real()
        if not publisher:
            publisher.append(threading.Thread(
                target=q.publish, args=(b"slipped in",), daemon=True))
            publisher[0].start()
            time.sleep(0.05)  # appended by now, and held at its notify
        return end

    q.end_offset = look
    by, took = _timed_wait(q, start)
    assert by == "publish" and took < 1.0
    publisher[0].join(timeout=5)
    assert q.idle_wakeups()["publish"] == 1


def test_a_message_that_stands_at_start_ends_the_wait_at_once(writer):
    before = writer.idle_wakeups()
    by, took = _timed_wait(writer, writer.end_offset() - 1)
    assert by == "publish" and took < 0.05
    assert writer.idle_wakeups() == before  # nobody slept, nobody was woken


def test_a_publish_ends_the_wait_and_counts_once(writer):
    before = writer.idle_wakeups()
    t, out = _waiting(writer, writer.end_offset())
    t0 = time.monotonic()
    writer.publish_batch([b"a", b"b", b"c"])
    t.join(timeout=5)
    assert out and out[0][0] == "publish"
    assert time.monotonic() - t0 < 0.5 and out[0][1] < 1.0
    after = writer.idle_wakeups()
    assert after == {**before, "publish": before["publish"] + 1}


def test_wake_ends_the_wait_and_one_given_early_ends_the_next(writer):
    before = writer.idle_wakeups()
    t, out = _waiting(writer, writer.end_offset())
    writer.wake()
    t.join(timeout=5)
    assert out and out[0][0] == "wake" and out[0][1] < 1.0
    writer.wake()  # nobody waits: kept for whoever waits next
    by, took = _timed_wait(writer, writer.end_offset())
    assert by == "wake" and took < 0.05
    after = writer.idle_wakeups()
    assert after == {**before, "wake": before["wake"] + 2}
    # taken: the wait after it sleeps to its bound
    by, took = _timed_wait(writer, writer.end_offset(), bound_s=0.05)
    assert by == "timer" and 0.04 < took < 1.0
    assert writer.idle_wakeups()["timer"] == before["timer"] + 1


def _amqp(request, cls):
    from gome_tpu.bus.fakebroker import FakeBroker

    broker = FakeBroker().start()
    request.addfinalizer(broker.stop)
    q = cls(_name(), port=broker.port)
    request.addfinalizer(q.close)
    return q


@pytest.fixture(params=["file reader", "file truncated", "cfile truncated",
                        "cfile reader", "amqp", "supervised amqp"])
def deaf(request, tmp_path):
    """A queue object that cannot hear its publisher, by its own history."""
    from gome_tpu.bus.amqp import AmqpQueue, SupervisedAmqpQueue

    kind, _, how = request.param.partition(" ")
    if kind == "amqp":
        q = _amqp(request, AmqpQueue)
        q.publish(b"its own publish goes to the broker and comes back")
        return q
    if kind == "supervised":
        return _amqp(request, SupervisedAmqpQueue)
    name = _name()
    if how == "reader":  # opened on what another object wrote, never appended
        seed = _open(kind, tmp_path, name)
        seed.publish_batch([b"a", b"b"])
        seed.close()
        return _open(kind, tmp_path, name)
    q = _open(kind, tmp_path, name)
    q.publish_batch([b"a", b"b", b"c"])
    q.truncate_to(1)  # recovery's: it proves itself again at its next append
    return q


def test_a_queue_that_cannot_hear_its_publisher_looks_on_its_timer(deaf):
    """Its wait is one timed look, whatever the bound; a loop round it wakes
    about a thousand times a second of idleness, as before this PR."""
    before = deaf.idle_wakeups()
    end = deaf.end_offset()
    by, took = _timed_wait(deaf, end)
    assert by == "timer" and took < 0.5
    t0 = time.monotonic()
    looks = 0
    while time.monotonic() - t0 < 0.2:
        assert deaf.wait_idle(end, BOUND_S) == "timer"
        looks += 1
    assert looks >= 20  # 200 on an idle machine
    after = deaf.idle_wakeups()
    assert after == {**before, "timer": before["timer"] + 1 + looks}


def test_a_pure_reader_finds_another_objects_append_at_its_next_look(tmp_path):
    name = _name()
    log_writer = _open("file", tmp_path, name)
    reader = _open("file", tmp_path, name)
    seen = []

    def tail():
        while not seen:
            reader.wait_idle(0, BOUND_S)
            seen.extend(reader.read_from(0, 8))
        seen.append(time.monotonic())

    t = threading.Thread(target=tail, daemon=True)
    t.start()
    time.sleep(0.05)
    t0 = time.monotonic()
    log_writer.publish(b"from another process")
    t.join(timeout=5)
    assert seen[0].body == b"from another process"
    assert seen[-1] - t0 < 0.5
    assert reader.idle_wakeups()["publish"] == 0  # nobody told it


def test_a_file_queue_hears_from_its_first_append_on(tmp_path):
    """The one-process venue's queue: a reader until its first publish (the
    wait is a timed look), the writer after it (the wait lasts to the bound
    or the next publish)."""
    q = _open("file", tmp_path)
    assert _timed_wait(q, 0)[0] == "timer"
    q.publish(b"first")
    t, out = _waiting(q, 1)
    q.publish(b"second")
    t.join(timeout=5)
    assert out and out[0][0] == "publish"


def test_wake_reaches_a_reader_on_timed_looks_too(deaf):
    if not isinstance(deaf, base._Waitable):
        pytest.skip("no condition: its sleeper is up within a look anyway")
    deaf.wake()
    before = deaf.idle_wakeups()
    assert deaf.wait_idle(deaf.end_offset(), BOUND_S) == "wake"
    assert deaf.idle_wakeups()["wake"] == before["wake"] + 1


def test_the_wake_ups_are_on_metrics_by_queue_and_cause(writer):
    from gome_tpu.utils.metrics import REGISTRY

    _timed_wait(writer, writer.end_offset(), bound_s=0.01)
    text = REGISTRY.render()
    for by in ("publish", "wake", "timer"):
        assert (f'gome_bus_idle_wakeups_total{{queue="{writer.name}",'
                f'woken_by="{by}"}}') in text, by
