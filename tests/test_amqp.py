"""AMQP 0-9-1 transport (gome_tpu.bus.amqp) against the in-process fake
broker (gome_tpu.bus.fakebroker): the queue contract, at-least-once
redelivery, multi-connection topologies, and the reference-config boot
story (a rabbitmq: config section must boot with or without a broker)."""

import threading
import time

import pytest

from gome_tpu.bus import make_bus
from gome_tpu.bus.amqp import AmqpQueue
from gome_tpu.bus.fakebroker import FakeBroker
from gome_tpu.config import BusConfig, load_config

from test_bus import POLL_RULE_CASES


@pytest.fixture
def broker():
    b = FakeBroker().start()
    yield b
    b.stop()


@pytest.fixture
def queue(broker):
    q = AmqpQueue("doOrder", port=broker.port)
    yield q
    q.close()


# --- the bus contract suite (mirrors tests/test_bus.py) -------------------


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
    assert time.monotonic() - t0 < 1.0


def test_poll_batch_times_out_partial(queue):
    queue.publish(b"only")
    msgs = queue.poll_batch(8, max_wait_s=0.2)
    assert [m.body for m in msgs] == [b"only"]


def test_poll_batch_wakes_on_publish(queue):
    queue.end_offset()  # start the consume loop first

    def later():
        time.sleep(0.05)
        queue.publish(b"late")

    t = threading.Thread(target=later)
    t.start()
    msgs = queue.poll_batch(1, max_wait_s=5.0)
    t.join()
    assert [m.body for m in msgs] == [b"late"]


@pytest.mark.parametrize("case", POLL_RULE_CASES, ids=lambda f: f.__name__)
def test_poll_batch_wait_ends_by_rule(queue, case):
    case(queue)


@pytest.mark.parametrize(
    "backend", ["MemoryQueue", "FileQueue", "NativeFileQueue", "AmqpQueue"]
)
def test_every_waitable_backend_waits_on_its_condition(backend):
    """_Waitable comes before Queue in the bases, or the backend's
    _notify_publish wakes nobody: poll_batch would sleep out Queue's
    poll interval instead (AmqpQueue did, until ISSUE 37)."""
    from gome_tpu.bus import amqp, filelog, memory, native

    cls = {
        "MemoryQueue": memory.MemoryQueue, "FileQueue": filelog.FileQueue,
        "NativeFileQueue": native.NativeFileQueue,
        "AmqpQueue": amqp.AmqpQueue,
    }[backend]
    assert cls._wait_for_publish.__qualname__ == "_Waitable._wait_for_publish"
    assert cls.poll_batch.__qualname__ == "Queue.poll_batch"  # the one rule


def test_large_bodies_split_into_frames(queue):
    big = bytes(range(256)) * 2048  # 512 KB > frame_max
    queue.publish(big)
    msgs = queue.poll_batch(1, max_wait_s=5.0)
    assert msgs[0].body == big


# --- AMQP-specific semantics ---------------------------------------------


def test_publisher_never_steals_from_consumer(broker):
    """A publish-only AmqpQueue must not register a consumer — otherwise
    it would round-robin-steal deliveries from the real consumer."""
    producer = AmqpQueue("doOrder", port=broker.port)
    consumer = AmqpQueue("doOrder", port=broker.port)
    consumer.end_offset()  # starts consuming
    for i in range(10):
        producer.publish(f"m{i}".encode())
    deadline = time.monotonic() + 5
    while consumer.end_offset() < 10 and time.monotonic() < deadline:
        time.sleep(0.01)
    msgs = consumer.read_from(0, 10)
    assert [m.body for m in msgs] == [f"m{i}".encode() for i in range(10)]
    producer.close()
    consumer.close()


def test_unacked_redelivery_on_reconnect(broker):
    """Messages consumed but never committed redeliver to the next
    consumer after the connection dies (broker-side at-least-once)."""
    producer = AmqpQueue("doOrder", port=broker.port)
    c1 = AmqpQueue("doOrder", port=broker.port)
    for i in range(4):
        producer.publish(f"m{i}".encode())
    msgs = c1.poll_batch(4, max_wait_s=5.0)
    assert len(msgs) == 4
    c1.commit(2)  # acks m0, m1; m2, m3 stay unacked
    c1.close()
    time.sleep(0.05)  # broker notices the close, requeues

    c2 = AmqpQueue("doOrder", port=broker.port)
    msgs = c2.poll_batch(2, max_wait_s=5.0)
    assert sorted(m.body for m in msgs) == [b"m2", b"m3"]
    producer.close()
    c2.close()


def test_make_bus_amqp_with_broker(broker):
    bus = make_bus(
        BusConfig(backend="amqp", host="127.0.0.1", port=broker.port)
    )
    assert bus.order_queue.name == "doOrder"
    assert bus.match_queue.name == "matchOrder"
    bus.order_queue.publish(b"x")
    assert bus.order_queue.poll_batch(1, 5.0)[0].body == b"x"
    bus.order_queue.close()
    bus.match_queue.close()


def test_make_bus_amqp_falls_back_without_broker():
    with pytest.warns(RuntimeWarning, match="falling back"):
        bus = make_bus(
            BusConfig(backend="amqp", host="127.0.0.1", port=1)  # nothing there
        )
    bus.order_queue.publish(b"x")  # memory backend works
    assert bus.order_queue.read_from(0, 1)[0].body == b"x"


REFERENCE_YAML = """\
rabbitmq:
  host: 127.0.0.1
  port: {port}
  username: guest
  password: guest
redis:
  host: 127.0.0.1
  port: 6379
  password: ""
grpc:
  host: 127.0.0.1
  port: 0
mysql:
  host: dead
gomengine:
  accuracy: 8
"""


def _write_ref_config(tmp_path, port):
    p = tmp_path / "config.yaml"
    p.write_text(REFERENCE_YAML.format(port=port))
    return str(p)


def test_reference_config_boots_without_broker(tmp_path):
    """VERDICT r1 weak #4: a reference-shaped config.yaml (rabbitmq:
    section selects the amqp backend) must BOOT and match even when no
    broker is listening."""
    from gome_tpu.service import EngineService

    cfg = load_config(_write_ref_config(tmp_path, port=1))
    assert cfg.bus.backend == "amqp"
    with pytest.warns(RuntimeWarning, match="falling back"):
        svc = EngineService(cfg)
    svc.start()
    try:
        from gome_tpu.api import order_pb2 as pb

        r = svc.gateway.DoOrder(
            pb.OrderRequest(
                uuid="u", oid="1", symbol="eth2usdt",
                transaction=pb.SALE, price=1.0, volume=2.0,
            ),
            None,
        )
        assert r.code == 0
        deadline = time.monotonic() + 120  # first CPU compile is slow
        while svc.engine.stats.orders < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert svc.engine.stats.orders == 1
    finally:
        svc.stop()


def test_reference_config_full_amqp_service(tmp_path, broker):
    """The full reference topology over real AMQP framing: gateway
    publishes to doOrder through the broker, the consumer matches, events
    land on matchOrder — with the reference's own config.yaml shape."""
    from gome_tpu.api import order_pb2 as pb
    from gome_tpu.service import EngineService

    cfg = load_config(_write_ref_config(tmp_path, port=broker.port))
    svc = EngineService(cfg)
    svc.start()
    try:
        from gome_tpu.bus.amqp import SupervisedAmqpQueue

        assert isinstance(svc.bus.order_queue, SupervisedAmqpQueue)
        r1 = svc.gateway.DoOrder(
            pb.OrderRequest(uuid="u1", oid="a", symbol="eth2usdt",
                            transaction=pb.SALE, price=1.0, volume=5.0),
            None,
        )
        r2 = svc.gateway.DoOrder(
            pb.OrderRequest(uuid="u2", oid="b", symbol="eth2usdt",
                            transaction=pb.BUY, price=1.0, volume=3.0),
            None,
        )
        assert r1.code == 0 and r2.code == 0
        deadline = time.monotonic() + 120  # first CPU compile is slow
        while svc.engine.stats.fills < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert svc.engine.stats.fills == 1
        # the fill event crossed the broker to matchOrder
        feed_deadline = time.monotonic() + 10
        while (
            svc.feed.events_seen < 1 and time.monotonic() < feed_deadline
        ):
            time.sleep(0.01)
        assert svc.feed.events_seen == 1
    finally:
        svc.stop()
