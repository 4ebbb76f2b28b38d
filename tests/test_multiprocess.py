"""Multi-process deployment over the file bus: a producer process (gateway
role) publishes orders into the shared bus directory; the consumer process
(this one) drains them through the device engine and publishes MatchResults
— the reference's three-process topology with the file bus standing in for
RabbitMQ (MIGRATION.md 'process topology')."""

import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from gome_tpu.bus import decode_match_result, make_bus
from gome_tpu.config import BusConfig
from gome_tpu.engine.orchestrator import MatchEngine
from gome_tpu.engine.book import BookConfig
from gome_tpu.oracle import OracleEngine
from gome_tpu.service.consumer import OrderConsumer
from gome_tpu.utils.streams import doorder_stream

_PRODUCER = r"""
import sys
sys.path.insert(0, {repo!r})
from gome_tpu.bus import encode_order, make_bus
from gome_tpu.config import BusConfig
from gome_tpu.utils.streams import doorder_stream

bus = make_bus(BusConfig(backend="file", dir={busdir!r}))
orders = list(doorder_stream(n=120))
bus.order_queue.publish_batch([encode_order(o) for o in orders])
print(len(orders))
"""


def test_cross_process_file_bus_pipeline(tmp_path):
    busdir = str(tmp_path / "bus")
    out = subprocess.run(
        [sys.executable, "-c", _PRODUCER.format(repo=_REPO, busdir=busdir)],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    n_published = int(out.stdout.strip())

    orders = list(doorder_stream(n=120))  # same stream the producer sent
    oracle = OracleEngine()
    expected = []
    for o in orders:
        expected.extend(oracle.process(o))

    bus = make_bus(BusConfig(backend="file", dir=busdir))
    engine = MatchEngine(BookConfig(cap=64, max_fills=8), n_slots=4)
    for o in orders:
        engine.mark(o)  # gateway-side marks (shared-process pre-pool model)
    consumer = OrderConsumer(engine, bus, batch_n=64)
    drained = consumer.drain()
    assert drained == n_published == len(orders)

    msgs = bus.match_queue.read_from(0, 10_000)
    events = [decode_match_result(m.body) for m in msgs]
    assert events == expected
    engine.batch.verify_books()


_AMQP_PRODUCER = r"""
import sys
sys.path.insert(0, {repo!r})
from gome_tpu.bus import encode_order
from gome_tpu.bus.amqp import AmqpQueue
from gome_tpu.utils.streams import doorder_stream

q = AmqpQueue("doOrder", port={port})
orders = list(doorder_stream(n=120))
for o in orders:
    q.publish(encode_order(o))
q.close()
print(len(orders))
"""


def test_cross_process_amqp_pipeline():
    """The reference's ACTUAL topology: separate producer process speaking
    AMQP 0-9-1 over TCP to the broker; this process consumes, matches, and
    publishes MatchResults back over AMQP — the full rabbitmq.go story with
    the fake broker standing in for RabbitMQ."""
    from gome_tpu.bus import QueueBus
    from gome_tpu.bus.amqp import AmqpQueue
    from gome_tpu.bus.fakebroker import FakeBroker

    broker = FakeBroker().start()
    try:
        out = subprocess.run(
            [
                sys.executable, "-c",
                _AMQP_PRODUCER.format(repo=_REPO, port=broker.port),
            ],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        n_published = int(out.stdout.strip())

        orders = list(doorder_stream(n=120))
        oracle = OracleEngine()
        expected = []
        for o in orders:
            expected.extend(oracle.process(o))

        bus = QueueBus(
            AmqpQueue("doOrder", port=broker.port),
            AmqpQueue("matchOrder", port=broker.port),
        )
        engine = MatchEngine(BookConfig(cap=64, max_fills=8), n_slots=4)
        for o in orders:
            engine.mark(o)
        consumer = OrderConsumer(engine, bus, batch_n=64)
        drained = 0
        import time as _time

        deadline = _time.monotonic() + 30
        while drained < n_published and _time.monotonic() < deadline:
            drained += consumer.run_once()
        assert drained == n_published == len(orders)

        msgs = bus.match_queue.read_from(0, 10_000)
        events = [decode_match_result(m.body) for m in msgs]
        assert events == expected
        engine.batch.verify_books()
        bus.order_queue.close()
        bus.match_queue.close()
    finally:
        broker.stop()


def test_verify_books_catches_corruption():
    import jax
    import numpy as np
    import pytest

    engine = MatchEngine(BookConfig(cap=16, max_fills=4), n_slots=2)
    for o in doorder_stream(n=60):
        engine.mark(o)
        engine.process([o])
    from gome_tpu.engine.batch import BookInvariantError

    engine.batch.verify_books()  # healthy book passes
    # corrupt: swap the top two bid slots' prices on the device copy
    books = jax.device_get(engine.batch.books)
    lane = engine.batch.symbol_lane("eth2usdt")
    assert int(books.count[lane, 0]) >= 2, "stream must leave >=2 resting bids"
    price = np.asarray(books.price).copy()
    price[lane, 0, 0], price[lane, 0, 1] = (
        price[lane, 0, 1] - 1,
        price[lane, 0, 0] + 1,
    )
    engine.batch.books = jax.device_put(books._replace(price=price))
    with pytest.raises(BookInvariantError):
        engine.batch.verify_books()


_RESP_GATEWAY = r"""
import sys
sys.path.insert(0, {repo!r})
from gome_tpu.bus import encode_order, make_bus
from gome_tpu.config import BusConfig
from gome_tpu.engine.prepool import RespPrePool, make_marker
from gome_tpu.persist.resp import RespClient
from gome_tpu.types import Action, Order, Side
from gome_tpu.utils.streams import doorder_stream

pool = RespPrePool(RespClient(port={resp_port}))
mark = make_marker(pool)
bus = make_bus(BusConfig(backend="file", dir={busdir!r}))

orders = list(doorder_stream(n=80))
# The race (SURVEY 2.3.3): the gateway ACCEPTED raced:oid=race (marked it)
# but its DoOrder publish lost the race to a concurrent DeleteOrder
# publish, so the DEL lands in doOrder first.
add = Order(uuid="u9", oid="race", symbol="raced", side=Side.BUY,
            price=3_000_000, volume=7)
delete = Order(uuid="u9", oid="race", symbol="raced", side=Side.BUY,
               price=3_000_000, volume=0, action=Action.DEL)
mark(add)                      # gateway handler marked at accept
for o in orders:
    mark(o)                    # main.go:44-45 (ADDs only)
payloads = [encode_order(delete), encode_order(add)]
payloads += [encode_order(o) for o in orders]
bus.order_queue.publish_batch(payloads)
print(len(payloads))
"""


def test_three_process_prepool_reference_topology(tmp_path):
    """The reference's deployment shape with reference semantics: a marker
    server process (fake Redis speaking RESP2), a gateway process that
    marks the pre-pool THERE and publishes to the file bus, and this
    consumer process which never calls engine.mark — admission state flows
    exclusively through the shared marker store, and the
    cancel-before-consume race drops the queued ADD exactly as
    engine.go:58-62 does."""
    from gome_tpu.engine.prepool import RespPrePool
    from gome_tpu.persist.resp import RespClient
    from gome_tpu.types import Action, Order, Side

    busdir = str(tmp_path / "bus")
    srv = subprocess.Popen(
        [sys.executable, "-m", "gome_tpu.persist.respserver", "--port", "0"],
        stdout=subprocess.PIPE, text=True, cwd=_REPO,
    )
    try:
        ready = srv.stdout.readline().split()
        assert ready and ready[0] == "READY", ready
        resp_port = int(ready[1])

        out = subprocess.run(
            [
                sys.executable, "-c",
                _RESP_GATEWAY.format(
                    repo=_REPO, busdir=busdir, resp_port=resp_port
                ),
            ],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        n_published = int(out.stdout.strip())

        # Consumer process (this one): NO engine.mark anywhere — admission
        # reads the marker server the gateway wrote.
        bus = make_bus(BusConfig(backend="file", dir=busdir))
        engine = MatchEngine(BookConfig(cap=64, max_fills=8), n_slots=4)
        engine.pre_pool = RespPrePool(RespClient(port=resp_port))
        consumer = OrderConsumer(engine, bus, batch_n=64)
        drained = consumer.drain()
        assert drained == n_published

        # Expected stream from the oracle under the same race interleaving.
        oracle = OracleEngine()
        add = Order(uuid="u9", oid="race", symbol="raced", side=Side.BUY,
                    price=3_000_000, volume=7)
        delete = Order(uuid="u9", oid="race", symbol="raced", side=Side.BUY,
                       price=3_000_000, volume=0, action=Action.DEL)
        oracle.pre_pool.add(("raced", "u9", "race"))
        oracle.queue.append(delete)
        oracle.queue.append(add)
        for o in doorder_stream(n=80):
            oracle.submit(o)
        expected = oracle.drain()

        msgs = bus.match_queue.read_from(0, 10_000)
        events = [decode_match_result(m.body) for m in msgs]
        assert events == expected
        # The raced ADD was dropped by admission: never rested anywhere.
        assert engine.stats.dropped_no_prepool == 1
        assert oracle.stats.dropped_no_prepool == 1
        lane = engine.batch.symbol_lane("raced")
        books = engine.batch.lane_books()
        assert int(np.asarray(books.count)[lane].sum()) == 0
        engine.batch.verify_books()
    finally:
        srv.terminate()
        srv.wait(timeout=10)


_CRASH_CONSUMER = r"""
import os
import sys
sys.path.insert(0, {repo!r})
mesh_n = {mesh_n}
if mesh_n:
    # Virtual CPU devices: flag spelling for older jax (read at backend
    # init), config option for newer — same dance as tests/conftest.py.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax
jax.config.update("jax_platforms", "cpu")
if mesh_n:
    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except AttributeError:
        pass
from gome_tpu.bus import make_bus
from gome_tpu.config import BusConfig, PersistConfig
from gome_tpu.engine.book import BookConfig
from gome_tpu.engine.orchestrator import MatchEngine
from gome_tpu.engine.prepool import RespPrePool
from gome_tpu.persist.resp import RespClient
from gome_tpu.persist.snapshot import Persister
from gome_tpu.service.consumer import OrderConsumer

bus = make_bus(BusConfig(backend="file", dir={busdir!r}))
mesh = None
if mesh_n:
    from gome_tpu.parallel import make_mesh
    mesh = make_mesh(mesh_n)
engine = MatchEngine(BookConfig(cap=64, max_fills=8), n_slots=8, mesh=mesh)
engine.pre_pool = RespPrePool(RespClient(port={resp_port}))
persist = Persister(PersistConfig(dir={snapdir!r}, every_n_batches=1))
consumer = OrderConsumer(
    engine, bus, batch_n=1, batch_wait_s=0, match_wire="frame",
    pipeline_depth=2,
)
persist.attach(engine, bus, consumer=consumer)
phase = {phase!r}
if phase == "crash":
    # Drain the first span (2 frames) -> a cut behind a frame -> snapshot.
    consumer.drain()
    persist.wait()
    assert persist.snapshots_taken >= 1, "no snapshot at the cut"
    print("SNAPSHOTTED", flush=True)
    # Now feed two more frames WITHOUT resolving (pipeline depth 2 keeps
    # them in flight: books advanced, marks consumed in the EXTERNAL
    # store, offsets uncommitted, events unpublished) — then die hard.
    consumer.run_once()
    consumer.run_once()
    os.kill(os.getpid(), 9)
else:
    restored = persist.restore_latest()
    print(f"RESTORED {{restored}}", flush=True)
    consumer.drain()
    print("DRAINED", flush=True)
"""


@pytest.mark.parametrize("mesh_n", [0, 4])
def test_cross_process_crash_drill_external_marker_store(tmp_path, mesh_n):
    """VERDICT r3 weak #7 (+r4 #4: mesh_n=4 runs the same drill with the
    consumer's books MESH-SHARDED over 4 virtual devices — snapshot taken
    while sharded, restore into a sharded engine): kill -9 a shard
    consumer mid-pipelined-frame — marker store external (RESP server),
    order log durable (file bus) — restart, and the matchOrder stream
    must be EXACTLY the oracle's.

    The hard part this pins: the dead consumer had already consumed the
    in-flight frames' pre-pool marks in the external store (admission
    HDELs them at feed time), so recovery must re-mark the queued tail
    from the durable order log (Persistence._reconstruct_marks) or the
    replayed ADDs would silently drop as unmarked."""
    import time as _time

    from gome_tpu.bus.colwire import decode_event_frame, encode_orders
    from gome_tpu.engine.prepool import RespPrePool
    from gome_tpu.persist.resp import RespClient
    from gome_tpu.utils.streams import multi_symbol_stream

    busdir = str(tmp_path / "bus")
    snapdir = str(tmp_path / "snaps")
    srv = subprocess.Popen(
        [sys.executable, "-m", "gome_tpu.persist.respserver", "--port", "0"],
        stdout=subprocess.PIPE, text=True, cwd=_REPO,
    )
    try:
        ready = srv.stdout.readline().split()
        assert ready and ready[0] == "READY", ready
        resp_port = int(ready[1])

        # Gateway role (this process): mark every ADD in the external
        # store, publish 5 ORDER frames of mixed flow (cancels included).
        orders = list(
            multi_symbol_stream(n=250, n_symbols=6, seed=33, cancel_prob=0.2)
        )
        pool = RespPrePool(RespClient(port=resp_port))
        from gome_tpu.types import Action

        for o in orders:
            if o.action is Action.ADD:
                pool.add((o.symbol, o.uuid, o.oid))
        bus = make_bus(BusConfig(backend="file", dir=busdir))
        frames = [orders[i : i + 50] for i in range(0, 250, 50)]
        # First span: frames 1-2 (consumed clean + snapshotted).
        for fr in frames[:2]:
            bus.order_queue.publish(encode_orders(fr))

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        crash = subprocess.Popen(
            [
                sys.executable, "-c",
                _CRASH_CONSUMER.format(
                    repo=_REPO, busdir=busdir, resp_port=resp_port,
                    snapdir=snapdir, phase="crash", mesh_n=mesh_n,
                ),
            ],
            stdout=subprocess.PIPE, text=True, cwd=_REPO, env=env,
        )
        line = crash.stdout.readline().strip()
        assert line == "SNAPSHOTTED", line
        # Second span arrives; the consumer feeds 2 frames into the device
        # pipeline and dies mid-flight (frame 5 still queued).
        for fr in frames[2:]:
            bus.order_queue.publish(encode_orders(fr))
        crash.wait(timeout=120)
        assert crash.returncode == -9, crash.returncode

        # Fresh handle: the file bus caches the committed marker at open.
        bus2 = make_bus(BusConfig(backend="file", dir=busdir))
        committed_at_crash = bus2.order_queue.committed()
        assert committed_at_crash == 2, committed_at_crash

        restart = subprocess.run(
            [
                sys.executable, "-c",
                _CRASH_CONSUMER.format(
                    repo=_REPO, busdir=busdir, resp_port=resp_port,
                    snapdir=snapdir, phase="restart", mesh_n=mesh_n,
                ),
            ],
            capture_output=True, text=True, timeout=300, cwd=_REPO, env=env,
        )
        assert restart.returncode == 0, restart.stderr
        assert "RESTORED True" in restart.stdout
        assert "DRAINED" in restart.stdout

        # The full matchOrder stream equals the oracle's, exactly once.
        oracle = OracleEngine()
        for o in orders:
            oracle.submit(o)
        expected = oracle.drain()
        bus3 = make_bus(BusConfig(backend="file", dir=busdir))
        got = []
        for m in bus3.match_queue.read_from(0, 10_000):
            got.extend(decode_event_frame(m.body).to_results())
        assert got == expected
        assert bus3.order_queue.committed() == 5
    finally:
        srv.terminate()
        srv.wait(timeout=10)
